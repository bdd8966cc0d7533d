"""The routed-expert operators against the plain reference
(``benchmark/reference/hybrid_lm.py``): the router's choice, normalisation
and factor; the expert layer with all experts held; the shares of an
expert-parallel layout adding up to the uncut layer; no token dropped under
the worst imbalance; the counters."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.ops import moe  # noqa: E402
from mxnet_tpu.ops.registry import get_op  # noqa: E402
from benchmark.reference import hybrid_lm as ref  # noqa: E402
from benchmark.reference.train import Exact  # noqa: E402

N, C, E, K, F = 48, 16, 16, 4, 12


def _layer(seed, n=N, e=E):
    r = np.random.RandomState(seed)
    return {"u": jnp.asarray(r.randn(n, C), jnp.float32),
            "l_router_weight": jnp.asarray(r.randn(e, C) * 0.3, jnp.float32),
            "l_router_bias": jnp.asarray(r.randn(e) * 0.05, jnp.float32),
            "up": jnp.asarray(r.randn(e, F, C) * 0.3, jnp.float32),
            "down": jnp.asarray(r.randn(e, C, F) * 0.3, jnp.float32),
            "l_shared_up_weight": jnp.asarray(r.randn(2 * F, C) * 0.3,
                                              jnp.float32),
            "l_shared_down_weight": jnp.asarray(r.randn(C, 2 * F) * 0.3,
                                                jnp.float32)}


def _cfg(held, first):
    return {"num_experts_per_tok": K, "routed_scaling_factor": 2.5,
            "deployment": {"first_expert": first}, "n_routed_experts": held}


def _reference(p, held=None, first=0, shared=True):
    """The reference's expert layer on experts first .. first + held (all
    of the layer's by default)."""
    held = held or p["up"].shape[0]
    sub = {k: v for k, v in p.items() if k.startswith("l_")
           and (shared or "shared" not in k)}
    sub["l_experts_up_weight"] = p["up"][first:first + held]
    sub["l_experts_down_weight"] = p["down"][first:first + held]
    return ref._experts(sub, p["u"], "l", _cfg(held, first), Exact())


def _program(p, held=None, first=0):
    """moe_router + moe_experts, the routed part alone."""
    e = p["up"].shape[0]
    held = held or e
    idx, w = get_op("moe_router").fn(
        p["u"], p["l_router_weight"], p["l_router_bias"], num_experts=e,
        top_k=K, scale=2.5)
    return get_op("moe_experts").fn(
        p["u"], idx, w, p["up"][first:first + held],
        p["down"][first:first + held], num_experts=e, experts_held=held,
        first_expert=first, num_hidden=F)


def test_the_router_chooses_normalises_and_scales():
    p = _layer(0)
    idx, w = get_op("moe_router").fn(
        p["u"], p["l_router_weight"], p["l_router_bias"], num_experts=E,
        top_k=K, scale=2.5)
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32
    s = 1 / (1 + np.exp(-np.asarray(p["u"]) @ np.asarray(
        p["l_router_weight"]).T))
    sel = s + np.asarray(p["l_router_bias"])
    want = np.argsort(-sel, axis=1)[:, :K]
    assert np.array_equal(np.sort(np.asarray(idx), 1), np.sort(want, 1))
    picked = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(w, picked / picked.sum(1, keepdims=True) * 2.5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.5, rtol=1e-5)
    ridx, rw = ref.route(p["u"], p["l_router_weight"], p["l_router_bias"], K,
                         2.5)
    assert np.array_equal(idx, ridx)
    np.testing.assert_allclose(w, rw, rtol=1e-6)


def test_the_selection_bias_moves_the_choice_and_not_the_weights():
    p = _layer(1)
    fn = get_op("moe_router").fn
    kw = dict(num_experts=E, top_k=K, scale=1.0)
    idx0, w0 = fn(p["u"], p["l_router_weight"], jnp.zeros(E), **kw)
    push = jnp.zeros(E).at[3].set(10.0)          # expert 3 always chosen
    idx1, w1 = fn(p["u"], p["l_router_weight"], push, **kw)
    assert (np.asarray(idx1) == 3).any(axis=1).all()
    assert not (np.asarray(idx0) == 3).any(axis=1).all()
    # the weight of expert 3 is its own sigmoid score, not score + 10
    s = jax.nn.sigmoid(p["u"] @ p["l_router_weight"].T)
    col = np.argmax(np.asarray(idx1) == 3, axis=1)
    got = np.asarray(w1)[np.arange(N), col]
    mass = np.take_along_axis(np.asarray(s), np.asarray(idx1), 1).sum(1)
    np.testing.assert_allclose(got, np.asarray(s)[:, 3] / mass, rtol=1e-5)
    # and it has no gradient
    g = jax.grad(lambda b: fn(p["u"], p["l_router_weight"], b, **kw)[1]
                 .sum())(p["l_router_bias"])
    assert not np.asarray(g).any()
    assert get_op("moe_router").f32_inputs == ("weight", "bias")


def test_all_experts_held_is_the_reference_layer_forward_and_gradients():
    """float32 on both sides, other orders of summation: 2e-5 of the
    largest entry."""
    p = _layer(2)
    want = _reference(p, shared=False)
    got = _program(p)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(
        jnp.abs(want).max()), rtol=0)
    keys = ("u", "l_router_weight", "up", "down")
    t = jnp.asarray(np.random.RandomState(9).randn(N, C), jnp.float32)

    def loss(fn, *vals):
        return (fn(dict(p, **dict(zip(keys, vals)))) * t).sum()
    g_got = jax.grad(lambda *v: loss(_program, *v), argnums=(0, 1, 2, 3))(
        *[p[k] for k in keys])
    g_want = jax.grad(lambda *v: loss(
        lambda q: _reference(q, shared=False), *v), argnums=(0, 1, 2, 3))(
        *[p[k] for k in keys])
    for k, a, b in zip(keys, g_got, g_want):
        np.testing.assert_allclose(a, b, atol=5e-5 * float(jnp.abs(b).max()),
                                   rtol=0, err_msg=k)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 shares of 4: the four partial results, with the
    shared expert (which every chip computes alike) counted once, are the
    uncut layer's; in the program and in the reference."""
    p = _layer(3)
    whole = _reference(p)                           # routed + shared
    shared = whole - _reference(p, shared=False)
    parts = [_program(p, held=4, first=4 * j) for j in range(4)]
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=3e-5 * float(
        jnp.abs(whole).max()), rtol=0)
    ref_parts = [_reference(p, held=4, first=4 * j, shared=False)
                 for j in range(4)]
    for a, b in zip(parts, ref_parts):
        np.testing.assert_allclose(a, b, atol=3e-5 * float(
            jnp.abs(whole).max()), rtol=0)
    # a share is a part, not the whole
    assert float(jnp.abs(parts[0] - (whole - shared)).max()) > 0.01


@pytest.mark.parametrize("held,first", [(4, 0), (4, 8), (16, 0)])
def test_no_token_is_dropped_when_every_token_chooses_the_same_expert(
        held, first):
    """Of 64 experts, a bias that sends every token to expert 9 (and 10, 11,
    12): the fullest expert holds all N tokens, 16 times the mean load, and
    a share of four that holds three of them gets 12 times its mean, past
    the rows set aside: the other path takes the step."""
    p = _layer(4, e=64)
    assert moe.capacity(N, K, 64, 4) == (8, 48 + 32, 192 + 32)
    p["l_router_bias"] = jnp.zeros(64).at[jnp.arange(9, 13)].set(10.0)
    with telemetry.collect_device_counters() as bag:
        got = _program(p, held=held, first=first)
    want = _reference(p, held=held, first=first, shared=False)
    np.testing.assert_allclose(got, want, atol=3e-5 * max(float(
        jnp.abs(want).max()), 1.0), rtol=0)
    landed, fullest, absent, dropped = np.asarray(bag.stacked()["moe"][0])
    here = len(set(range(first, first + held)) & {9, 10, 11, 12})
    assert landed == N * here and absent == N * (K - here)
    assert fullest == (N if here else 0) and dropped == 0


def test_the_capacity():
    # 4096 tokens x 6 of 128, 8 held: 1536 assignments land here on
    # average; blocks of 256; four times the mean set aside, and every
    # assignment, each with a part-empty last block for every held expert
    assert moe.capacity(4096, 6, 128, 8) == (256, 6144 + 2048, 24576 + 2048)
    assert moe.capacity(2048, 2, 64, 4) == (64, 1024 + 256, 4096 + 256)
    # a quarter of the experts or more held: four times the mean is all
    assert moe.capacity(48, 4, 16, 4) == (16, 192 + 64, 192 + 64)
    assert moe.capacity(8, 2, 4, 4) == (8, 16 + 32, 16 + 32)


def test_the_work_follows_the_tokens_routed_here():
    """The products run over blocks of 256 rows, one expert's each, that in
    all hold the assignments landing here, not over every token for every
    held expert: read off the jaxpr's dot shapes and trip counts."""
    p = _layer(5, n=2048, e=64)
    text = str(jax.make_jaxpr(lambda q: _program(q, held=4, first=0))(p))
    block, aside, most = moe.capacity(2048, K, 64, 4)
    assert (block, aside, most) == (256, 2048 + 1024, 8192 + 1024)
    assert "f32[256,%d] = dot_general" % F in text
    assert "f32[2048,%d] = dot_general" % F not in text
    assert "length=%d" % (aside // 256) in text \
        and "length=%d" % (most // 256) in text


def test_the_counters_reach_telemetry_from_a_train_step():
    from mxnet_tpu.train import TrainStep
    data = mx.sym.Variable("data")
    route = mx.sym.moe_router(data, num_experts=E, top_k=K, name="r")
    y = mx.sym.moe_experts(data, route[0], route[1], num_experts=E,
                           experts_held=4, first_expert=4, num_hidden=F,
                           name="e")
    net = mx.sym.LinearRegressionOutput(y, mx.sym.Variable("label"))
    ts = TrainStep(net, mx.optimizer.create("sgd", learning_rate=0.01),
                   data_names=("data",), label_names=("label",))
    assert sorted(ts.param_names) == ["e_down_weight", "e_up_weight",
                                      "r_bias", "r_weight"]
    params, state, aux = ts.init({"data": (N, C)}, {"label": (N, C)})
    assert params["e_up_weight"].shape == (4, F, C)
    r = np.random.RandomState(0)
    batch = {"data": jnp.asarray(r.randn(3, N, C), jnp.float32),
             "label": jnp.asarray(r.randn(3, N, C), jnp.float32)}
    bias0 = np.asarray(params["r_bias"])
    params, state, aux, outs = ts.run_steps(params, state, aux, batch, 2,
                                            stacked=True)
    assert len(outs) == 1 and outs[0].shape == (N, C)
    got, steps = telemetry.device_counters()
    assert steps == 3 and got["moe"].shape == (1, 4)
    landed, fullest, absent, dropped = got["moe"][0]
    assert landed + absent == 3 * N * K and dropped == 0
    assert 0 < fullest <= landed
    # the selection bias is a leaf without a gradient: it does not move
    np.testing.assert_array_equal(np.asarray(params["r_bias"]), bias0)
    params, state, aux, outs = ts(params, state, aux,
                                  {k: v[0] for k, v in batch.items()})
    assert len(outs) == 1 and telemetry.device_counters()[1] == 1
