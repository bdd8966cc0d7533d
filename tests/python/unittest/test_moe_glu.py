"""The routed experts in their gated form (``moe_experts`` with a gate's
matrix, ``act_type`` silu: SwiGLU) against the experts taken one at a time
in plain ``jax.numpy``: forward and every gradient, through both arms of the
``lax.cond``, with an expert that gets no token, no token dropped; the
shares of a 32-chip layout adding up to the uncut layer; the grouped kernels
in interpret mode; and the ungated form left as it was."""
import functools
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

N, C, E, K, F = 64, 16, 64, 4, 12
HP = jax.lax.Precision.HIGHEST


def _layer(seed, n=N, e=E):
    r = np.random.RandomState(seed)
    x = lambda *s: jnp.asarray(r.randn(*s) * 0.3, jnp.float32)  # noqa: E731
    return {"u": x(n, C) / 0.3, "router": x(e, C),
            "bias": jnp.zeros((e,), jnp.float32),
            "gate": x(e, F, C), "up": x(e, F, C), "down": x(e, C, F),
            "shared": (x(F, C), x(F, C), x(C, F))}


def _route(p):
    return get_op("moe_router").fn(p["u"], p["router"], p["bias"],
                                   num_experts=p["router"].shape[0], top_k=K,
                                   scale=2.446)


def _swiglu(u, gate, up, down):
    return jnp.dot(jax.nn.silu(jnp.dot(u, gate.T, precision=HP))
                   * jnp.dot(u, up.T, precision=HP), down.T, precision=HP)


def _by_expert(p, held=None, first=0):
    """The held experts one at a time, each on every token, weighted by the
    token's routing weight for it (zero where it was not chosen)."""
    held = held or p["up"].shape[0]
    idx, w = _route(p)
    out = jnp.zeros_like(p["u"])
    for e in range(first, first + held):
        w_e = jnp.where(idx == e, w, 0.0).sum(axis=1)
        out = out + w_e[:, None] * _swiglu(p["u"], p["gate"][e], p["up"][e],
                                           p["down"][e])
    return out


def _program(p, held=None, first=0):
    held = held or p["up"].shape[0]
    idx, w = _route(p)
    cut = slice(first, first + held)
    return get_op("moe_experts").fn(
        p["u"], idx, w, p["up"][cut], p["down"][cut], p["gate"][cut],
        num_experts=p["router"].shape[0], experts_held=held,
        first_expert=first, num_hidden=F, act_type="silu", gated=True)


def _close(got, want, tol=3e-5):
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * max(
        float(jnp.abs(want).max()), 1e-3))


KEYS = ("u", "router", "gate", "up", "down")


def _grads(fn, p, **kw):
    t = jnp.asarray(np.random.RandomState(9).randn(*p["u"].shape),
                    jnp.float32)
    return jax.grad(lambda *v: (fn(dict(p, **dict(zip(KEYS, v))), **kw)
                                * t).sum(), argnums=tuple(range(len(KEYS))))(
        *[p[k] for k in KEYS])


# name -> (experts held, first, the bias that bends the router)
ARMS = {
    # a share of 8 of 64 under a router left alone: within the rows set aside
    "rows_set_aside": (8, 8, None),
    # every token sent to experts 9-12: a share that holds them lands 16
    # times its mean, past the rows set aside: expert by expert
    "expert_by_expert": (8, 8, (9, 10, 11, 12)),
    # all 64 held: the rows set aside are all there can be, no second arm
    "all_held": (64, 0, None),
}


@pytest.mark.parametrize("arm", sorted(ARMS))
def test_the_gated_experts_are_the_experts_one_at_a_time(arm):
    held, first, bent = ARMS[arm]
    p = _layer(1)
    if bent:
        p["bias"] = p["bias"].at[jnp.asarray(bent)].set(10.0)
    block, aside, most = moe.capacity(N, K, E, held)
    with telemetry.collect_device_counters() as bag:
        got = _program(p, held, first)
    _close(got, _by_expert(p, held, first))
    landed, fullest, absent, dropped = np.asarray(bag.stacked()["moe"][0])
    idx = np.asarray(_route(p)[0])
    here = ((idx >= first) & (idx < first + held)).sum()
    assert landed == here and absent == N * K - here and dropped == 0
    counts = np.bincount(idx.ravel(), minlength=E)[first:first + held]
    # which arm took the step, and an expert with no token in it
    needed = (np.maximum(-(-counts // block), 1) * block).sum()
    assert (needed > aside) == (arm == "expert_by_expert")
    if arm != "all_held":
        assert (counts == 0).any()
    assert fullest == counts.max()
    # rows, not products: the counter counts what one product runs over
    assert float(bag.stacked()["moe_rows"][0]) == needed


@pytest.mark.parametrize("arm", sorted(ARMS))
def test_every_gradient_of_the_gated_experts(arm):
    held, first, bent = ARMS[arm]
    p = _layer(2)
    if bent:
        p["bias"] = p["bias"].at[jnp.asarray(bent)].set(10.0)
    got = _grads(_program, p, held=held, first=first)
    want = _grads(_by_expert, p, held=held, first=first)
    for k, a, b in zip(KEYS, got, want):
        np.testing.assert_allclose(a, b, rtol=0, err_msg=k, atol=1e-4 * max(
            float(jnp.abs(b).max()), 1e-3))
    # an expert held here that got no token has no gradient, and has one
    # where tokens came
    idx = np.asarray(_route(p)[0])
    counts = np.bincount(idx.ravel(), minlength=E)
    for name in ("gate", "up", "down"):
        g = np.asarray(got[KEYS.index(name)])
        for e in range(first, first + held):
            assert bool(np.abs(g[e]).max() > 0) == bool(counts[e]), (name, e)
        assert not np.abs(np.delete(g, np.s_[first:first + held], 0)).any()


def test_the_32_shares_add_up_to_the_uncut_layer():
    """What ties the share to the model: 64 experts over 32 chips of 2
    (held = num / 32, first_expert = 0, 2, ...), each computing its own
    experts' part for the tokens routed to them; the 32 parts, with the
    shared expert (which every chip computes alike) counted once, are the
    uncut layer."""
    p = _layer(3)
    whole = _by_expert(p) + _swiglu(p["u"], *p["shared"])
    parts = [_program(p, held=E // 32, first=j * (E // 32))
             for j in range(32)]
    _close(sum(parts) + _swiglu(p["u"], *p["shared"]), whole)
    for j in (0, 13, 31):
        _close(parts[j], _by_expert(p, held=2, first=2 * j))
    # a share is a part, not the whole
    assert float(jnp.abs(parts[0] - _by_expert(p)).max()) > 0.01
    _close(_program(p), _by_expert(p))


@pytest.mark.parametrize("impl", ["kernel", "plain"])
def test_the_gated_experts_through_the_grouped_kernels(monkeypatch, impl):
    """The Pallas products in interpret mode work a part-empty block as its
    lower half and leave the blocks past the last unwritten: the gated
    forward and backward mask what they must."""
    from mxnet_tpu.ops import pallas_kernels as pk
    pair = {"kernel": (functools.partial(pk.grouped_matmul, interpret=True),
                       functools.partial(pk.grouped_matmul_t,
                                         interpret=True)),
            "plain": (moe.grouped_matmul, moe.grouped_matmul_t)}[impl]
    unit = {"kernel": 2, "plain": 1}[impl]
    monkeypatch.setattr(moe, "_products", lambda block, data, up: (
        pair[0], pair[1], block // unit))
    p = _layer(4, n=512)
    assert moe.capacity(512, K, E, 8)[0] == 32
    _close(_program(p, 8, 16), _by_expert(p, 8, 16))
    got = _grads(_program, p, held=8, first=16)
    want = _grads(_by_expert, p, held=8, first=16)
    for k, a, b in zip(KEYS, got, want):
        np.testing.assert_allclose(a, b, rtol=0, err_msg=k, atol=1e-4 * max(
            float(jnp.abs(b).max()), 1e-3))


def test_in_bfloat16_the_hidden_rows_are_rounded_once():
    p = _layer(5)
    half = {k: (v.astype(jnp.bfloat16) if k in ("u", "gate", "up", "down")
                else v) for k, v in p.items()}
    got = _program(half, 8, 0)
    assert got.dtype == jnp.bfloat16
    exact = {k: (v.astype(jnp.float32) if k in ("u", "gate", "up", "down")
                 else v) for k, v in half.items()}
    _close(got.astype(jnp.float32), _by_expert(exact, 8, 0), tol=2e-2)


def test_the_symbol_takes_the_gate_as_a_sixth_input():
    def net(gated):
        mats = [mx.sym.Variable(n) for n in ("up", "down", "gate")[:2 + gated]]
        return mx.sym.moe_experts(
            mx.sym.Variable("u"), mx.sym.Variable("idx"), mx.sym.Variable("w"),
            *mats, num_experts=E, experts_held=8, num_hidden=F,
            act_type="silu", name="x", **({"gated": True} if gated else {}))
    args, outs, _ = net(True).infer_shape(u=(N, C), idx=(N, K), w=(N, K))
    shapes = dict(zip(net(True).list_arguments(), args))
    assert shapes["gate"] == shapes["up"] == (8, F, C)
    assert shapes["down"] == (8, C, F) and outs == [(N, C)]
    assert net(False).list_arguments() == ["u", "idx", "w", "up", "down"]


def test_the_ungated_form_is_what_it_was():
    """``down(act(up u))``: no gate, the same two leaves, the same result."""
    p = _layer(6)
    idx, w = _route(p)
    got = get_op("moe_experts").fn(
        p["u"], idx, w, p["up"][:8], p["down"][:8], num_experts=E,
        experts_held=8, num_hidden=F, act_type="silu")
    want = jnp.zeros_like(p["u"])
    for e in range(8):
        w_e = jnp.where(idx == e, w, 0.0).sum(axis=1)
        want = want + w_e[:, None] * jnp.dot(
            jax.nn.silu(jnp.dot(p["u"], p["up"][e].T, precision=HP)),
            p["down"][e].T, precision=HP)
    _close(got, want)
