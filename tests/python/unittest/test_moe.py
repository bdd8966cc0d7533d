"""The routed-expert operators against the plain reference
(``benchmark/reference/hybrid_lm.py``): the router's choice, normalisation
and factor; the expert layer with all experts held; the shares of an
expert-parallel layout adding up to the uncut layer; no token dropped under
the worst imbalance; the counters."""
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


def _routed_counts(p, held):
    """What the router sends each of the first ``held`` experts."""
    idx, _ = get_op("moe_router").fn(
        p["u"], p["l_router_weight"], p["l_router_bias"],
        num_experts=p["up"].shape[0], top_k=K, scale=2.5)
    return np.bincount(np.asarray(idx).ravel())[:held]


def test_the_work_follows_the_tokens_routed_here():
    """The products run over the blocks of 256 rows that hold the landed
    assignments, each held expert's run rounded up to whole blocks (one
    block for an expert with none), not over the room set aside and not
    over every token for every held expert: the ``moe_rows`` counter."""
    p = _layer(5, n=2048, e=64)
    block, aside, most = moe.capacity(2048, K, 64, 4)
    assert (block, aside, most) == (256, 2048 + 1024, 8192 + 1024)
    with telemetry.collect_device_counters() as bag:
        _program(p, held=4, first=0)
    counted = bag.stacked()
    counts = _routed_counts(p, 4)
    landed, _, _, dropped = np.asarray(counted["moe"][0])
    assert landed == counts.sum() and dropped == 0
    want = (np.maximum(-(-counts // 256), 1) * 256).sum()
    assert counted["moe_rows"].shape == (1,)
    assert float(counted["moe_rows"][0]) == want
    assert landed <= want < landed + 4 * 256 and want < aside
    # nothing landing: one block an expert, of rows that hold nothing
    p["l_router_bias"] = jnp.zeros(64).at[jnp.arange(40, 44)].set(10.0)
    with telemetry.collect_device_counters() as bag:
        out = _program(p, held=4, first=0)
    assert float(bag.stacked()["moe_rows"][0]) == 4 * 256
    assert not np.asarray(out).any()


# ---------------------------------------------------- the grouped products
def _rowwise(data, idx, w, up, down, first):
    """Every assignment on its own expert's matrices, ``W[e(i)]`` gathered
    row by row: what the grouped products have to give, and by autodiff
    their gradients."""
    held = up.shape[0]
    local = idx - first
    here = (local >= 0) & (local < held)
    e = jnp.clip(local, 0, held - 1)
    hp = jax.lax.Precision.HIGHEST
    hid = jnp.einsum("nc,nkfc->nkf", data, up[e], precision=hp)
    y = jnp.einsum("nkf,nkcf->nkc", jnp.square(jnp.maximum(hid, 0)),
                   down[e], precision=hp)
    return (jnp.where(here, w, 0.0)[:, :, None] * y).sum(axis=1)


def _poisoned(fn):
    """``fn`` with NaN in every row of the blocks from ``live`` on: what a
    kernel that never wrote them may leave there."""
    def product(x, w, tiles, fill, live, *args, **kw):
        out = fn(x, w, tiles, fill, live, *args, **kw)
        if out.ndim == 3:                       # the transposed product
            return out
        block = x.shape[0] // tiles.shape[0]
        dead = jnp.arange(x.shape[0]) >= live * block
        return jnp.where(dead[:, None], jnp.nan, out)
    return product


def _kernels(monkeypatch, impl):
    """Steer ``moe_experts`` to one implementation of the two products:
    the Pallas kernels in interpret mode, which work a part-empty block in
    halves, or the plain forms, which work it whole; both leave NaN past
    the last block that holds rows."""
    from mxnet_tpu.ops import pallas_kernels as pk
    pair = {"kernel": (functools.partial(pk.grouped_matmul, interpret=True),
                       functools.partial(pk.grouped_matmul_t,
                                         interpret=True)),
            "plain": (moe.grouped_matmul, moe.grouped_matmul_t)}[impl]
    monkeypatch.setattr(moe, "_products", lambda block, data, up: tuple(
        _poisoned(fn) for fn in pair) + (
            block // 2 if impl == "kernel" else block,))


def _assignments(case, n=48, e=64, held=4):
    """(N, K) expert indices, every token's distinct: the held experts'
    runs as ``case`` names them, the rest on absent experts."""
    r = np.random.RandomState(7)
    absent = np.stack([r.choice(np.arange(held, e), K, replace=False)
                       for _ in range(n)])
    idx = absent.copy()
    if case == "uneven":            # 13, 5, 22 and 1 rows
        for expert, rows in enumerate((13, 5, 22, 1)):
            idx[r.choice(n, rows, replace=False), expert] = expert
    elif case == "an_expert_without_rows":
        idx[:20, 0], idx[10:37, 2], idx[5:8, 3] = 0, 2, 3
    elif case == "all_on_the_same_two":  # 96 rows: past the 80 set aside
        idx[:, 0], idx[:, 1] = 1, 2
    elif case == "runs_end_on_a_block_edge":     # blocks of 8 rows
        idx[:16, 0], idx[8:16, 1], idx[:24, 2], idx[40:, 3] = 0, 1, 2, 3
    else:
        assert case == "nothing_lands"
    return jnp.asarray(idx, jnp.int32)


@pytest.mark.parametrize("impl", ["kernel", "plain"])
@pytest.mark.parametrize("case", ["uneven", "an_expert_without_rows",
                                  "all_on_the_same_two",
                                  "runs_end_on_a_block_edge",
                                  "nothing_lands"])
def test_the_grouped_products_are_the_per_row_products(monkeypatch, case,
                                                       impl):
    """Forward and the gradients of the rows, the routing weights and both
    matrices, widths of 16 and 12 (no multiple of 128); nothing of the NaN
    past the last live block reaches any of them."""
    _kernels(monkeypatch, impl)
    assert moe.capacity(N, K, 64, 4) == (8, 48 + 32, 192 + 32)
    p = _layer(11, e=4)
    idx = _assignments(case)
    r = np.random.RandomState(3)
    w = jnp.asarray(r.rand(N, K) + 0.1, jnp.float32)
    t = jnp.asarray(r.randn(N, C), jnp.float32)

    def program(data, w, up, down):
        return get_op("moe_experts").fn(
            data, idx, w, up, down, num_experts=64, experts_held=4,
            first_expert=0, num_hidden=F)

    def reference(data, w, up, down):
        return _rowwise(data, idx, w, up, down, 0)
    args = (p["u"], w, p["up"], p["down"])
    with telemetry.collect_device_counters() as bag:
        got = program(*args)
    want = reference(*args)
    scale = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(got, want, atol=3e-5 * scale, rtol=0)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=64)[:4]
    landed, fullest, absent, dropped = np.asarray(bag.stacked()["moe"][0])
    assert (landed, fullest, dropped) == (counts.sum(), counts.max(), 0)
    # whole blocks of 8 rows; the kernels work a run's last block, and an
    # expert's without rows, in halves
    unit = 4 if impl == "kernel" else 8
    blocks = np.maximum(-(-counts // 8), 1)
    last = counts - 8 * (blocks - 1)
    assert float(bag.stacked()["moe_rows"][0]) == (
        8 * (blocks - 1) + np.maximum(-(-last // unit), 1) * unit).sum()
    grads = [jax.grad(lambda *a: (fn(*a) * t).sum(), argnums=(0, 1, 2, 3))(
        *args) for fn in (program, reference)]
    for name, a, b in zip(("data", "weights", "up", "down"), *grads):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(
            a, b, atol=5e-5 * max(float(jnp.abs(b).max()), 1.0), rtol=0,
            err_msg=name)


@pytest.mark.parametrize("transpose_rhs", [True, False])
def test_the_kernels_tile_a_width_that_is_no_multiple_of_128(transpose_rhs):
    """n = 200 in tiles of 128: the last tile hangs over the edge, in the
    product and in the transposed product; k = 72 is taken whole."""
    from mxnet_tpu.ops import pallas_kernels as pk
    r = np.random.RandomState(5)
    block, k, n, groups = 16, 72, 200, 3
    tiles = jnp.asarray([0, 0, 1, 2, 2, 2, 2, 2], jnp.int32)
    fill = jnp.full(8, block, jnp.int32)
    live = jnp.int32(6)
    x = jnp.asarray(r.randn(8 * block, k), jnp.float32)
    mat = jnp.asarray(r.randn(groups, k, n), jnp.float32)
    rows = np.repeat(np.asarray(tiles), block)[:6 * block]
    want = np.einsum("mk,mkn->mn", np.asarray(x)[:6 * block],
                     np.asarray(mat)[rows])
    for fn, kw in ((pk.grouped_matmul, dict(block_n=128, interpret=True)),
                   (moe.grouped_matmul, {})):
        got = fn(x, jnp.swapaxes(mat, 1, 2) if transpose_rhs else mat,
                 tiles, fill, live, transpose_rhs=transpose_rhs, **kw)
        np.testing.assert_allclose(got[:6 * block], want, atol=1e-4)
    other = jnp.asarray(r.randn(8 * block, n), jnp.float32)
    want = np.stack([np.asarray(x)[:6 * block][rows == g].T
                     @ np.asarray(other)[:6 * block][rows == g]
                     for g in range(groups)])
    for fn, kw in ((pk.grouped_matmul_t, dict(block_n=128, interpret=True)),
                   (moe.grouped_matmul_t, {})):
        np.testing.assert_allclose(
            fn(x, other, tiles, fill, live, groups, **kw), want, atol=1e-4)


def test_the_kernels_work_a_half_empty_block_as_its_lower_half():
    """Blocks of 16 rows holding 16, 3, 8, 9 and 0: the product writes the
    lower 8 rows of the second, third and fifth and leaves their upper
    rows alone; the transposed product leaves those rows out of the sum."""
    from mxnet_tpu.ops import pallas_kernels as pk
    r = np.random.RandomState(6)
    block, k, n = 16, 24, 40
    tiles = jnp.asarray([0, 0, 1, 1, 2, 2], jnp.int32)
    fill = jnp.asarray([16, 3, 8, 9, 0, 0], jnp.int32)
    live = jnp.int32(5)
    x = jnp.asarray(r.randn(6 * block, k), jnp.float32)
    mat = jnp.asarray(r.randn(3, k, n), jnp.float32)
    rows = np.repeat(np.asarray(tiles), block)
    worked = np.concatenate([np.arange(16) < w for w in (16, 8, 8, 16, 8, 0)])
    got = np.asarray(pk.grouped_matmul(x, mat, tiles, fill, live,
                                       interpret=True))
    want = np.einsum("mk,mkn->mn", np.asarray(x), np.asarray(mat)[rows])
    np.testing.assert_allclose(got[worked], want[worked], atol=1e-4)
    assert np.isnan(got[~worked]).all()         # interpret mode's unwritten
    other = jnp.asarray(r.randn(6 * block, n), jnp.float32)
    got = pk.grouped_matmul_t(x, other, tiles, fill, live, 3, interpret=True)
    want = np.stack([np.asarray(x)[worked & (rows == g)].T
                     @ np.asarray(other)[worked & (rows == g)]
                     for g in range(3)])
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_the_tiles_come_from_the_shape_and_the_guard_asks_them():
    from mxnet_tpu.ops import pallas_kernels as pk
    # the cell's experts, bfloat16: the product takes a matrix whole (10 MB,
    # fetched once a run); the transposed product's f32 tile is cut
    assert pk.grouped_blocks(256, 2688, 1856, 2) == (1856, 640)
    assert pk.grouped_blocks(256, 1856, 2688, 2) == (2688, 896)
    assert pk.grouped_available(256, 2688, 1856, 2)
    # the tests' blocks of 8 to 64 rows go to the plain form
    assert not pk.grouped_available(64, 2688, 1856, 2)
    assert not pk.grouped_available(8, 16, 12, 4)
    # a matrix too large for any tile
    assert pk.grouped_blocks(256, 1 << 20, 1856, 2) == (None, None)
    assert not pk.grouped_available(256, 1 << 20, 1856, 2)


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
