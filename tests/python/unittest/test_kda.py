"""The gated delta rule with a decay a key channel (``ops/kda.py``): the
chunked form against the recurrence taken token by token, forward and every
gradient, in float32 and bfloat16; at the published rates, at the extreme
where a chunk's running log-decay passes float32's range, at the rule the
benchmark's seed lands; the state carried across chunks; the op's own norms
and gates.  Then the rule's Pallas kernels (``ops/pallas_kernels.py``
``mxtpu_kda_*``) in interpret mode against the plain chunked form, which is
their oracle: forward, every gradient, the carried state, the extreme decay,
the chooser and the guard."""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.ops import kda, pallas_kernels as pk, ssm  # noqa: E402
from mxnet_tpu.ops.registry import get_op  # noqa: E402

B, H, DK, DV = 2, 3, 16, 8


def _recurrence(q, k, v, g, beta):
    """S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    and o_t = S_t^T q_t, one token at a time: q, k, g (B, T, H, dk), v (B, T,
    H, dv), beta (B, T, H) -> (o (B, T, H, dv), the last state)."""
    def head(q, k, v, g, beta):
        def step(s, inp):
            q_t, k_t, v_t, g_t, b_t = inp
            s = jnp.exp(g_t)[:, None] * s
            s = s - b_t * jnp.outer(k_t, k_t @ s) + b_t * jnp.outer(k_t, v_t)
            return s, q_t @ s
        s0 = jnp.zeros((q.shape[-1], v.shape[-1]), jnp.float32)
        last, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
        return o, last
    over_heads = jax.vmap(head, in_axes=(1, 1, 1, 1, 1), out_axes=(1, 0))
    return jax.vmap(over_heads)(q, k, v, g, beta)


# name -> (A (low, high), the step dt (low, high), beta (low, high))
RULES = {"published": ((1.0, 16.0), (0.001, 0.1), (0.05, 0.95)),
         "extreme": ((16.0, 16.0), (0.1, 0.1), (0.3, 0.9)),
         "the_seeds": ((1.0, 1.0), (0.69, 0.69), (0.4, 0.6)),
         "beta_near_0": ((1.0, 4.0), (0.01, 0.05), (1e-4, 1e-3)),
         "beta_near_1": ((1.0, 4.0), (0.01, 0.05), (0.999, 0.9999))}


def _inputs(rule, t, seed=0, dtype=jnp.float32, dims=None):
    """q and k of unit norm (q scaled), v, the log-decay and beta as the
    rule takes them; ``dims`` (B, H, DK, DV) in place of the module's."""
    (a_lo, a_hi), (dt_lo, dt_hi), (b_lo, b_hi) = RULES[rule]
    r = np.random.RandomState(seed)
    B, H, DK, DV = dims or (globals()[n] for n in ("B", "H", "DK", "DV"))

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(r.randn(B, t, H, DK)) * DK ** -0.5
    k = unit(r.randn(B, t, H, DK))
    v = r.randn(B, t, H, DV)
    a = r.uniform(a_lo, a_hi, (H, 1))
    dt = np.exp(r.uniform(np.log(dt_lo), np.log(dt_hi), (B, t, H, DK)))
    beta = r.uniform(b_lo, b_hi, (B, t, H))
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return (f32(q).astype(dtype), f32(k).astype(dtype), f32(v).astype(dtype),
            f32(-a * dt), f32(beta))


def _gap(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return np.abs(got - want).max() / (np.abs(want).max() + 1e-30)


@pytest.mark.parametrize("t,chunk", [(256, 64), (200, 64), (40, 16),
                                     (10, 16)])
@pytest.mark.parametrize("rule", sorted(RULES))
def test_the_chunked_rule_is_the_recurrence(rule, t, chunk):
    """Four whole chunks, a ragged last chunk, less than one chunk."""
    args = _inputs(rule, t)
    got = kda.kda_chunked(*args, chunk)
    want, _ = _recurrence(*args)
    assert got.shape == (B, t, H, DV) and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    assert _gap(got, want) < 2e-5


@pytest.mark.parametrize("rule", sorted(RULES))
def test_every_gradient_of_the_chunked_rule_is_the_recurrences(rule):
    args = _inputs(rule, 150, seed=1)
    weight = jnp.asarray(np.random.RandomState(2).randn(B, 150, H, DV),
                         jnp.float32)
    got = jax.grad(lambda *a: (kda.kda_chunked(*a, 64) * weight).sum(),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: (_recurrence(*a)[0] * weight).sum(),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.isfinite(a).all()), name
        assert _gap(a, b) < 1e-4, (name, _gap(a, b))


def test_a_chunks_running_decay_passes_float32s_range_and_stays_finite():
    """A = 16 and dt = 0.1 cumulate -102 over a chunk of 64: the
    exponential of the NEGATED running sum, which a factored ``exp(G_i)
    exp(-G_j)`` needs, is infinite in float32."""
    q, k, v, g, beta = _inputs("extreme", 192)
    running = np.cumsum(np.asarray(g[:, :64]), axis=1)
    with np.errstate(over="ignore"):
        assert running.min() < -88 and not np.isfinite(
            np.exp(-running.astype(np.float32))).all()
    loss = lambda *a: (kda.kda_chunked(*a, 64) ** 2).sum()  # noqa: E731
    value, grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
        q, k, v, g, beta)
    assert np.isfinite(float(value))
    assert all(bool(jnp.isfinite(x).all()) for x in grads)


def test_the_state_carried_across_chunks_is_a_tenth_of_the_output():
    """At the published rates what a chunk inherits matters: the last chunk
    alone, its entering state left out, is far from the last chunk of the
    whole; and the op's state after T tokens is the recurrence's."""
    args = _inputs("published", 256, seed=3)
    whole = kda.kda_chunked(*args, 64)
    alone = kda.kda_chunked(*(x[:, 192:] for x in args), 64)
    carried = np.linalg.norm(np.asarray(whole[:, 192:] - alone)) \
        / np.linalg.norm(np.asarray(whole[:, 192:]))
    assert carried > 0.1, carried
    # at the seed's rule a token's weight halves every step: nothing carries
    args = _inputs("the_seeds", 256, seed=3)
    whole = kda.kda_chunked(*args, 64)
    alone = kda.kda_chunked(*(x[:, 192:] for x in args), 64)
    assert _gap(alone[:, 8:], whole[:, 200:]) < 1e-2


@pytest.mark.parametrize("rule", ["published", "extreme", "the_seeds"])
def test_in_bfloat16_the_decay_the_solve_and_the_state_stay_float32(rule):
    """bfloat16 operands for the products alone: the gap to the float32
    recurrence of the same (rounded) inputs is bfloat16's, forward and
    every gradient."""
    args = _inputs(rule, 200, seed=4, dtype=jnp.bfloat16)
    exact = tuple(x.astype(jnp.float32) for x in args)
    weight = jnp.asarray(np.random.RandomState(5).randn(B, 200, H, DV),
                         jnp.float32)
    got = kda.kda_chunked(*args, 64)
    assert got.dtype == jnp.float32
    assert _gap(got, _recurrence(*exact)[0]) < 2e-2
    grads = jax.grad(lambda *a: (kda.kda_chunked(*a, 64) * weight).sum(),
                     argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: (_recurrence(*a)[0] * weight).sum(),
                    argnums=(0, 1, 2, 3, 4))(*exact)
    for name, a, b in zip("q k v g beta".split(), grads, want):
        assert _gap(a, b) < 4e-2, (name, _gap(a, b))


def _op_inputs(seed, t, dtype=jnp.float32):
    r = np.random.RandomState(seed)
    x = lambda *s: jnp.asarray(r.randn(*s), jnp.float32).astype(dtype)  # noqa: E731
    step = np.exp(r.uniform(np.log(1e-3), np.log(0.1), (H * DK,)))
    return (x(B, t, H * DK), x(B, t, H * DK), x(B, t, H * DV),
            x(B, t, H * DK) * 0.5, x(B, t, H),
            jnp.asarray(np.log(r.uniform(1, 16, (H,))), jnp.float32),
            jnp.asarray(np.log(np.expm1(step)), jnp.float32))


def _op_by_hand(q, k, v, gate, beta, a_log, dt_bias):
    f32 = jnp.float32
    t = q.shape[1]
    heads = lambda x: x.astype(f32).reshape(B, t, H, -1)  # noqa: E731

    def l2(x):
        return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        heads(gate) + dt_bias.reshape(H, DK))
    o, _ = _recurrence(l2(heads(q)) / np.sqrt(DK), l2(heads(k)), heads(v), g,
                       jax.nn.sigmoid(beta.astype(f32)))
    return o.reshape(B, t, H * DV)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 4e-2)])
def test_the_op_norms_gates_and_takes_the_rule(dtype, tol):
    """``kda_scan``: l2 norms of q and k with q scaled, the decay from
    ``A_log``, the gate and ``dt_bias``, the step from beta's logits, then
    the rule; forward and the gradients of all seven inputs."""
    args = _op_inputs(6, 100, dtype)
    op = lambda *a: get_op("kda_scan").fn(*a, num_heads=H, chunk_size=32)  # noqa: E731
    got = op(*args)
    assert got.shape == (B, 100, H * DV) and got.dtype == dtype
    exact = tuple(x.astype(jnp.float32) for x in args)
    assert _gap(got, _op_by_hand(*exact)) < tol
    weight = jnp.asarray(np.random.RandomState(7).randn(B, 100, H * DV),
                         jnp.float32)
    grads = jax.grad(lambda *a: (op(*a).astype(jnp.float32) * weight).sum(),
                     argnums=tuple(range(7)))(*args)
    want = jax.grad(lambda *a: (_op_by_hand(*a) * weight).sum(),
                    argnums=tuple(range(7)))(*exact)
    for name, a, b in zip("q k v gate beta a_log dt_bias".split(), grads,
                          want):
        assert a.dtype == dict(zip("q k v gate beta".split(),
                                   [dtype] * 5)).get(name, jnp.float32)
        assert _gap(a, b) < 5 * tol, (name, _gap(a, b))


def test_the_symbol_infers_its_leaves_and_keeps_them_float32():
    q = mx.sym.Variable("q")
    net = mx.sym.kda_scan(q, mx.sym.Variable("k"), mx.sym.Variable("v"),
                          mx.sym.Variable("gate"), mx.sym.Variable("beta"),
                          mx.sym.Variable("A_log"), mx.sym.Variable("dt_bias"),
                          num_heads=H, name="kda")
    args, outs, _ = net.infer_shape(q=(B, 20, H * DK), v=(B, 20, H * DV))
    shapes = dict(zip(net.list_arguments(), args))
    assert shapes["k"] == shapes["gate"] == (B, 20, H * DK)
    assert shapes["beta"] == (B, 20, H)
    assert shapes["A_log"] == (H,) and shapes["dt_bias"] == (H * DK,)
    assert outs == [(B, 20, H * DV)]
    assert get_op("kda_scan").f32_inputs == ("a_log", "dt_bias")
    with pytest.raises(ValueError):
        get_op("kda_scan").fn(*_op_inputs(0, 20), num_heads=H, chunk_size=24)


# ------------------------------------------------------------- the kernels
# what the guard admits: keys and values of whole 128-lane tiles
KB, KH, KD = 1, 2, 128
KDIMS = (KB, KH, KD, KD)


def _flat(x):
    return x.reshape(x.shape[:2] + (-1,))


def _kernels_rule(q, k, v, g, beta, chunk=64):
    """``kda_chunked`` through the kernels, interpreted: the same
    arguments, T padded to whole chunks as the op pads it."""
    t, h = q.shape[1], q.shape[2]
    q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, -t % chunk), (0, 0)))
                        for x in (_flat(q), _flat(k), _flat(v), _flat(g),
                                  beta))
    o = pk.kda_scan_fwd(q, k, v, g, beta, h, chunk, interpret=True)
    return o[:, :t].reshape(o.shape[0], t, h, -1)


def _kernels_grads(q, k, v, g, beta, do, chunk=64):
    """The five gradients of ``_kernels_rule`` for ``do`` (B, T, H, dv),
    through ``kda_scan_bwd``."""
    t, h = q.shape[1], q.shape[2]
    flat = tuple(jnp.pad(x, ((0, 0), (0, -t % chunk), (0, 0)))
                 for x in (_flat(q), _flat(k), _flat(v), _flat(g), beta,
                           _flat(do).astype(v.dtype)))
    grads = pk.kda_scan_bwd(*flat, h, chunk, interpret=True)
    return tuple(x[:, :t].reshape(like.shape)
                 for x, like in zip(grads, (q, k, v, g, beta)))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("t", [128, 100])
@pytest.mark.parametrize("rule", ["published", "extreme", "the_seeds"])
def test_the_kernels_forward_is_the_plain_form_and_the_recurrence(
        rule, t, dtype, tol):
    """Two whole chunks, and a ragged last one; float32 to rounding,
    bfloat16 to its products' rounding (each side rounds its own)."""
    args = _inputs(rule, t, seed=8, dtype=dtype, dims=KDIMS)
    got = _kernels_rule(*args)
    assert got.shape == (KB, t, KH, KD) and got.dtype == dtype
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    assert _gap(got, kda.kda_chunked(*args, 64)) < tol
    exact = tuple(x.astype(jnp.float32) for x in args)
    assert _gap(got, _recurrence(*exact)[0]) < tol


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("rule", ["published", "extreme", "the_seeds"])
def test_every_gradient_of_the_kernels_is_autodiffs_of_the_plain_form(
        rule, dtype, tol):
    """The written backward (the states formed again, the chunks last to
    first) against ``jax.vjp`` of ``kda_chunked``, three chunks, the last
    ragged."""
    t = 150
    args = _inputs(rule, t, seed=9, dtype=dtype, dims=KDIMS)
    do = jnp.asarray(np.random.RandomState(10).randn(KB, t, KH, KD),
                     jnp.float32)
    got = _kernels_grads(*args, do)
    _, back = jax.vjp(lambda *a: kda.kda_chunked(*a, 64), *args)
    want = back(do.astype(dtype).astype(jnp.float32))
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), name
        assert _gap(a, b) < tol, (name, _gap(a, b))


@pytest.mark.parametrize("chunk,t,dims", [
    (16, 40, KDIMS), (32, 96, KDIMS), (128, 256, KDIMS),
    (16, 32, (2, 1, 256, 128)), (32, 64, (1, 2, 128, 256))])
def test_the_kernels_take_every_chunk_the_guard_admits(chunk, t, dims):
    """One sub-block a chunk (nothing to join), two, eight; keys wider
    than values and values wider than keys: forward and the five
    gradients."""
    args = _inputs("published", t, seed=11, dims=dims)
    do = jnp.asarray(np.random.RandomState(12).randn(
        dims[0], t, dims[1], dims[3]), jnp.float32)
    assert _gap(_kernels_rule(*args, chunk),
                kda.kda_chunked(*args, chunk)) < 2e-5
    _, back = jax.vjp(lambda *a: kda.kda_chunked(*a, chunk), *args)
    for name, a, b in zip("q k v g beta".split(),
                          _kernels_grads(*args, do, chunk), back(do)):
        assert _gap(a, b) < 1e-4, (name, _gap(a, b))


def test_the_kernels_carry_the_state_where_it_is_most_of_the_output():
    """A slow decay (0.999 a token) and, in the last chunk, a beta near 0:
    what the last chunk puts out is what it inherits.  Its output and the
    gradients that reach the earlier chunks' rows, which pass through the
    carried state and its gradient alone, are the plain form's."""
    t = 256
    q, k, v, g, beta = _inputs("published", t, seed=13, dims=KDIMS)
    g = jnp.full_like(g, -1e-3)
    beta = beta.at[:, 192:].set(1e-3)
    args = (q, k, v, g, beta)
    whole = kda.kda_chunked(*args, 64)
    alone = kda.kda_chunked(*(x[:, 192:] for x in args), 64)
    carried = np.linalg.norm(np.asarray(whole[:, 192:] - alone)) \
        / np.linalg.norm(np.asarray(whole[:, 192:]))
    assert carried > 0.9, carried
    assert _gap(_kernels_rule(*args)[:, 192:], whole[:, 192:]) < 2e-5
    do = jnp.zeros((KB, t, KH, KD), jnp.float32).at[:, 192:].set(
        jnp.asarray(np.random.RandomState(14).randn(KB, 64, KH, KD),
                    jnp.float32))
    _, back = jax.vjp(lambda *a: kda.kda_chunked(*a, 64), *args)
    for name, a, b in zip("q k v g beta".split(), _kernels_grads(*args, do),
                          back(do)):
        # an earlier row's q reaches its own output alone
        assert (name == "q") == (float(jnp.abs(b[:, :192]).max()) == 0), name
        assert _gap(a[:, :192], b[:, :192]) < 1e-4, (name, _gap(a, b))


def test_the_kernels_stay_finite_where_a_chunks_decay_passes_minus_88():
    q, k, v, g, beta = _inputs("extreme", 192, seed=15, dims=KDIMS)
    assert float(np.cumsum(np.asarray(g[:, :64]), axis=1).min()) < -88
    o = _kernels_rule(q, k, v, g, beta)
    grads = _kernels_grads(q, k, v, g, beta, 2 * o)
    assert bool(jnp.isfinite(o).all())
    assert all(bool(jnp.isfinite(x).all()) for x in grads)
    want = jax.grad(lambda *a: (kda.kda_chunked(*a, 64) ** 2).sum(),
                    argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    for name, a, b in zip("q k v g beta".split(), grads, want):
        assert _gap(a, b) < 1e-4, (name, _gap(a, b))


def _kernel_op_inputs(seed, t, dtype):
    r = np.random.RandomState(seed)
    x = lambda *s: jnp.asarray(r.randn(*s), jnp.float32).astype(dtype)  # noqa: E731
    step = np.exp(r.uniform(np.log(1e-3), np.log(0.1), (KH * KD,)))
    return (x(KB, t, KH * KD), x(KB, t, KH * KD), x(KB, t, KH * KD),
            x(KB, t, KH * KD) * 0.5, x(KB, t, KH),
            jnp.asarray(np.log(r.uniform(1, 16, (KH,))), jnp.float32),
            jnp.asarray(np.log(np.expm1(step)), jnp.float32))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 4e-2)])
def test_the_op_through_the_kernels_gives_all_seven_gradients(dtype, tol):
    """``_scan_kernels`` (norms, gates, the kernels under one
    ``custom_vjp``) against ``_scan``, the plain op: the output and the
    gradients of q, k, v, gate, beta, a_log and dt_bias."""
    t = 100
    args = _kernel_op_inputs(16, t, dtype)
    weight = jnp.asarray(np.random.RandomState(17).randn(KB, t, KH * KD),
                         jnp.float32)

    def both(fn):
        def loss(*a):
            o = fn(*a)
            return (o.astype(jnp.float32) * weight).sum(), o
        (_, o), grads = jax.value_and_grad(
            loss, argnums=tuple(range(7)), has_aux=True)(*args)
        return (o,) + grads
    got = both(lambda *a: kda._scan_kernels(*a, KH, 64, True))
    want = both(lambda *a: kda._scan(*a, h=KH, chunk=64))
    for name, a, b in zip("o q k v gate beta a_log dt_bias".split(), got,
                          want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert _gap(a, b) < tol, (name, _gap(a, b))


def test_the_backward_waits_for_the_cotangent_before_its_states():
    """The kernels' five inputs pass one optimization barrier with o's
    cotangent before the first kernel of the backward, so XLA cannot form a
    layer's states (160 MiB at kimi-linear-steps-t4096's shape) before that
    layer's backward: left free, it formed all four layers' at the forward's
    end and kept them, 1.2 GB more scratch (PERF.md 6-7, PR 38;
    ``tools/step_memory.py``)."""
    args = _kernel_op_inputs(16, 64, jnp.float32)
    o, vjp = jax.vjp(lambda *a: kda._scan_kernels(*a, KH, 64, True), *args)
    closed = jax.make_jaxpr(vjp)(o)

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)
    order = [e for e in eqns(closed.jaxpr) if e.primitive.name in (
        "optimization_barrier", "pallas_call")]
    assert [e.primitive.name for e in order] == [
        "optimization_barrier", "pallas_call", "pallas_call"]
    barrier = order[0]
    assert len(barrier.invars) == 6
    assert closed.jaxpr.invars[0] in barrier.invars


@pytest.mark.parametrize("shape,heads", [
    ((4096, 32, 128, 128, 64, 2), 8),      # kimi-linear-steps-t4096
    ((4096, 32, 128, 128, 64, 4), 8),
    ((4096, 12, 128, 128, 64, 2), 6),      # the most that divide H
    ((256, 32, 256, 256, 128, 4), 4),      # the most that fit
    ((100, 2, 128, 128, 64, 2), 2),        # T is the op's to pad
    ((256, 3, 128, 256, 128, 4), 3),
    ((256, 1, 256, 128, 16, 2), 1),
    ((256, 4, 64, 128, 64, 2), None),      # a key narrower than the lanes
    ((256, 4, 128, 96, 64, 2), None),
    ((256, 4, 128, 128, 48, 2), None),     # sub-blocks that do not halve
    ((256, 4, 128, 128, 256, 2), None),
    ((0, 4, 128, 128, 64, 2), None)])
def test_the_chooser_and_the_guard(shape, heads):
    assert pk.kda_blocks(*shape) == heads
    assert pk.kda_available(*shape) == (heads is not None)
    if heads:
        assert pk._kda_vmem(heads, *shape[2:]) <= pk._VMEM_BUDGET


def test_off_the_tpu_the_op_is_the_plain_form():
    """On the CPU harness ``kda_scan`` never asks for a kernel, at a shape
    the guard admits too; and a shape it refuses raises from the kernels'
    own entry."""
    args = _kernel_op_inputs(18, 64, jnp.float32)
    op = lambda *a: get_op("kda_scan").fn(*a, num_heads=KH, chunk_size=64)  # noqa: E731
    assert pk.kda_available(64, KH, KD, KD, 64, 4)
    assert "pallas_call" not in str(jax.make_jaxpr(op)(*args))
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *a: kda._scan_kernels(*a, KH, 64, True))(*args))
    q, k, v, g, beta = _inputs("published", 64)
    with pytest.raises(ValueError):
        pk.kda_scan_fwd(_flat(q), _flat(k), _flat(v), _flat(g), beta, H, 64,
                        interpret=True)


# ------------------------------------------------------------ the host cost
# What a warm run still pays every process: tracing the step and lowering it
# (PERF.md 6, PR 35).  Held by counts, not by a clock: from abstract inputs
# at kimi-linear-steps-t4096's shape, lowered for ``tpu`` on this host.
CELL = (1, 4096, 32, 128, 128, 64)


def _lowered_for_tpu(fn, *abstract):
    """(the StableHLO text, the jaxpr's text) of ``fn`` for a TPU, nothing
    compiled and nothing run."""
    traced = jax.jit(fn).trace(*abstract)
    return (traced.lower(lowering_platforms=("tpu",)).as_text(),
            str(traced.jaxpr))


def _bodies(text):
    """{kernel's name: ``tpu_custom_call`` bodies of it in the text}."""
    names = re.findall(r'kernel_name = "(\w+)"', text)
    assert len(names) == text.count("@tpu_custom_call")
    return {name: names.count(name) for name in set(names)}


def _rule_stack(layers):
    """The gradient of ``layers`` mixers' rule one after another at the
    cell's shape, and its abstract inputs."""
    bsz, t, h, dk, dv, chunk = CELL
    bf16, f32 = jnp.bfloat16, jnp.float32
    shaped = jax.ShapeDtypeStruct
    keys, vals = shaped((bsz, t, h * dk), bf16), shaped((bsz, t, h * dv),
                                                        bf16)

    def loss(q, k, v, gate, beta, a_log, dt_bias):
        o = v
        for _ in range(layers):
            o = kda._scan_kernels(q + o, k, o, gate, beta, a_log, dt_bias, h,
                                  chunk)
        return (o.astype(f32) ** 2).sum()
    return jax.grad(loss, argnums=tuple(range(7))), (
        keys, keys, vals, keys, shaped((bsz, t, h), bf16), shaped((h,), f32),
        shaped((h * dk,), f32))


@pytest.mark.parametrize("layers,text_kb,jaxpr_kb", [(1, 300, 360),
                                                     (4, 400, 700)])
def test_each_kernels_body_is_traced_and_lowered_once_for_all_layers(
        layers, text_kb, jaxpr_kb):
    """The second to fourth mixer find the first's jaxpr (the two callers
    are under ``jax.jit``), so the module holds each body once behind as
    many calls; and a body is written once for a grid step's heads, so it
    is small.  The limits stand well above what this tree reads (189 KB of
    text and 181 KB of jaxpr for one layer, 246 and 350 for four) and well
    under PR 34's bodies, unrolled a head and a column and traced a layer
    (631 KB and 2,228 KB for one, 2,499 and 9,030 for four)."""
    fn, abstract = _rule_stack(layers)
    text, jaxpr = _lowered_for_tpu(fn, *abstract)
    assert _bodies(text) == {"mxtpu_kda_fwd": 1, "mxtpu_kda_states": 1,
                             "mxtpu_kda_bwd": 1}
    assert len(re.findall(r"call @kda_scan_fwd", text)) == layers
    assert len(re.findall(r"call @kda_scan_bwd", text)) == layers
    assert len(text) < text_kb << 10, len(text)
    assert len(jaxpr) < jaxpr_kb << 10, len(jaxpr)


def test_the_other_kernels_bodies_a_layer_read_as_they_did():
    """The flash and the state-space kernels are not under a ``jit`` of
    their own: two layers are two of each body.  Their bodies are small
    (53 KB and 87 KB of text for these two stacks), so it costs little;
    this holds the reading, it does not ask for it."""
    shaped = jax.ShapeDtypeStruct
    bf16, f32 = jnp.bfloat16, jnp.float32
    x = shaped((1, 32, 4096, 128), bf16)

    def flash(q, k, v):
        for _ in range(2):
            v = pk.flash_attention(q, k, v, True)
        return (v.astype(f32) ** 2).sum()
    text, _ = _lowered_for_tpu(jax.grad(flash, argnums=(0, 1, 2)), x, x, x)
    assert _bodies(text) == {"mxtpu_flash_fwd": 2, "mxtpu_flash_dq": 2,
                             "mxtpu_flash_dkv": 2}
    h, p, g, n, chunk = 64, 64, 8, 128, 128
    width = h * p + 2 * g * n

    def scan(xbc, dt, a_log, d, dt_bias):
        for _ in range(2):
            y = ssm._scan_kernels(xbc, dt, a_log, d, dt_bias, h, p, g, chunk)
            xbc = jnp.pad(y, ((0, 0), (0, 0), (0, width - h * p)))
        return (xbc.astype(f32) ** 2).sum()
    text, _ = _lowered_for_tpu(
        jax.grad(scan, argnums=(0, 1, 2, 3, 4)), shaped((1, 4096, width),
                                                        bf16),
        shaped((1, 4096, h), bf16), shaped((h,), f32), shaped((h,), f32),
        shaped((h,), f32))
    assert _bodies(text) == {"mxtpu_ssd_fwd": 2, "mxtpu_ssd_states": 2,
                             "mxtpu_ssd_bwd": 2}


def test_the_conv_kernels_body_is_lowered_once_a_shape(monkeypatch):
    """The gradient of the KDA mixer's three convolutions (no bias) and of
    the Mamba mixer's (a bias, 6,144 channels) on a TPU: the caller of
    ``mxtpu_conv_bwd`` is under ``jax.jit``, so the three of one shape share
    one body and the module holds two behind four calls.  The backend is
    steered here, in the test, as the op takes the kernel on a TPU alone.
    This tree reads 39 KB of text and 44 KB of jaxpr."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shaped = jax.ShapeDtypeStruct
    bf16 = jnp.bfloat16
    silu = jax.nn.silu

    def convs(q, k, v, w, xbc, wm, bm):
        outs = [ssm.causal_conv(a, w, None, silu) for a in (q, k, v)]
        outs.append(ssm.causal_conv(xbc, wm, bm, silu))
        return sum((o.astype(jnp.float32) ** 2).sum() for o in outs)
    x = shaped((1, 4096, 4096), bf16)
    text, jaxpr = _lowered_for_tpu(
        jax.grad(convs, argnums=tuple(range(7))), x, x, x,
        shaped((4096, 4), bf16), shaped((1, 4096, 6144), bf16),
        shaped((6144, 4), bf16), shaped((6144,), bf16))
    assert _bodies(text) == {"mxtpu_conv_bwd": 2}
    assert len(re.findall(r"call @causal_conv_bwd", text)) == 4
    assert len(text) < 80 << 10, len(text)
    assert len(jaxpr) < 90 << 10, len(jaxpr)


@pytest.mark.parametrize("layers", [1, 4])
def test_the_gated_norms_kernels_bodies_are_lowered_once_for_all_layers(
        layers):
    """The gradient of nemotron-twotower-steps-t4096's gated group norm, one
    layer and the four Mamba layers of its ``MEMEM*EME`` stack, lowered for a
    TPU: the callers of ``mxtpu_gnorm_fwd`` and ``_bwd`` are under
    ``jax.jit``, so the module holds each body once behind a call a layer,
    and the text hardly grows with the layers (this tree reads 15 KB and
    16 KB of text, 18 KB and 31 KB of jaxpr)."""
    shaped = jax.ShapeDtypeStruct
    bf16 = jnp.bfloat16
    x = shaped((1, 4096, 4096), bf16)

    def norms(y, w, z):
        for _ in range(layers):
            y = ssm.gated_group_norm(y, w, z, 1e-5, 8)
        return (y.astype(jnp.float32) ** 2).sum()
    text, jaxpr = _lowered_for_tpu(jax.grad(norms, argnums=(0, 1, 2)), x,
                                   shaped((4096,), bf16), x)
    assert _bodies(text) == {"mxtpu_gnorm_fwd": 1, "mxtpu_gnorm_bwd": 1}
    assert len(re.findall(r"call @gnorm_fwd", text)) == layers
    assert len(re.findall(r"call @gnorm_bwd", text)) == layers
    assert len(text) < 40 << 10, len(text)
    assert len(jaxpr) < 40 << 10, len(jaxpr)
