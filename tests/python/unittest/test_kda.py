"""The gated delta rule with a decay a key channel (``ops/kda.py``): the
chunked form against the recurrence taken token by token, forward and every
gradient, in float32 and bfloat16; at the published rates, at the extreme
where a chunk's running log-decay passes float32's range, at the rule the
benchmark's seed lands; the state carried across chunks; the op's own norms
and gates."""
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
from mxnet_tpu.ops import kda  # noqa: E402
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


def _inputs(rule, t, seed=0, dtype=jnp.float32):
    """q and k of unit norm (q scaled), v, the log-decay and beta as the
    rule takes them."""
    (a_lo, a_hi), (dt_lo, dt_hi), (b_lo, b_hi) = RULES[rule]
    r = np.random.RandomState(seed)

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
