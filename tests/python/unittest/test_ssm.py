"""The state-space mixer's operators against the plain reference
(``benchmark/reference/hybrid_lm.py``: the recurrence over t, float32): the
chunked scan over several chunks with the published initialisation's ranges,
where the state carried between chunks matters; the causal convolution; the
norms."""
import functools
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
from mxnet_tpu.ops import pallas_kernels as pk, ssm  # noqa: E402
from mxnet_tpu.ops.registry import get_op  # noqa: E402
from benchmark.reference import hybrid_lm as ref  # noqa: E402



def _scan_inputs(seed, bsz, t, h, p, g, n):
    """dt in 0.001-0.1 and A in 1-16, the published initialisation: a token
    is still felt hundreds of steps later, so several chunks of 16 are tied
    by the carried state."""
    r = np.random.RandomState(seed)
    x = r.randn(bsz, t, h, p).astype(np.float32)
    dt = np.exp(r.uniform(np.log(1e-3), np.log(0.1), (bsz, t, h)))
    a = -r.uniform(1.0, 16.0, (h,))
    b = r.randn(bsz, t, g, n).astype(np.float32)
    c = r.randn(bsz, t, g, n).astype(np.float32)
    return [jnp.asarray(v, jnp.float32) for v in (x, dt, a, b, c)]


def _recurrence(x, dt, a, b, c):
    h, g = x.shape[2], b.shape[2]
    b, c = (jnp.repeat(v, h // g, axis=2) for v in (b, c))
    return jax.vmap(ref._recurrence, in_axes=(0, 0, None, 0, 0))(
        x, dt, a, b, c)


# T = 75 is 4 chunks of 16 and 11 steps of a fifth: the last chunk's edge
# is padded, not hidden by a multiple
@pytest.mark.parametrize("t,chunk", [(75, 16), (64, 16), (10, 16), (130, 64)])
def test_chunked_scan_is_the_recurrence(t, chunk):
    """Forward and every gradient.  Tolerance 2e-4 relative to the largest
    entry: both sides are float32, and they differ by the order of up to T
    additions and by exp(sum) against a product of exps."""
    args = _scan_inputs(t, 2, t, 4, 8, 2, 8)
    got = ssm.ssm_scan_chunked(*args, chunk=chunk)
    want = _recurrence(*args)
    np.testing.assert_allclose(got, want, atol=2e-4 * float(
        jnp.abs(want).max()), rtol=0)
    w = jnp.asarray(np.random.RandomState(1).randn(*want.shape), jnp.float32)
    g_got = jax.grad(lambda *a: (ssm.ssm_scan_chunked(*a, chunk=chunk)
                                 * w).sum(), argnums=(0, 1, 2, 3, 4))(*args)
    g_want = jax.grad(lambda *a: (_recurrence(*a) * w).sum(),
                      argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("x dt a b c".split(), g_got, g_want):
        np.testing.assert_allclose(
            a, b, atol=5e-4 * float(jnp.abs(b).max()), rtol=0, err_msg=name)


def test_the_carried_state_matters_at_these_rates():
    """The test above would pass with the carried state left out if decay
    were fast: at dt <= 0.1, A <= 16 a chunk of 16 keeps at least
    exp(-16 * 0.1 * 16) of its start, and most heads far more."""
    args = _scan_inputs(3, 1, 64, 4, 8, 2, 8)
    whole = ssm.ssm_scan_chunked(*args, chunk=16)
    alone = ssm.ssm_scan_chunked(*[v[:, 48:] if v.ndim > 1 else v
                                   for v in args], chunk=16)
    gap = jnp.abs(whole[:, 48:] - alone).max() / jnp.abs(whole).max()
    assert gap > 0.05


def test_ssm_scan_op_adds_the_step_the_decay_and_the_skip():
    r = np.random.RandomState(5)
    bsz, t, h, p, g, n = 2, 40, 4, 8, 2, 8
    x, dt, a, b, c = _scan_inputs(5, bsz, t, h, p, g, n)
    raw = jnp.asarray(r.randn(bsz, t, h), jnp.float32)
    a_log = jnp.log(-a)
    d = jnp.asarray(r.randn(h), jnp.float32)
    dt_bias = jnp.asarray(r.randn(h) - 3.0, jnp.float32)
    op = get_op("ssm_scan")
    xbc = jnp.concatenate([x.reshape(bsz, t, h * p), b.reshape(bsz, t, g * n),
                           c.reshape(bsz, t, g * n)], axis=-1)
    y = op.fn(xbc, raw, a_log, d, dt_bias, num_heads=h, head_dim=p,
              num_groups=g, chunk_size=16)
    step = jax.nn.softplus(raw + dt_bias)
    want = _recurrence(x, step, a, b, c) + d[:, None] * x
    np.testing.assert_allclose(y.reshape(want.shape), want, atol=2e-4 * float(
        jnp.abs(want).max()), rtol=0)
    assert op.f32_inputs == ("a_log", "d", "dt_bias")


def test_ssm_scan_in_bfloat16_keeps_decay_and_state_in_float32():
    """bfloat16 operands, float32 decays and state: against the float32
    recurrence the gap is the operands' rounding (2**-8 each, three
    operands), not that of a bfloat16 running sum over 75 steps."""
    x, dt, a, b, c = _scan_inputs(7, 1, 75, 4, 8, 2, 8)
    got = ssm.ssm_scan_chunked(x.astype(jnp.bfloat16), dt, a,
                               b.astype(jnp.bfloat16),
                               c.astype(jnp.bfloat16), chunk=16)
    assert got.dtype == jnp.float32
    want = _recurrence(x, dt, a, b, c)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 0.03


@pytest.mark.parametrize("k", [4, 3])
def test_causal_conv1d_is_the_shifted_sum(k):
    r = np.random.RandomState(0)
    x = r.randn(2, 9, 6).astype(np.float32)
    w = r.randn(6, k).astype(np.float32)
    b = r.randn(6).astype(np.float32)
    args = [mx.nd.array(x), mx.nd.array(w), mx.nd.array(b)]
    y = mx.nd.causal_conv1d(*args, kernel=k).asnumpy()
    want = np.zeros_like(x) + b
    for t in range(9):
        for j in range(k):
            src = t - (k - 1) + j
            if src >= 0:
                want[:, t] += w[:, j] * x[:, src]
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    # causal: the output at t does not move with the input after t
    x2 = x.copy()
    x2[:, 5:] += 1.0
    y2 = mx.nd.causal_conv1d(mx.nd.array(x2), *args[1:], kernel=k).asnumpy()
    np.testing.assert_array_equal(y[:, :5], y2[:, :5])
    # the fused activation is the activation of the sum
    y3 = mx.nd.causal_conv1d(*args, kernel=k, act_type="silu").asnumpy()
    np.testing.assert_allclose(y3, want / (1 + np.exp(-want)), rtol=1e-5,
                               atol=1e-5)


def test_causal_conv1d_infers_its_leaves():
    s = mx.sym.causal_conv1d(mx.sym.Variable("data"), kernel=4, name="c")
    shapes, outs, _ = s.infer_shape(data=(2, 9, 6))
    assert dict(zip(s.list_arguments(), shapes)) == {
        "data": (2, 9, 6), "c_weight": (6, 4), "c_bias": (6,)}
    assert outs == [(2, 9, 6)]


def _shifted_sum(data, weight, bias, act_type):
    """The oracle: the plain shifted sum as the op stood before its backward
    was written by hand, float32 inside, differentiated by autodiff."""
    k, t = weight.shape[1], data.shape[1]
    padded = jnp.pad(data, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    y = sum(padded[:, j:j + t] * weight[:, j].astype(jnp.float32)
            for j in range(k))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return (jax.nn.silu(y) if act_type else y).astype(data.dtype)


def _conv_inputs(seed, bsz, t, c, k, with_bias, dtype):
    r = np.random.RandomState(seed)
    x, w, dy = (jnp.asarray(r.randn(*s), jnp.float32).astype(dtype)
                for s in ((bsz, t, c), (c, k), (bsz, t, c)))
    b = jnp.asarray(r.randn(c), jnp.float32).astype(dtype) \
        if with_bias else None
    return x, w, b, dy


def _conv_grads(fn, x, w, b, dy):
    return jax.vjp(fn, x, w, b)[1](dy)


def _assert_conv_grads(got, want, dtype, names=("dx", "dweight", "dbias")):
    """dx, dweight and dbias (or what ``names`` says) in their primals'
    dtype and shape; float32 to 1e-5 of the largest entry, bfloat16 within
    one unit in the last place of it (bfloat16 keeps 8 bits)."""
    assert len(got) == len(want) == len(names)
    for name, a, ref_ in zip(names, got, want):
        if ref_ is None:
            assert a is None
            continue
        assert a.dtype == ref_.dtype == dtype and a.shape == ref_.shape
        top = float(jnp.abs(ref_.astype(jnp.float32)).max())
        tol = 1e-5 * top if dtype == jnp.float32 else \
            2.0 ** (np.floor(np.log2(top)) - 7) if top else 0.0
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(ref_, np.float32), rtol=0,
                                   atol=tol, err_msg=name)


@pytest.mark.parametrize("t", [1, 37])         # T < K; T no multiple of 16
@pytest.mark.parametrize("act_type", [None, "silu"])
@pytest.mark.parametrize("with_bias", [True, False])
@pytest.mark.parametrize("k", [2, 3, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_causal_conv1ds_backward_is_autodiff_of_the_shifted_sum(
        dtype, k, with_bias, act_type, t):
    """dx, dweight and dbias of the op against autodiff of the plain form:
    float32 to 1e-5 of the largest entry; bfloat16 to the plain form's own
    bfloat16 result within one ulp of its largest entry.  And the gradient
    is causal: dy after s moves no dx before s - K + 1."""
    x, w, b, dy = _conv_inputs(k + t, 2, t, 6, k, with_bias, dtype)
    op = lambda x, w, b: get_op("causal_conv1d").fn(  # noqa: E731
        x, w, b, kernel=k, act_type=act_type)
    got = _conv_grads(op, x, w, b, dy)
    _assert_conv_grads(got, _conv_grads(
        lambda *a: _shifted_sum(*a, act_type), x, w, b, dy), dtype)
    s = t - 1
    moved = _conv_grads(op, x, w, b, dy.at[:, s:].add(1.0))[0]
    np.testing.assert_array_equal(np.asarray(moved[:, :max(s - k + 1, 0)]),
                                  np.asarray(got[0][:, :max(s - k + 1, 0)]))
    assert not np.array_equal(np.asarray(moved), np.asarray(got[0]))


def test_causal_conv1ds_backward_is_its_own_and_keeps_the_inputs_alone(
        monkeypatch):
    """Off the TPU the op's gradient is ``conv_bwd_plain`` under one
    ``custom_vjp``: no ``checkpoint`` / ``remat`` in the program, and the
    forward hands the backward the op's inputs and nothing else."""
    x, w, b, dy = _conv_inputs(0, 2, 9, 6, 4, True, jnp.float32)
    op = lambda x, w, b: get_op("causal_conv1d").fn(  # noqa: E731
        x, w, b, kernel=4, act_type="silu")
    loss = lambda *a: (op(*a) * dy).sum()  # noqa: E731
    assert "custom_vjp_call" in str(jax.make_jaxpr(op)(x, w, b))
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, w, b))
    assert "checkpoint" not in text and "remat" not in text
    _, res = ssm._conv_fwd(x, w, b, jax.nn.silu)
    assert len(res) == 3 and all(r is v for r, v in zip(res, (x, w, b)))
    calls = []
    plain = ssm.conv_bwd_plain
    monkeypatch.setattr(ssm, "conv_bwd_plain",
                        lambda *a: calls.append(1) or plain(*a))
    jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
    assert calls == [1]


# (B, T, C, K, bias, activation, dtype): three row blocks of 16 and their
# halos; three row blocks of 32 by three column blocks of 128, no bias; 17
# taps, the most the halo holds
CONV_KERNEL_SHAPES = [(2, 48, 256, 4, True, None, jnp.float32),
                      (2, 48, 256, 4, True, "silu", jnp.float32),
                      (1, 96, 384, 3, False, "silu", jnp.bfloat16),
                      (1, 48, 128, 17, True, "silu", jnp.float32)]


@pytest.mark.parametrize("case", CONV_KERNEL_SHAPES)
def test_the_conv_kernel_is_the_plain_backward(case):
    """``mxtpu_conv_bwd`` in interpret mode against ``conv_bwd_plain``:
    float32 to 1e-5 of the largest entry, bfloat16 within one ulp of it
    (both sum in float32, in another order)."""
    bsz, t, c, k, with_bias, act_type, dtype = case
    x, w, b, dy = _conv_inputs(c, bsz, t, c, k, with_bias, dtype)
    act = jax.nn.silu if act_type else None
    _assert_conv_grads(pk.causal_conv_bwd(x, w, b, dy, act, interpret=True),
                       ssm.conv_bwd_plain(x, w, b, dy, act), dtype)


@pytest.mark.parametrize("shape,blocks", [
    ((4096, 6144, 4, 2), (1024, 256)),     # nemotron-twotower's Mamba conv
    ((4096, 4096, 4, 2), (1024, 256)),     # kimi-linear's q, k and v
    ((48, 256, 4, 4), (16, 256)),
    ((96, 384, 3, 2), (32, 128)),
    ((4096, 384, 4, 2), (1024, 128)),
    ((16, 128, 17, 4), (16, 128)),         # K - 1 fills the halo
    ((16, 128, 18, 4), None),
    ((40, 256, 4, 2), None),               # T no multiple of 16
    ((64, 200, 4, 2), None),               # C no multiple of the lanes
    ((0, 128, 4, 2), None)])
def test_the_conv_kernels_chooser_and_guard(shape, blocks):
    assert pk.conv_blocks(*shape) == blocks
    if blocks:
        assert pk._conv_vmem(*blocks, shape[3]) <= pk._VMEM_BUDGET


@pytest.mark.parametrize("groups", [1, 4])
def test_rms_norm_plain_and_over_groups(groups):
    """Float32 statistics: exact to rounding (1e-5)."""
    r = np.random.RandomState(0)
    x = r.randn(5, 16).astype(np.float32) * 3
    g = r.randn(16).astype(np.float32)
    y = mx.nd.RMSNorm(mx.nd.array(x), mx.nd.array(g), eps=1e-5,
                      num_groups=groups).asnumpy()
    xg = x.reshape(5, groups, 16 // groups)
    want = (xg / np.sqrt((xg ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(5, 16) * g
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        y, ref._rms(jnp.asarray(x), jnp.asarray(g), 1e-5, groups),
        rtol=1e-5, atol=1e-5)


def test_gated_rms_norm_gates_first():
    r = np.random.RandomState(2)
    x = r.randn(5, 16).astype(np.float32)
    z = r.randn(5, 16).astype(np.float32)
    g = r.randn(16).astype(np.float32)
    y = mx.nd.RMSNorm(mx.nd.array(x), mx.nd.array(g), mx.nd.array(z),
                      gated=True, num_groups=4).asnumpy()
    gated = x * z / (1 + np.exp(-z))
    want = np.asarray(ref._rms(jnp.asarray(gated), jnp.asarray(g), 1e-5, 4))
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    s = mx.sym.RMSNorm(mx.sym.Variable("data"), gate=mx.sym.Variable("z"),
                       gated=True, name="n")
    shapes, _, _ = s.infer_shape(data=(5, 16))
    assert dict(zip(s.list_arguments(), shapes)) == {
        "data": (5, 16), "n_gamma": (16,), "z": (5, 16)}


def test_rms_norm_in_bfloat16_takes_its_statistics_in_float32():
    x = jnp.full((2, 4096), 3.0, jnp.bfloat16)      # sum of squares 36,864
    y = get_op("RMSNorm").fn(x, jnp.ones((4096,), jnp.bfloat16))
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(y, np.float32), 1.0, atol=1e-2)


def test_layer_norm_by_hand():
    """ROADMAP noted LayerNorm had no test of its own."""
    r = np.random.RandomState(1)
    x = r.randn(4, 12).astype(np.float32) * 2 + 1
    g, b = r.randn(12).astype(np.float32), r.randn(12).astype(np.float32)
    y = mx.nd.LayerNorm(mx.nd.array(x), mx.nd.array(g), mx.nd.array(b),
                        eps=1e-5).asnumpy()
    mu = x.mean(-1, keepdims=True)
    want = (x - mu) / np.sqrt(x.var(-1, keepdims=True) + 1e-5) * g + b
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    s = mx.sym.LayerNorm(mx.sym.Variable("data"), name="ln")
    shapes, _, _ = s.infer_shape(data=(4, 12))
    assert shapes == [(4, 12), (12,), (12,)]


@pytest.mark.parametrize("act,fn", [
    ("silu", lambda x: x / (1 + np.exp(-x))),
    ("relu2", lambda x: np.maximum(x, 0) ** 2)])
def test_the_new_activations(act, fn):
    x = np.linspace(-3, 3, 13).astype(np.float32)
    y = mx.nd.Activation(mx.nd.array(x), act_type=act).asnumpy()
    np.testing.assert_allclose(y, fn(x), rtol=1e-5, atol=1e-6)


def test_the_ssm_initialisers_draw_the_published_ranges():
    a_log = mx.nd.zeros((512,))
    mx.init.LogOfUniform(1, 16)._init_weight("a", a_log)
    a = np.exp(a_log.asnumpy())
    assert a.min() >= 1 and a.max() <= 16 and a.std() > 3
    bias = mx.nd.zeros((512,))
    mx.init.InverseSoftplusLogUniform(0.001, 0.1)._init_weight("b", bias)
    dt = np.log1p(np.exp(bias.asnumpy()))
    assert dt.min() >= 0.00099 and dt.max() <= 0.101


# ---------------------------------------------- the Pallas kernels of the scan
# Interpret mode, at tile sizes the TPU takes (chunks of 128, N = 128).  The
# cases: (B, T, H, P, G, N).  Two heads a 128-lane tile and two tiles a step;
# a head a tile; four heads a tile; two steps of 8 heads to a group, whose dB
# and dC are summed outside the kernel.
KERNEL_SHAPES = {"pairs": (2, 512, 8, 64, 2, 128),
                 "whole": (2, 512, 4, 128, 2, 128),
                 "fours": (2, 512, 8, 32, 2, 128),
                 "parts": (1, 512, 16, 64, 1, 128)}


def _op_inputs(seed, shape, dtype=jnp.float32):
    """The op's five inputs at the rates of ``_scan_inputs``: the step
    ``softplus(dt + dt_bias)`` spreads round a head's own rate in
    0.001-0.1, A in 1-16."""
    bsz, t, h, p, g, n = shape
    r = np.random.RandomState(seed)
    data = jnp.asarray(r.randn(bsz, t, h * p + 2 * g * n), jnp.float32)
    dt = jnp.asarray(r.randn(bsz, t, h) * 0.5, jnp.float32)
    rate = np.exp(r.uniform(np.log(1e-3), np.log(0.1), (h,)))
    return (data.astype(dtype), dt.astype(dtype),
            jnp.asarray(np.log(r.uniform(1.0, 16.0, (h,))), jnp.float32),
            jnp.asarray(r.randn(h), jnp.float32),
            jnp.asarray(np.log(np.expm1(rate)), jnp.float32))


def _plain(shape):
    _, _, h, p, g, _ = shape
    return lambda *a: ssm._scan(*a, h=h, p=p, g=g, chunk=128)


def _kernels(shape):
    _, _, h, p, g, _ = shape
    return lambda *a: ssm._scan_kernels(*a, h, p, g, 128, True)


def _gap(got, want):
    got, want = (jnp.asarray(v, jnp.float32) for v in (got, want))
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 1e-2)])
@pytest.mark.parametrize("case", sorted(KERNEL_SHAPES))
def test_the_scan_kernels_are_the_plain_scan_and_the_recurrence(case, dtype,
                                                                tol):
    """Against the plain form both sides round alike (bfloat16: y's own
    rounding, 2**-8, is the gap's size); against the float32 recurrence the
    bfloat16 gap is the operands' rounding, as for the plain form above."""
    shape = KERNEL_SHAPES[case]
    bsz, t, h, p, g, n = shape
    args = _op_inputs(11, shape, dtype)
    got = jax.jit(_kernels(shape))(*args)
    assert got.dtype == dtype and got.shape == (bsz, t, h * p)
    assert _gap(got, jax.jit(_plain(shape))(*args)) < tol
    data, dt, a_log, d, dt_bias = (v.astype(jnp.float32) for v in args)
    x = data[..., :h * p].reshape(bsz, t, h, p)
    b, c = (data[..., h * p + i * g * n:h * p + (i + 1) * g * n].reshape(
        bsz, t, g, n) for i in (0, 1))
    want = _recurrence(x, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log), b,
                       c) + d[:, None] * x
    assert _gap(got.reshape(want.shape), want) < (
        2e-4 if dtype == jnp.float32 else 0.03)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-4),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("case", sorted(KERNEL_SHAPES))
def test_the_scan_kernels_gradients_are_autodiffs_of_the_plain_scan(
        case, dtype, tol):
    """All five inputs; x, B and C of ``data`` apart, so that a small
    gradient is not hidden beside a large one."""
    shape = KERNEL_SHAPES[case]
    _, _, h, p, g, n = shape
    args = _op_inputs(13, shape, dtype)
    w = jnp.asarray(np.random.RandomState(2).randn(
        *args[0].shape[:2], h * p), jnp.float32)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: (fn(*a).astype(jnp.float32) * w).sum(),
            argnums=(0, 1, 2, 3, 4)))(*args)
    got, want = grads(_kernels(shape)), grads(_plain(shape))
    for name, a, b in zip("data dt a_log d dt_bias".split(), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert _gap(a, b) < tol, name
    for name, cols in (("x", slice(0, h * p)),
                       ("B", slice(h * p, h * p + g * n)),
                       ("C", slice(h * p + g * n, None))):
        assert _gap(got[0][..., cols], want[0][..., cols]) < tol, name


def test_the_scan_kernels_carry_the_state_and_its_gradient():
    """The twin of ``test_the_carried_state_matters_at_these_rates``.  A
    forward that dropped the state between chunks would give the last
    chunk what it gives that chunk alone; a backward that dropped dS would
    give the first three chunks no gradient from the last chunk's y."""
    shape = KERNEL_SHAPES["pairs"]
    args = _op_inputs(17, shape)
    kernels, plain = _kernels(shape), _plain(shape)
    whole = jax.jit(kernels)(*args)
    alone = jax.jit(kernels)(*[v[:, 384:] if v.ndim > 1 else v
                               for v in args])
    assert _gap(alone, whole[:, 384:]) > 0.05
    assert _gap(whole, jax.jit(plain)(*args)) < 1e-5

    def last(fn):
        return jax.jit(jax.grad(lambda *a: (
            fn(*a)[:, 384:].astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1)))(*args)
    got, want = last(kernels), last(plain)
    for name, a, b in zip(("data", "dt"), got, want):
        early = float(jnp.abs(b[:, :384]).max() / jnp.abs(b).max())
        assert early > 0.05, name
        assert _gap(a[:, :384], b[:, :384]) < 5e-4, name


def test_the_scan_kernels_chooser_and_guard():
    from mxnet_tpu.ops.pallas_kernels import ssd_available, ssd_blocks
    # nemotron-twotower-steps-t4096: a group's 8 heads a step
    assert ssd_blocks(4096, 64, 64, 8, 128, 128, 2) == 8
    assert ssd_available(4096, 64, 64, 8, 128, 128, 2)
    # one group of 64 heads: 8 a step all the same (the loop is unrolled)
    assert ssd_blocks(4096, 64, 64, 1, 128, 128, 2) == 8
    # a head a tile, four heads a tile
    assert ssd_blocks(512, 4, 128, 2, 128, 128, 4) == 2
    assert ssd_blocks(512, 8, 32, 2, 128, 128, 4) == 4
    refused = {"T off the chunk": (4000, 64, 64, 8, 128, 128),
               "a chunk off the lanes": (4096, 64, 64, 8, 128, 64),
               "P the lanes refuse": (4096, 64, 48, 8, 128, 128),
               "N off the lanes": (4096, 64, 64, 8, 64, 128),
               "half a tile of heads a group": (4096, 8, 64, 8, 128, 128),
               "a chunk VMEM does not hold": (4096, 64, 64, 8, 128, 2048)}
    for why, shape in refused.items():
        assert ssd_blocks(*shape, 2) is None, why
        assert not ssd_available(*shape, 2), why


def test_off_the_tpu_the_op_is_the_plain_scan():
    """The path is chosen from the backend and the shape: here, on the CPU,
    the cell's own shape holds no kernel."""
    shape = (1, 512, 64, 64, 8, 128)
    args = _op_inputs(19, shape, jnp.bfloat16)
    op = get_op("ssm_scan")
    fn = lambda *a: op.fn(*a, num_heads=64, head_dim=64, num_groups=8,  # noqa: E731
                          chunk_size=128)
    assert "pallas_call" not in str(jax.make_jaxpr(fn)(*args))
    assert "pallas_call" not in str(jax.make_jaxpr(jax.grad(
        lambda *a: fn(*a).astype(jnp.float32).sum()))(*args))
    assert "pallas_call" in str(jax.make_jaxpr(_kernels(shape))(*args))


# ------------------------------------------------------- the gated group norm
# (B, T, groups, a group's width, dtype, the gate's scale): widths of 128 and
# 512 lanes, one group and eight, float32 and bfloat16, and a gate at the
# published scale of the in-projection's, |z| up to 100, where silu'
# saturates; the last two the guard refuses (groups of 96 lanes; 40 rows, no
# multiple of the 16-row strip) and the op stays the plain form
GNORM_CASES = [(2, 24, 1, 128, jnp.float32, 1.0),
               (1, 64, 8, 128, jnp.bfloat16, 1.0),
               (1, 32, 8, 512, jnp.float32, 1.0),
               (2, 16, 1, 512, jnp.bfloat16, 1.0),
               (1, 48, 8, 512, jnp.bfloat16, 30.0),
               (1, 32, 2, 96, jnp.float32, 1.0),
               (1, 40, 8, 128, jnp.bfloat16, 1.0)]


@pytest.mark.parametrize("case", GNORM_CASES)
def test_the_gated_norms_kernels_are_the_plain_form(case, monkeypatch):
    """The gated ``RMSNorm`` over groups with the backend steered to a TPU
    (here, in the test) and the kernels ``mxtpu_gnorm_fwd`` / ``_bwd`` in
    interpret mode, against the op's plain form off the TPU: the result and
    the gradients of data, gamma and gate, float32 to 1e-5 of the largest
    entry, bfloat16 within one ulp of it.  A shape the guard refuses takes
    the plain form on the TPU too: the same numbers to the bit."""
    bsz, t, g, width, dtype, scale = case
    c = g * width
    r = np.random.RandomState(c + t)
    x, z, dy = (jnp.asarray(r.randn(bsz, t, c) * s, jnp.float32).astype(dtype)
                for s in (1.0, scale, 1.0))
    w = jnp.asarray(r.randn(c), jnp.float32).astype(dtype)

    def run():                 # a new function: jax keeps a trace by it
        op = lambda x, w, z: get_op("RMSNorm").fn(  # noqa: E731
            x, w, z, num_groups=g, gated=True)
        y, vjp = jax.vjp(op, x, w, z)
        return (y,) + vjp(dy), str(jax.make_jaxpr(op)(x, w, z))
    want, jaxpr = run()
    assert "pallas_call" not in jaxpr
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name in ("gnorm_fwd", "gnorm_bwd"):
        monkeypatch.setattr(pk, name, functools.partial(getattr(pk, name),
                                                        interpret=True))
    got, jaxpr = run()
    taken = width % 128 == 0 and (bsz * t) % 16 == 0
    assert pk.gnorm_available(bsz * t, c, g, x.dtype.itemsize) == taken
    assert ("pallas_call" in jaxpr) == taken
    if taken:
        _assert_conv_grads(got, want, dtype, ("y", "dx", "dgamma", "dgate"))
    else:
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


@pytest.mark.parametrize("shape,rows", [
    ((4096, 4096, 8, 2), 512),             # nemotron-twotower's Mamba norm
    ((4096, 4096, 8, 4), 512),
    ((4096, 4096, 1, 4), 256),             # one group of 4,096 in float32
    ((48, 128, 1, 4), 16),
    ((96, 1024, 8, 2), 32),
    ((40, 1024, 8, 2), None),              # T no multiple of 16
    ((64, 768, 8, 2), None),               # groups of 96 lanes
    ((64, 1000, 8, 2), None),              # C no multiple of G
    ((0, 1024, 8, 2), None)])
def test_the_gated_norms_chooser_and_guard(shape, rows):
    assert pk.gnorm_blocks(*shape) == rows
    assert pk.gnorm_available(*shape) == (rows is not None)
    if rows:
        t, c, g, itemsize = shape
        assert pk._gnorm_vmem(rows, c // g, itemsize) <= pk._VMEM_BUDGET


def test_only_the_gated_norms_ask_the_guard(monkeypatch):
    """The step of an ``MEMEM*EME`` model traced with the backend steered to
    a TPU: each Mamba mixer's gated norm asks ``gnorm_available`` once, the
    ten ungated norms (the pre-norms, the final norm) never; the four layers
    call each kernel's one traced body (the callers are under ``jax.jit``)."""
    from mxnet_tpu import amp
    from mxnet_tpu.models import hybrid_lm
    from mxnet_tpu.train import TrainStep
    calls = []
    guard = pk.gnorm_available
    monkeypatch.setattr(pk, "gnorm_available",
                        lambda *a: calls.append(a) or guard(*a))
    net = hybrid_lm.get_symbol(vocab_size=64, seq_len=32, pattern="MEMEM*EME",
                               num_hidden=32, ssm_heads=4, ssm_head_dim=64,
                               ssm_groups=2)
    ts = TrainStep(net, mx.optimizer.Adam(learning_rate=1e-4),
                   policy=amp.Policy("bfloat16"))
    p, s, a = ts.init({"data": (2, 32)}, {"softmax_label": (2, 32)})
    b = ts.shard_batch({"data": np.zeros((2, 32), np.float32),
                        "softmax_label": np.zeros((2, 32), np.float32)})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jaxpr = str(ts._step.trace(p, s, a, ts._scale_state_dev(), b,
                               jax.random.PRNGKey(0), ts.fopt.hyper(0),
                               np.int32(1)).jaxpr)
    assert calls == [(64, 256, 2, 2)] * 4        # B T rows, inner, groups
    assert re.findall(r"name=(gnorm_\w+)", jaxpr) == ["gnorm_fwd"] * 4 + [
        "gnorm_bwd"] * 4
    assert jaxpr.count("pallas_call") == 2
