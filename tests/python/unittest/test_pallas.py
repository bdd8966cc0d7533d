"""Pallas flash-attention kernel tests (SURVEY.md §7 "Pallas kernels for the
hot ops"; runs the kernel in interpret mode on the CPU harness — the same
code path compiles natively on TPU, where ``tools/tpu_numerics_check.py``
checks it and the benchmark times it: PERF.md 5)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_kernels
from mxnet_tpu.ops.pallas_kernels import (flash_attention, flash_available,
                                          flash_blocks)
from mxnet_tpu.parallel.ring import attention_reference

RS = np.random.RandomState


def _qkv(B=2, H=2, T=256, D=64, seed=0):
    rng = RS(seed)
    return tuple(jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    out = np.asarray(flash_attention(q, k, v, causal, None, 128, 128, True))
    ref = np.asarray(attention_reference(q, k, v, causal=causal))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_flash_uneven_blocks():
    q, k, v = _qkv(T=384, seed=1)  # 3 blocks of 128
    out = np.asarray(flash_attention(q, k, v, True, None, 128, 128, True))
    ref = np.asarray(attention_reference(q, k, v, causal=True))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_flash_gradients():
    q, k, v = _qkv(T=128, seed=2)

    def lf(q, k, v):
        return (flash_attention(q, k, v, True, None, 64, 64, True) ** 2).sum()

    def lr(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-4)


@pytest.mark.parametrize("causal,bq,bk", [(False, 64, 64), (True, 64, 32),
                                          (True, 32, 64)])
def test_flash_pallas_backward_blocks(causal, bq, bk):
    """The Pallas dq/dkv kernels across block aspect ratios (the causal
    start-block arithmetic differs when block_q != block_k)."""
    q, k, v = _qkv(T=128, seed=5)

    def lf(q, k, v):
        return (flash_attention(q, k, v, causal, None, bq, bk, True)
                * jnp.cos(q)).sum()

    def lr(q, k, v):
        return (attention_reference(q, k, v, causal=causal)
                * jnp.cos(q)).sum()

    g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_pallas_backward_vs_xla_blocked(causal):
    """The Pallas dq/dkv kernels against the blocked-XLA backward
    (_flash_bwd_xla — kept exactly as the oracle for this test)."""
    from mxnet_tpu.ops.pallas_kernels import (_flash_bwd, _flash_bwd_xla,
                                              _flash_fwd)
    q, k, v = _qkv(T=128, seed=9)
    out, res = _flash_fwd(q, k, v, causal, None, 64, 64, True)
    g = jnp.cos(out)
    got = _flash_bwd(causal, None, 64, 64, True, res, g)
    want = _flash_bwd_xla(causal, None, 64, 64, True, res, g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_flash_available_guard():
    assert flash_available((2, 2, 1024, 64))
    assert not flash_available((2, 2, 100, 64))    # T not block-divisible
    assert not flash_available((2, 2, 1024, 300))  # D too large
    assert not flash_available((2, 1024, 64))      # wrong rank
    # the corners the chip run compiled stay admitted; T=32768, D=32 —
    # which Mosaic refuses in f32 (dK/dV residents) — does not
    for t, d in ((16384, 64), (8192, 128), (4096, 256)):
        assert flash_available((1, 1, t, d))
    assert not flash_available((1, 1, 32768, 32))


# bfloat16 keeps 8 significant bits: neighbouring values lie 2**-8 apart
# relative to the larger, and a rounding moves a value by at most half that
BF16_STEP = 2.0 ** -8


def _bf16(x):
    return x.astype(jnp.bfloat16)


def _rel(a, b):
    """Largest difference over the reference's largest magnitude."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.max(np.abs(a - b)) / np.max(np.abs(b))


def _bf16_case(causal, blocks, T, seed):
    """Forward and the three gradients of the kernels on bf16 values against
    ``attention_reference`` in f32 on the same values."""
    q, k, v = (_bf16(x) for x in _qkv(B=1, H=2, T=T, seed=seed))
    bq, bk = blocks

    def lf(q, k, v):
        out = flash_attention(q, k, v, causal, None, bq, bk, True)
        return (out.astype(jnp.float32)
                * jnp.cos(q.astype(jnp.float32))).sum()

    def lr(q, k, v):
        return (attention_reference(q, k, v, causal=causal)
                * jnp.cos(q)).sum()

    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    out = flash_attention(q, k, v, causal, None, bq, bk, True)
    assert out.dtype == jnp.bfloat16
    # the result is rounded to bf16 once (half a step) after products whose
    # probabilities were rounded too: two steps of room
    assert _rel(out, attention_reference(*f32, causal=causal)) \
        < 2 * BF16_STEP
    got = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lr, argnums=(0, 1, 2))(*f32)
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == jnp.bfloat16
        # a gradient passes two rounded operands (p, ds) and its own
        # rounding: measured 2e-3 to 6e-3, held to four steps
        assert _rel(a, b) < 4 * BF16_STEP, (name, _rel(a, b))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bf16_forward_and_gradients(causal):
    _bf16_case(causal, (64, 64), 256, 11)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_default_blocks_from_the_chooser(causal):
    """block_q = block_k = None at T = 1024: every kernel's blocks come from
    ``flash_blocks``, are above 128, and under ``causal`` each kernel has
    blocks wholly below the diagonal (the unmasked loop) beside the ones
    the diagonal crosses."""
    bq, bk = flash_blocks(1024, 64, 2)
    assert bq > 128 and bk > 128 and 1024 // bq >= 2 and 1024 // bk >= 2
    _bf16_case(causal, (None, None), 1024, 12)


@pytest.mark.parametrize("bq,bk", [(64, 32), (32, 64), (128, 32), (32, 128)])
def test_flash_causal_unequal_blocks(bq, bk):
    """Block ratios 2 and 4, both ways: the first / whole / crossed block
    arithmetic of the masked and the unmasked loop in all three kernels."""
    q, k, v = _qkv(B=1, H=2, T=256, seed=13)

    def lf(q, k, v):
        return (flash_attention(q, k, v, True, None, bq, bk, True)
                * jnp.cos(q)).sum()

    def lr(q, k, v):
        return (attention_reference(q, k, v, causal=True) * jnp.cos(q)).sum()

    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, True, None, bq, bk, True)),
        np.asarray(attention_reference(q, k, v, causal=True)),
        rtol=2e-4, atol=2e-5)
    g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-4)


# the guard's corners (tools/tpu_numerics_check.py compiles them on the
# chip), the benchmark cell's shape and the smallest T the op sends here
CORNERS = [(16384, 64), (8192, 128), (4096, 256), (2048, 64), (512, 64),
           (1024, 128)]


@pytest.mark.parametrize("t,d", CORNERS)
@pytest.mark.parametrize("itemsize", [2, 4])
def test_flash_blocks_fit_and_guard_agrees(t, d, itemsize):
    bq, bk = flash_blocks(t, d, itemsize)
    assert t % bq == 0 and t % bk == 0
    assert bq % 128 == 0 and bk % 128 == 0          # lane-aligned rows
    assert pallas_kernels._vmem_bytes(t, d, itemsize, bq, bk) \
        <= pallas_kernels._VMEM_BUDGET
    assert flash_available((1, 1, t, d))


def test_flash_blocks_refuses_what_the_guard_refuses():
    for t, d in ((32768, 32), (100, 64), (1000, 64)):
        assert flash_blocks(t, d, 4) is None
        assert not flash_available((1, 1, t, d))
    with pytest.raises(ValueError):
        q = jnp.zeros((1, 1, 100, 64), jnp.float32)
        flash_attention(q, q, q, True, None, None, None, True)


def _pallas_calls(dtype, causal=True):
    """name -> (kernel jaxpr, result avals) of the pallas_calls in forward
    and backward of one flash_attention."""
    q = jnp.zeros((1, 2, 256, 64), dtype)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal, None, None, None, True)
        return out.astype(jnp.float32).sum()

    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = (
                    eqn.params["jaxpr"], [v.aval for v in eqn.outvars])
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

    walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr)
    return found


def _products(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _products(sub)


@pytest.mark.parametrize("kernel,count", [("mxtpu_flash_fwd", 2),
                                          ("mxtpu_flash_dq", 3),
                                          ("mxtpu_flash_dkv", 4)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_products_take_the_inputs_dtype(kernel, count, dtype):
    """Every product of a kernel multiplies two operands of the dtype q, k
    and v came in and accumulates in f32 (one MXU pass for bf16 inputs);
    under ``causal`` each product appears in the unmasked and in the masked
    loop."""
    body, _ = _pallas_calls(dtype)[kernel]
    products = list(_products(body))
    assert len(products) == 2 * count
    for eqn in products:
        assert [v.aval.dtype for v in eqn.invars] == [dtype, dtype]
        assert eqn.outvars[0].aval.dtype == jnp.float32
        assert eqn.params["preferred_element_type"] == jnp.float32


def test_flash_results_are_what_the_benchmarks_readers_key_on():
    """``benchmark/readers/kernels.py::_classify`` tells the three kernels
    apart by their results alone."""
    import os
    import sys
    root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))
    sys.path.insert(0, root)
    try:
        from benchmark.readers.kernels import _classify
    finally:
        sys.path.remove(root)
    names = {"bfloat16": "bf16", "float32": "f32"}
    for name, kind in (("mxtpu_flash_fwd", "fwd"), ("mxtpu_flash_dq", "dq"),
                       ("mxtpu_flash_dkv", "dkv")):
        _, avals = _pallas_calls(jnp.bfloat16)[name]
        hlo = "(%s) custom-call(operands" % ", ".join(
            "%s[%s]" % (names[str(a.dtype)], ",".join(map(str, a.shape)))
            for a in avals)
        assert _classify(hlo, 2, 256, 64) == kind, hlo


def test_attention_op_impl_attr():
    """impl='flash' forces the Pallas path through the symbol op (interpret
    mode off-TPU would fail to compile, so only check attr plumbing +
    default XLA path numerics here)."""
    import mxnet_tpu as mx
    q, k, v = _qkv(B=1, H=1, T=64, D=16, seed=3)
    qs, ks, vs = (mx.sym.Variable(n) for n in ("q", "k", "v"))
    net = mx.sym.dot_product_attention(qs, ks, vs, causal=True, impl="xla")
    ex = net.bind(mx.cpu(), {"q": mx.nd.array(np.asarray(q)),
                             "k": mx.nd.array(np.asarray(k)),
                             "v": mx.nd.array(np.asarray(v))})
    out = ex.forward()[0].asnumpy()
    ref = np.asarray(attention_reference(q, k, v, causal=True))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_rtc_pallas_kernel():
    """Runtime Pallas compilation (parity: reference rtc.py MXRtc — CUDA
    source JIT becomes a Pallas kernel body)."""
    import mxnet_tpu as mx

    def kern(x_ref, y_ref, out_ref):
        out_ref[...] = x_ref[...] * 2.0 + y_ref[...]

    rtc = mx.rtc.Rtc("axpb", ["x", "y"], ["out"], kern)
    x = mx.nd.array(RS(0).rand(16, 128).astype(np.float32))
    y = mx.nd.array(RS(1).rand(16, 128).astype(np.float32))
    out = mx.nd.zeros((16, 128))
    rtc.push([x, y], [out])
    np.testing.assert_allclose(out.asnumpy(),
                               x.asnumpy() * 2 + y.asnumpy(), rtol=1e-6)
