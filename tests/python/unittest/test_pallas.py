"""Pallas flash-attention kernel tests (SURVEY.md §7 "Pallas kernels for the
hot ops"; runs the kernel in interpret mode on the CPU harness — the same
code path compiles natively on TPU, where it is ~2x XLA attention at
T=4096)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_kernels import flash_attention, flash_available
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
