"""On-chip numerics assertions for the Pallas kernels: each kernel COMPILED
(no interpret mode) on the TPU and compared against its XLA formulation at
bf16-appropriate tolerances — flash attention forward and both backward
kernels, including the corners of its shape guard (those with f32 operands
too), and NormConv at the four ResNet-50 stage shapes.  The interpret-mode
twins of these checks run on the CPU harness (test_pallas.py,
test_norm_conv.py).

Run on a machine with a chip:  python tools/tpu_numerics_check.py
Prints one PASS line per check; exits non-zero on any mismatch, on a shape
its guard no longer admits, and without a TPU.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

# (B, H, T, D, causal); the last four sit on flash_available's budgets
# (T*D = 2**20 at each lane width it admits, and docs/long_context.md's shape)
FLASH_SHAPES = [(2, 4, 512, 64, False),
                (2, 4, 512, 64, True),
                (1, 8, 1024, 128, True),
                (1, 2, 4096, 64, True),
                (1, 1, 8192, 128, True),
                (1, 1, 16384, 64, True),
                (1, 1, 4096, 256, True)]

# (H=W, K, stride, pad, Cin, Cout): one conv of each ResNet-50 stage kind
NORM_CONV_SHAPES = [(56, 1, 1, 0, 256, 64),
                    (56, 3, 1, 1, 64, 64),
                    (56, 3, 2, 1, 128, 128),
                    (56, 1, 2, 0, 256, 512)]


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-6)


def check_flash_attention():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import (flash_attention, flash_available,
                                              flash_blocks)
    from mxnet_tpu.parallel.ring import attention_reference

    def loss_f(fn):
        return lambda a, b_, c: (fn(a, b_, c).astype(jnp.float32) ** 2).sum()

    for (b, h_, t, d, causal) in FLASH_SHAPES:
        assert flash_available((b, h_, t, d)), (b, h_, t, d)
        # the guard plans VMEM at the f32 upper bound: its corners are
        # compiled with f32 operands too
        corner = t * d == 2 ** 20
        for dtype in (jnp.bfloat16, jnp.float32) if corner else (
                jnp.bfloat16,):
            rng = np.random.RandomState(0)
            q, k, v = (jnp.asarray(rng.randn(b, h_, t, d).astype(np.float32))
                       .astype(dtype) for _ in range(3))
            q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
            flash = lambda a, b_, c: flash_attention(a, b_, c, causal)  # noqa: E731
            ref = lambda a, b_, c: attention_reference(  # noqa: E731
                a, b_, c, causal=causal)
            errs = [_rel(jax.jit(flash)(q, k, v), jax.jit(ref)(q32, k32, v32))]
            assert errs[0] < 2e-2, "flash fwd rel err %.2e at %s" % (
                errs[0], (b, h_, t, d, causal))
            # gradients: pallas backward kernels vs autodiff of the reference
            gp = jax.jit(jax.grad(loss_f(flash), argnums=(0, 1, 2)))(q, k, v)
            gr = jax.jit(jax.grad(loss_f(ref), argnums=(0, 1, 2)))(
                q32, k32, v32)
            for name, a, bb in zip("qkv", gp, gr):
                errs.append(_rel(a, bb))
                assert errs[-1] < 5e-2, "flash d%s rel err %.2e at %s" % (
                    name, errs[-1], (b, h_, t, d, causal))
            print("PASS flash_attention %s %s blocks %s  rel err fwd %.1e "
                  "dq %.1e dk %.1e dv %.1e" % (
                      (b, h_, t, d, causal), jnp.dtype(dtype).name,
                      flash_blocks(t, d, jnp.dtype(dtype).itemsize),
                      *errs), flush=True)


def check_norm_conv():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_conv import norm_conv, norm_conv_available

    for (h, k, s, p, cin, cout) in NORM_CONV_SHAPES:
        assert norm_conv_available((8, h, h, cin), (k, k, cin, cout),
                                   (s, s), (p, p)), (h, k, s, p, cin, cout)
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(8, h, h, cin).astype(np.float32)) \
            .astype(jnp.bfloat16)
        w = jnp.asarray((rng.randn(k, k, cin, cout) * 0.05)
                        .astype(np.float32)).astype(jnp.bfloat16)
        sc = jnp.asarray(rng.rand(cin).astype(np.float32) + 0.5)
        sh = jnp.asarray(rng.randn(cin).astype(np.float32))

        def run(up):
            return jax.jit(lambda *a: norm_conv(
                *a, kernel=k, stride=s, pad=p, relu=True, prologue=True,
                stats=True, use_pallas=up))(x, w, sc, sh)
        for name, a, b in zip(("y", "sum", "sumsq"), run(True), run(False)):
            err = _rel(a, b)
            assert err < 2e-2, "norm_conv %s rel err %.2e" % (name, err)
        print("PASS norm_conv k=%d s=%d %dx%d %d->%d" % (k, s, h, h, cin,
                                                         cout), flush=True)


if __name__ == "__main__":
    import jax
    if jax.default_backend() != "tpu":
        sys.exit("tpu_numerics_check: the kernels are compiled for a TPU and "
                 "the default backend is %r — nothing was checked"
                 % jax.default_backend())
    from mxnet_tpu.base import enable_compile_cache
    enable_compile_cache()
    check_flash_attention()
    check_norm_conv()
    print("ALL TPU NUMERICS CHECKS PASSED")
