"""On-chip numerics assertions for the Pallas kernels: each kernel COMPILED
(no interpret mode) on the TPU and compared against its XLA formulation at
bf16-appropriate tolerances — flash attention forward and both backward
kernels, including the corners of its shape guard (those with f32 operands
too) and a latent-attention layer's value heads of 128 beside query/key heads
of 192, the grouped products of the routed experts at the benchmark cell's
own shape, the state-space scan's kernels, the gated delta rule's kernels
against its token-by-token recurrence, and the short causal convolution's
backward kernel and the gated group norm's two kernels against float32
autodiff.  The interpret-mode twins of
these checks run on the CPU harness (test_pallas.py, test_moe.py,
test_ssm.py, test_kda.py).

Run on a machine with a chip:  python tools/tpu_numerics_check.py
Prints one PASS line per check; exits non-zero on any mismatch, on a shape
its guard no longer admits, and without a TPU.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

# (B, H, T, D, causal[, Dv]); four sit on flash_available's budgets (T*D =
# 2**20 at each lane width it admits, and docs/long_context.md's shape); the
# last is kimi-linear-steps-t4096's latent attention, value heads of 128
FLASH_SHAPES = [(2, 4, 512, 64, False),
                (2, 4, 512, 64, True),
                (1, 8, 1024, 128, True),
                (1, 2, 4096, 64, True),
                (1, 1, 8192, 128, True),
                (1, 1, 16384, 64, True),
                (1, 1, 4096, 256, True),
                (1, 32, 4096, 192, True, 128)]

# the routed experts of nemotron-twotower-steps-t4096: (experts held, hidden,
# expert width, rows of a block), and each held expert's rows in three steps:
# a balanced router's, one that sends nearly all to two experts, none at all
GROUPED_SHAPE = (8, 2688, 1856, 256)
GROUPED_RUNS = [(192, 190, 200, 185, 256, 130, 257, 126),
                (4000, 0, 1, 3071, 0, 512, 0, 300),
                (0, 0, 0, 0, 0, 0, 0, 0)]

# the state-space mixer of nemotron-twotower-steps-t4096: (B, T, H, P, G, N,
# chunk), bfloat16
SSD_SHAPE = (1, 4096, 64, 64, 8, 128, 128)


# the linear-attention mixer of kimi-linear-steps-t4096: (B, T, H, d_k, d_v),
# bfloat16, chunks of 64
KDA_SHAPE = (1, 4096, 32, 128, 128)

# the short causal convolutions of both hybrid cells, bfloat16, 4 taps, SiLU:
# (B, T, C, with a bias) the Mamba mixer's over xBC, the KDA mixer's q, k, v
CONV_SHAPES = [(1, 4096, 6144, True), (1, 4096, 4096, False)]

# the gated group norm of nemotron-twotower-steps-t4096's Mamba mixers: (B, T,
# C, G), and the scale of the gate: the cell's, and one where silu' saturates
GNORM_SHAPE = (1, 4096, 4096, 8)
GNORM_GATES = (1.0, 30.0)


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

    for (b, h_, t, d, causal, *dv) in FLASH_SHAPES:
        dv = dv[0] if dv else d
        assert flash_available((b, h_, t, d), (b, h_, t, d),
                               (b, h_, t, dv)), (b, h_, t, d, dv)
        # the guard plans VMEM at the f32 upper bound: its corners are
        # compiled with f32 operands too
        corner = t * d == 2 ** 20
        for dtype in (jnp.bfloat16, jnp.float32) if corner else (
                jnp.bfloat16,):
            rng = np.random.RandomState(0)
            q, k, v = (jnp.asarray(rng.randn(b, h_, t, w).astype(np.float32))
                       .astype(dtype) for w in (d, d, dv))
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
                      (b, h_, t, d, causal) + ((dv,) if dv != d else ()),
                      jnp.dtype(dtype).name,
                      flash_blocks(t, max(d, dv), jnp.dtype(dtype).itemsize),
                      *errs), flush=True)


def check_grouped_products():
    """``grouped_matmul`` (both layouts of the matrices, with and without
    the activation) and ``grouped_matmul_t`` against their plain forms
    (``ops/moe.py``), on rows laid out as ``moe_experts`` lays them out;
    only the rows that hold an assignment are compared, the rest being
    nobody's."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import moe, pallas_kernels as pk

    held, c, f, block = GROUPED_SHAPE
    assert pk.grouped_available(block, c, f, 2), GROUPED_SHAPE
    relu2 = moe.ACTIVATIONS["relu2"]
    rng = np.random.RandomState(2)
    bf16 = lambda *shape: jnp.asarray(  # noqa: E731
        rng.randn(*shape).astype(np.float32)).astype(jnp.bfloat16)
    up, down = bf16(held, f, c) * 0.02, bf16(held, c, f) * 0.02
    for runs in GROUPED_RUNS:
        counts = jnp.asarray(runs, jnp.int32)
        cap = moe.capacity(4096, 6, 128, held)[1 if sum(runs) < 6144 else 2]
        order = jnp.arange(4096 * 6, dtype=jnp.int32)
        _, valid, tiles, live = jax.jit(
            lambda o, n: moe._layout(o, n, cap, block))(order, counts)
        keep = valid[:, None]
        x, hid = bf16(cap, c), bf16(cap, f)
        cases = [("up", (x, up), dict(transpose_rhs=True, act=relu2)),
                 ("down", (hid, down), dict(transpose_rhs=True)),
                 ("d_hid", (x, down), dict(out_dtype=jnp.float32)),
                 ("d_x", (hid, up), {})]
        errs = []
        for name, operands, kw in cases:
            got, want = (jax.jit(lambda a, w, fn=fn: jnp.where(keep, fn(
                a, w, *tiles, live, **kw), 0))(*operands)
                         for fn in (pk.grouped_matmul, moe.grouped_matmul))
            errs.append(_rel(got, want))
            assert errs[-1] < 2e-2, "grouped %s rel err %.2e at %s" % (
                name, errs[-1], runs)
        # the transposed product reads the rows that hold nothing too: they
        # are zero there, as the layer's backward makes them
        for name, (a, b) in (("d_up", (hid, x)), ("d_down", (x, hid))):
            a, b = jnp.where(keep, a, 0), jnp.where(keep, b, 0)
            got, want = (jax.jit(lambda a, b, fn=fn: fn(a, b, *tiles, live,
                                                        held))(a, b)
                         for fn in (pk.grouped_matmul_t,
                                    moe.grouped_matmul_t))
            errs.append(_rel(got, want))
            assert errs[-1] < 2e-2, "grouped %s rel err %.2e at %s" % (
                name, errs[-1], runs)
        print("PASS grouped products %s runs %s blocks %d of %d tiles %s %s"
              "  rel err up %.1e down %.1e d_hid %.1e d_x %.1e d_up %.1e "
              "d_down %.1e" % (
                  GROUPED_SHAPE, runs, int(live), cap // block,
                  pk.grouped_blocks(block, c, f, 2),
                  pk.grouped_blocks(block, f, c, 2), *errs), flush=True)


def check_ssd_scan():
    """``ssm_scan`` through the kernels against its plain form, forward and
    the gradients of its five inputs, at the rates where the state carried
    from chunk to chunk matters (``test_ssm.py``: dt in 0.001-0.1, A in
    1-16), which the benchmark's own seed does not reach.  Both sides take
    the same bfloat16 inputs; the plain form's float32 (L, L) products run
    as one bfloat16 pass on the chip too."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import ssm, pallas_kernels as pk

    bsz, t, h, p, g, n, chunk = SSD_SHAPE
    assert pk.ssd_available(t, h, p, g, n, chunk, 2), SSD_SHAPE
    rng = np.random.RandomState(3)
    bf16 = lambda *shape: jnp.asarray(  # noqa: E731
        rng.randn(*shape).astype(np.float32)).astype(jnp.bfloat16)
    f32 = lambda v: jnp.asarray(v, jnp.float32)  # noqa: E731
    step = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (h,)))
    args = (bf16(bsz, t, h * p + 2 * g * n), bf16(bsz, t, h) * 0.5,
            f32(np.log(rng.uniform(1.0, 16.0, (h,)))), f32(rng.randn(h)),
            f32(np.log(np.expm1(step))))
    weight = bf16(bsz, t, h * p).astype(jnp.float32)
    plain = lambda *a: ssm._scan(*a, h=h, p=p, g=g, chunk=chunk)  # noqa: E731
    kernels = lambda *a: ssm._scan_kernels(*a, h, p, g, chunk)  # noqa: E731

    def both(fn):
        def loss(*a):
            y = fn(*a)
            return (y.astype(jnp.float32) * weight).sum(), y
        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
        return (y,) + grads
    got, want = both(kernels), both(plain)
    # the last chunk alone, its entering state left out: how much of y the
    # carried state is at these rates
    alone = jax.jit(plain)(*(v[:, t - chunk:] if v.ndim > 1 else v
                             for v in args))
    carried = _rel(alone, want[0][:, t - chunk:])
    assert carried > 0.05, "the carried state is %.1e of y" % carried
    names = "y data dt a_log d dt_bias".split()
    errs = [_rel(a, b) for a, b in zip(got, want)]
    for name, err in zip(names, errs):
        assert err < (2e-2 if name == "y" else 5e-2), \
            "ssd_scan %s rel err %.2e at %s" % (name, err, SSD_SHAPE)
    print("PASS ssd_scan %s bfloat16 heads a step %d carried state %.2f of y"
          "  rel err %s" % (SSD_SHAPE, pk.ssd_blocks(t, h, p, g, n, chunk, 2),
                            carried, " ".join("%s %.1e" % (k, e) for k, e in
                                              zip(names, errs))), flush=True)


def check_kda_scan():
    """``kda_scan`` through its kernels (the chunked form: sub-blocks, the
    unit-triangular solve, a state a chunk, all in VMEM; the backward
    written by hand) against the token-by-token recurrence of
    ``benchmark/reference/kda_lm.py`` in float32 (sums of products, no dot),
    forward and the gradients of its seven inputs, at the published rates
    (``test_kda.py``: the decay's step in 0.001-0.1, A in 1-16), where the
    state carried from chunk to chunk matters and which the benchmark's own
    seed does not reach.  Both sides take the same bfloat16 inputs."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import kda, pallas_kernels as pk
    from benchmark.reference import kda_lm as ref

    bsz, t, h, dk, dv = KDA_SHAPE
    chunk = 64
    assert pk.kda_available(t, h, dk, dv, chunk, 2), KDA_SHAPE
    rng = np.random.RandomState(4)
    bf16 = lambda *shape: jnp.asarray(  # noqa: E731
        rng.randn(*shape).astype(np.float32)).astype(jnp.bfloat16)
    f32 = lambda v: jnp.asarray(v, jnp.float32)  # noqa: E731
    step = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (h * dk,)))
    args = (bf16(bsz, t, h * dk), bf16(bsz, t, h * dk), bf16(bsz, t, h * dv),
            bf16(bsz, t, h * dk) * 0.5, bf16(bsz, t, h),
            f32(np.log(rng.uniform(1.0, 16.0, (h,)))),
            f32(np.log(np.expm1(step))))
    weight = bf16(bsz, t, h * dv).astype(jnp.float32)
    chunked = lambda *a: kda._scan_kernels(*a, h, chunk)  # noqa: E731

    def recurrence(q, k, v, gate, beta, a_log, dt_bias):
        heads = lambda x: x.astype(jnp.float32).reshape(  # noqa: E731
            bsz, t, h, -1)
        g, b = kda.kda_gates(gate, beta, a_log, dt_bias, h)
        o = jax.vmap(ref.delta_rule)(
            ref._l2norm(heads(q)) * dk ** -0.5, ref._l2norm(heads(k)),
            heads(v), g, b)
        return o.reshape(bsz, t, h * dv)

    def both(fn):
        def loss(*a):
            y = fn(*a)
            return (y.astype(jnp.float32) * weight).sum(), y
        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(7)), has_aux=True))(*args)
        return (y,) + grads
    got, want = both(chunked), both(recurrence)
    alone = jax.jit(chunked)(*(v[:, t - chunk:] if v.ndim > 1 else v
                               for v in args))
    carried = _rel(alone, want[0][:, t - chunk:])
    assert carried > 0.05, "the carried state is %.1e of o" % carried
    names = "o q k v gate beta a_log dt_bias".split()
    errs = [_rel(a, b) for a, b in zip(got, want)]
    for name, err in zip(names, errs):
        assert err < (2e-2 if name == "o" else 5e-2), \
            "kda_scan %s rel err %.2e at %s" % (name, err, KDA_SHAPE)
    print("PASS kda_scan %s bfloat16 chunks of %d carried state %.2f of o"
          "  rel err %s" % (KDA_SHAPE, chunk, carried,
                            " ".join("%s %.1e" % (k, e) for k, e in
                                     zip(names, errs))), flush=True)


def check_causal_conv():
    """``causal_conv1d``'s backward (the kernel ``mxtpu_conv_bwd`` on the
    chip) against autodiff of the plain shifted sum in float32 on the same
    bfloat16 inputs, y's and the three gradients' largest error relative to
    the largest entry."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import ssm, pallas_kernels as pk
    from mxnet_tpu.ops.nn import ACTIVATIONS

    silu = ACTIVATIONS["silu"]
    rng = np.random.RandomState(5)
    for bsz, t, c, with_bias in CONV_SHAPES:
        assert pk.conv_blocks(t, c, 4, 2) is not None, (t, c)
        bf16 = lambda *shape: jnp.asarray(  # noqa: E731
            rng.randn(*shape).astype(np.float32)).astype(jnp.bfloat16)
        args = (bf16(bsz, t, c), bf16(c, 4) * 0.5,
                bf16(c) if with_bias else None)
        dy = bf16(bsz, t, c)

        def plain(x, w, b):
            y = ssm._conv_pre(x, w, b)
            return silu(y)

        def grads(fn, cast):
            y, vjp = jax.vjp(fn, *(None if a is None else a.astype(cast)
                                   for a in args))
            return (y,) + vjp(dy.astype(cast))
        got = jax.jit(lambda: grads(
            lambda *a: ssm.causal_conv(*a, silu), jnp.bfloat16))()
        want = jax.jit(lambda: grads(plain, jnp.float32))()
        errs = [_rel(a, b) for a, b in zip(got, want) if b is not None]
        names = "y data weight bias".split()[:len(errs)]
        for name, err in zip(names, errs):
            assert err < 2e-2, "causal_conv %s rel err %.2e at %s" % (
                name, err, (bsz, t, c))
        print("PASS causal_conv %s bias %s bfloat16 blocks %s  rel err %s"
              % ((bsz, t, c), with_bias, pk.conv_blocks(t, c, 4, 2),
                 " ".join("%s %.1e" % (k, e) for k, e in zip(names, errs))),
              flush=True)


def check_gated_norm():
    """The gated ``RMSNorm`` over groups (the kernels ``mxtpu_gnorm_fwd`` and
    ``_bwd`` on the chip) against autodiff of the plain form in float32 on
    the same bfloat16 inputs: the result's and the three gradients' largest
    error relative to the largest entry."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.ops.registry import get_op

    bsz, t, c, g = GNORM_SHAPE
    assert pk.gnorm_available(bsz * t, c, g, 2), GNORM_SHAPE
    rng = np.random.RandomState(6)
    bf16 = lambda *shape: jnp.asarray(  # noqa: E731
        rng.randn(*shape).astype(np.float32)).astype(jnp.bfloat16)

    def plain(x, w, z):
        u = x * jax.nn.silu(z)
        grouped = u.reshape(u.shape[:-1] + (g, c // g))
        r = jax.lax.rsqrt(jnp.mean(grouped * grouped, -1, keepdims=True)
                          + 1e-5)
        return (grouped * r).reshape(u.shape) * w

    def op(x, w, z):
        return get_op("RMSNorm").fn(x, w, z, eps=1e-5, num_groups=g,
                                    gated=True)
    for scale in GNORM_GATES:
        args = (bf16(bsz, t, c), bf16(c), bf16(bsz, t, c) * scale)
        dy = bf16(bsz, t, c)
        assert "mxtpu_gnorm_fwd" in str(jax.make_jaxpr(op)(*args))

        def grads(fn, cast):
            y, vjp = jax.vjp(fn, *(a.astype(cast) for a in args))
            return (y,) + vjp(dy.astype(cast))
        got = jax.jit(lambda: grads(op, jnp.bfloat16))()
        want = jax.jit(lambda: grads(plain, jnp.float32))()
        names = "y data gamma gate".split()
        errs = [_rel(a, b) for a, b in zip(got, want)]
        for name, err in zip(names, errs):
            assert err < 2e-2, "gated_norm %s rel err %.2e, gate x %g" % (
                name, err, scale)
        print("PASS gated_norm %s bfloat16 gate x %g rows %s  rel err %s"
              % (GNORM_SHAPE, scale, pk.gnorm_blocks(bsz * t, c, g, 2),
                 " ".join("%s %.1e" % (k, e) for k, e in zip(names, errs))),
              flush=True)


if __name__ == "__main__":
    import jax
    if jax.default_backend() != "tpu":
        sys.exit("tpu_numerics_check: the kernels are compiled for a TPU and "
                 "the default backend is %r — nothing was checked"
                 % jax.default_backend())
    from mxnet_tpu.base import enable_compile_cache
    enable_compile_cache()
    check_flash_attention()
    check_grouped_products()
    check_ssd_scan()
    check_kda_scan()
    check_causal_conv()
    check_gated_norm()
    print("ALL TPU NUMERICS CHECKS PASSED")
