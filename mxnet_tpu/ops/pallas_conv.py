"""Pallas fused NormConv kernel: (BN-apply + ReLU) -> Conv -> (stats) in one
HBM sweep each way.

Why (docs/perf.md round-3 roofline): the XLA formulation of a pre-activation
conv net needs ~4 activation sweeps per layer forward (conv write, stats
read, apply read+write) and measures at 85% of that formulation's bandwidth
floor — the MXU is mostly idle.  This kernel removes two of the sweeps:

- **prologue**: the *previous* BatchNorm's scale/shift (+ReLU) is applied to
  the input while it streams HBM->VMEM for the convolution, so the BN "apply"
  pass never materialises;
- **epilogue**: per-channel sum and sum-of-squares of the conv output are
  accumulated while the output tile is still in VMEM, so the *next*
  BatchNorm's statistics pass never reads the activation again.

The conv itself is a tap-decomposed implicit GEMM: the whole (H, W, Cin)
feature map of one image is VMEM-resident (guarded — ResNet-50 layers are
0.2-1.6 MB in bf16 against ~16 MB VMEM), each of the K*K taps is one MXU
`dot` of the strided spatial slice against the (Cin, Cout) weight plane,
accumulated in f32.

The backward is XLA (jax.vjp of the conv + elementwise glue) under
`jax.custom_vjp`; per-channel reductions accumulate in f32.  A pure-XLA
composition (`norm_conv_ref`) with identical semantics serves CPU tests,
f64 parity runs and non-TPU backends.

Capability parity: the reference fuses conv+BN only through cuDNN's fused
paths (reference src/operator/cudnn_batch_norm*, convolution-inl.h:563);
this is the TPU-native equivalent of that fusion, owned by the framework
instead of the vendor library.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["norm_conv", "norm_conv_available", "NC_VMEM_BUDGET"]

# VMEM working-set budget (bytes) for the whole-image blocking, in units of
# the estimate below.  The estimate is not Mosaic's own accounting (the
# pack-phase temporaries are not shared the way it assumes), so the budget is
# set from what compiles: on a v5e (libtpu 0.0.34) every shape tried whose
# estimate is under 15 MB compiled — every conv shape of ResNet-50's four
# stages at 224x224, the largest being the 56x56 1x1/s2 256->512 shortcut at
# 14.8 MB, and the whole bf16 train step with MXNET_NORM_CONV=1 — while
# refusals start at 16.3 MB (3x3/s2 112x112 64->128; some larger estimates
# still pass).
NC_VMEM_BUDGET = 15 * 1024 * 1024


def _geom(h, w, k, s, p):
    oh = (h + 2 * p - k) // s + 1
    ow = (w + 2 * p - k) // s + 1
    return oh, ow


def norm_conv_available(x_shape, w_shape, stride, pad, dilate=(1, 1),
                        num_group=1, dtype=jnp.bfloat16):
    """Shape guard for the Pallas path.

    x_shape: (N, H, W, Cin) channel-last; w_shape: (K, K, Cin, Cout) HWIO.
    Conservative: 2-D, square 1x1/3x3 kernels, stride 1 or 2, pad 0/1,
    ungrouped, undilated, MXU-friendly channel counts, and the whole-image
    working set must fit the VMEM budget (excludes the 7x7 ImageNet stem,
    which stays on XLA's conv — Cin=3 would waste the MXU anyway).
    """
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    n, h, w, cin = x_shape
    kh, kw, wcin, cout = w_shape
    if kh != kw or kh not in (1, 3):
        return False
    if wcin != cin or num_group != 1:
        return False
    if tuple(dilate) != (1, 1):
        return False
    s = tuple(stride)
    if s not in ((1, 1), (2, 2)):
        return False
    p = tuple(pad)
    if p[0] != p[1] or p[0] not in (0, 1) or p[0] >= kh:
        return False
    if cin % 8 != 0 or cout % 8 != 0 or cin < 16:
        return False
    oh, ow = _geom(h, w, kh, s[0], p[0])
    if oh < 1 or ow < 1:
        return False
    esize = jnp.dtype(dtype).itemsize
    vmem = (
        2 * h * w * cin * esize            # x block, double-buffered
        + kh * kw * cin * cout * esize     # weight plane(s)
        + 2 * oh * ow * cout * 4           # f32 accumulator (loop carry)
        + 2 * oh * ow * cout * esize       # output block, double-buffered
    )
    if not (kh == 1 and s[0] == 1):
        # pack-phase shapes additionally stage the padded input, the
        # channel-packed scratch and the per-tap slice temporaries
        hp, wp = _pad_geom(h, w, kh, s[0], p[0], oh, ow)
        vmem += (hp * wp * cin * esize
                 + hp * ow * kh * cin * esize
                 + 3 * s[0] * oh * s[0] * ow * cin * esize)
    return vmem <= NC_VMEM_BUDGET


def _pad_geom(h, w_sp, k, stride, pad, oh, ow):
    """Padded-buffer extents; stride-2 taps read even-sized spans (gathered
    by reshape+index — Mosaic only lowers unit-stride slices), so the
    buffer carries slack zeros on the bottom/right when needed."""
    hp = max(h + 2 * pad, (k - 1 + stride * oh) if stride > 1 else 0)
    wp = max(w_sp + 2 * pad, (k - 1 + stride * ow) if stride > 1 else 0)
    return hp, wp


def _nc_kernel(x_ref, w_ref, s_ref, t_ref, o_ref, *refs, k, stride, pad,
               oh, ow, relu, prologue, stats):
    stat_refs, xw_ref = refs[:-1], refs[-1]
    x = x_ref[0]                                   # (H, W, Cin)
    h, w_sp, cin = x.shape
    if prologue:
        xh = x * s_ref[0] + t_ref[0]               # broadcast over (Cin,)
        if relu:
            xh = jnp.maximum(xh, jnp.zeros((), xh.dtype))
    else:
        xh = x
    cout = w_ref.shape[2]
    if k == 1 and stride == 1:
        # pure matmul — no staging, no tap loop
        acc = jax.lax.dot_general(xh.reshape(h * w_sp, cin), w_ref[0],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    else:
        # Two-level tap decomposition sized for Mosaic's constraints:
        #  - the K width-taps (and the width stride phase) are folded into
        #    the channel (lane) dimension ONCE, staged in a VMEM scratch of
        #    shape (HP, OW, K*Cin) — so the weight K-dim is K*Cin and the
        #    MXU runs K times fewer, fatter matmuls;
        #  - the K row-taps run as a fori_loop of dynamic reads on dim 0,
        #    the one dimension where Mosaic allows unaligned dynamic
        #    offsets (a K*K-unrolled version overflowed scoped VMEM, and
        #    dynamic sublane offsets must be provably 8-aligned).
        hp, _ = _pad_geom(h, w_sp, k, stride, pad, oh, ow)
        if pad or hp > h:
            zt = jnp.zeros((pad, w_sp + 2 * pad, cin), xh.dtype)
            zb = jnp.zeros((hp - h - pad, w_sp + 2 * pad, cin), xh.dtype)
            zl = jnp.zeros((h, pad, cin), xh.dtype)
            xp = jnp.concatenate(
                [zt, jnp.concatenate([zl, xh, zl], axis=1), zb], axis=0)
        else:
            xp = xh
        wp_have = xp.shape[1]
        for dw in range(k):
            # columns dw, dw+s, ..., dw+s*(OW-1); the strided phase select
            # reads an s*OW span, padded right with slack zeros when the
            # buffer ends early (the slack positions are discarded)
            span = ow if stride == 1 else min(stride * ow, wp_have - dw)
            pv = jax.lax.slice(xp, (0, dw, 0), (hp, dw + span, cin))
            if stride > 1:
                if span < stride * ow:
                    pv = jnp.concatenate(
                        [pv, jnp.zeros((hp, stride * ow - span, cin),
                                       pv.dtype)], axis=1)
                pv = pv.reshape(hp, ow, stride, cin)[:, :, 0]
            xw_ref[:, :, dw * cin:(dw + 1) * cin] = pv

        def tap(dh, acc):
            v = xw_ref[pl.ds(dh, stride * oh)]     # (s*OH, OW, K*Cin)
            if stride > 1:
                v = v.reshape(oh, stride, ow, k * cin)[:, 0]
            return acc + jax.lax.dot_general(
                v.reshape(oh * ow, k * cin), w_ref[dh],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        acc = jax.lax.fori_loop(0, k, tap,
                                jnp.zeros((oh * ow, cout), jnp.float32))
    o_ref[0] = acc.reshape(oh, ow, cout).astype(o_ref.dtype)
    if stats:
        stat_refs[0][0, 0] = acc.sum(axis=0)
        stat_refs[1][0, 0] = (acc * acc).sum(axis=0)


def _nc_pallas_fwd(x, w, scale, shift, meta):
    k, stride, pad, relu, prologue, stats, interpret = meta
    n, h, w_sp, cin = x.shape
    cout = w.shape[3]
    oh, ow = _geom(h, w_sp, k, stride, pad)
    kernel = functools.partial(_nc_kernel, k=k, stride=stride, pad=pad,
                               oh=oh, ow=ow, relu=relu, prologue=prologue,
                               stats=stats)
    sc = scale.astype(x.dtype).reshape(1, cin)
    sh = shift.astype(x.dtype).reshape(1, cin)
    out_shape = [jax.ShapeDtypeStruct((n, oh, ow, cout), x.dtype)]
    out_specs = [pl.BlockSpec((1, oh, ow, cout), lambda i: (i, 0, 0, 0))]
    if stats:
        # (N, 1, Cout) so the block's trailing dims equal the array's (the
        # TPU lowering requires (8, 128)-divisible or full-dim blocks)
        out_shape += [jax.ShapeDtypeStruct((n, 1, cout), jnp.float32)] * 2
        out_specs += [pl.BlockSpec((1, 1, cout), lambda i: (i, 0, 0))] * 2
    if k == 1 and stride == 1:
        scratch = pltpu.VMEM((1, 1, 1), x.dtype)      # unused
    else:
        hp, _ = _pad_geom(h, w_sp, k, stride, pad, oh, ow)
        scratch = pltpu.VMEM((hp, ow, k * cin), x.dtype)
    # width taps live in the weight K-dim: (K, K, Cin, Cout)->(K, K*Cin, Cout)
    w2 = w.reshape(k, k * cin, cout)
    outs = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, w_sp, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((k, k * cin, cout), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, cin), lambda i: (0, 0)),
            pl.BlockSpec((1, cin), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[scratch],
        interpret=interpret,
    )(x, w2, sc, sh)
    y = outs[0]
    if stats:
        # per-image partials -> per-channel totals (tiny (N, Cout) reduce)
        return y, outs[1].sum(axis=(0, 1)), outs[2].sum(axis=(0, 1))
    return y, None, None


def _conv_dn(stride, pad):
    return dict(window_strides=(stride, stride),
                padding=[(pad, pad), (pad, pad)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _apply(x, scale, shift, relu):
    out = x * scale.astype(x.dtype).reshape(1, 1, 1, -1) \
        + shift.astype(x.dtype).reshape(1, 1, 1, -1)
    if relu:
        out = jnp.maximum(out, 0)
    return out


def norm_conv_ref(x, w, scale, shift, meta):
    """Pure-XLA composition with the same semantics (CPU tests, f64 parity,
    non-TPU backends; gradients via autodiff)."""
    k, stride, pad, relu, prologue, stats, _ = meta
    xh = _apply(x, scale, shift, relu) if prologue else x
    y = jax.lax.conv_general_dilated(xh, w, **_conv_dn(stride, pad))
    if stats:
        y32 = y.astype(jnp.promote_types(y.dtype, jnp.float32))
        return y, y32.sum(axis=(0, 1, 2)), jnp.square(y32).sum(axis=(0, 1, 2))
    return y, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _nc_core(x, w, scale, shift, meta):
    return _nc_pallas_fwd(x, w, scale, shift, meta)


def _nc_core_fwd(x, w, scale, shift, meta):
    out = _nc_pallas_fwd(x, w, scale, shift, meta)
    stats = meta[5]
    return out, (x, w, scale, shift, out[0] if stats else None)


def _nc_core_bwd(meta, res, cts):
    k, stride, pad, relu, prologue, stats, _ = meta
    x, w, scale, shift, y = res
    dy, dsum, dsq = cts
    if stats:
        # d(sum)/dy = 1, d(sumsq)/dy = 2y: fold the per-channel stat
        # cotangents into one elementwise pass over (dy, y)
        dy_eff = (dy.astype(jnp.float32)
                  + dsum.reshape(1, 1, 1, -1)
                  + 2.0 * y.astype(jnp.float32) * dsq.reshape(1, 1, 1, -1))
        dy_eff = dy_eff.astype(dy.dtype)
    else:
        dy_eff = dy
    xh = _apply(x, scale, shift, relu) if prologue else x
    conv = lambda a, b: jax.lax.conv_general_dilated(  # noqa: E731
        a, b, **_conv_dn(stride, pad))
    _, pullback = jax.vjp(conv, xh, w)
    dxh, dw = pullback(dy_eff)
    if prologue:
        if relu:
            dpre = jnp.where(xh > 0, dxh, jnp.zeros((), dxh.dtype))
        else:
            dpre = dxh
        dx = dpre * scale.astype(dpre.dtype).reshape(1, 1, 1, -1)
        acc = jnp.promote_types(x.dtype, jnp.float32)
        dscale = jnp.sum((dpre * x).astype(acc), axis=(0, 1, 2))
        dshift = jnp.sum(dpre.astype(acc), axis=(0, 1, 2))
        return (dx, dw, dscale.astype(scale.dtype),
                dshift.astype(shift.dtype))
    return dxh, dw, jnp.zeros_like(scale), jnp.zeros_like(shift)


_nc_core.defvjp(_nc_core_fwd, _nc_core_bwd)


def norm_conv(x, w, scale, shift, kernel, stride, pad, relu=True,
              prologue=True, stats=False, use_pallas=None, interpret=False):
    """Fused (apply + conv + stats) over channel-last tensors.

    x       : (N, H, W, Cin); w: (KH, KW, Cin, Cout) HWIO
    scale   : (Cin,) f32 — previous BN's gamma * rsqrt(var + eps)
    shift   : (Cin,) f32 — previous BN's beta - mean * scale
    returns : (y, ysum, ysumsq) — stats are f32 per-Cout-channel sums of the
              conv output (None when stats=False).
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" and norm_conv_available(
            x.shape, w.shape, (stride, stride), (pad, pad), dtype=x.dtype)
    meta = (kernel, stride, pad, bool(relu), bool(prologue), bool(stats),
            bool(interpret))
    if use_pallas or interpret:
        return _nc_core(x, w, scale, shift, meta)
    return norm_conv_ref(x, w, scale, shift, meta)
