"""Pallas TPU kernels for the hot ops (SURVEY.md §7: "Pallas kernels where
XLA fusion is insufficient").

``flash_attention``: blocked attention forward that never materialises the
(T, T) score matrix — Q tiles stay resident in VMEM while K/V blocks stream
through, folded with the online-softmax recurrence (running max ``m``,
normaliser ``l``, f32 accumulator).  The backward pass is two further
Pallas kernels (``_dq_kernel``, ``_dkv_kernel``) recomputing scores against
the saved log-sum-exp under ``jax.custom_vjp`` (flash-style recompute:
O(T) memory in both directions).

Used by ``dot_product_attention`` (ops/attention.py) on TPU for long
sequences; everything is shape-guarded so XLA's fused attention remains the
fallback.  Tested in Pallas interpret mode on the CPU harness and compiled on
the chip by ``tools/tpu_numerics_check.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention", "flash_available"]

_NEG_INF = -1e30


# VMEM budgets of the shape guard, in bytes at the f32 upper bound.  What
# they admit was compiled, forward and both backward kernels, on a v5e
# (libtpu 0.0.34): the corners T*D = 2**20 at D = 64, 128 and 256 pass in
# f32 and bf16; T=32768, D=32 in f32 is what the compiler refuses (128 MB
# by the dK/dV estimate below), and it is no longer admitted.
_KV_BUDGET = 8 * 1024 * 1024
_DKV_BUDGET = 64 * 1024 * 1024


def flash_available(q_shape, k_shape=None, v_shape=None, block_q=128,
                    block_k=128):
    """Shape guard: self-attention only (q/k/v shapes equal), T divisible
    into blocks, D lane-friendly, and each kernel's whole-T residents must
    fit VMEM: one head's K+V in the forward and dQ kernels, and in the
    dK/dV kernel q, dO and the (T, 1) lse/delta columns — double-buffered,
    with the last dimension padded to the 128 lanes of a VMEM tile, so a
    narrow head costs as much as D=128 and each column as much as a
    (T, 128) block."""
    if len(q_shape) != 4:
        return False
    for other in (k_shape, v_shape):
        if other is not None and tuple(other) != tuple(q_shape):
            return False  # cross-attention -> XLA path
    t, d = q_shape[2], q_shape[3]
    if 2 * t * d * 4 > _KV_BUDGET:
        return False
    if 2 * 2 * t * (max(d, 128) + 128) * 4 > _DKV_BUDGET:
        return False
    return t % block_q == 0 and t % block_k == 0 and t >= block_q and \
        d % 8 == 0 and d <= 256


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k, seq_len):
    # refs carry one (bh) slice: q (1, block_q, D), k/v (1, T, D)
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
    bq, d = q.shape
    q_pos = j * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    def fold(kb, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        if causal:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        blk_max = jnp.max(s, axis=1, keepdims=True)
        new_m = jnp.maximum(m, blk_max)
        p = jnp.exp(s - new_m)
        corr = jnp.exp(m - new_m)
        l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * corr + jax.lax.dot(p, v)
        return acc, new_m, l

    acc = jnp.zeros((bq, d), jnp.float32)
    m = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    if causal:
        # blocks at or below the diagonal only; ceil so partial blocks count
        num_kb = ((j + 1) * block_q + block_k - 1) // block_k
    else:
        num_kb = seq_len // block_k
    acc, m, l = jax.lax.fori_loop(0, num_kb, fold, (acc, m, l))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # log-sum-exp residual for the blocked backward
    lse_ref[0] = m + jnp.log(l)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                    block_k=128, interpret=False):
    """Blocked attention over (B, H, T, D); same semantics as
    ``attention_reference``."""
    return _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k,
                           interpret)[0]


def _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret):
    b, h, t, d = q.shape
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d)
    kernel = functools.partial(_fwd_kernel, scale=sc, causal=causal,
                               block_q=block_q, block_k=block_k, seq_len=t)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32),
        ],
        interpret=interpret,
        name="mxtpu_flash_fwd",
    )(qf, kf, vf)
    return out.reshape(b, h, t, d), lse.reshape(b, h, t, 1)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k,
                               interpret)
    return out, (q, k, v, out, lse)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               scale, causal, block_q, block_k, seq_len):
    """dQ: one Q-tile resident, K/V blocks stream (mirrors the forward)."""
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]                       # (bq, 1) f32
    delta = delta_ref[0]                   # (bq, 1) f32
    bq, d = q.shape
    q_pos = j * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    def fold(kb, dq):
        kblk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        vblk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ()))) * scale
        if causal:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)               # masked entries underflow to 0
        dp = jax.lax.dot_general(do, vblk, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot(ds, kblk)

    if causal:
        num_kb = ((j + 1) * block_q + block_k - 1) // block_k
    else:
        num_kb = seq_len // block_k
    dq = jax.lax.fori_loop(0, num_kb, fold, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dk_ref,
                dv_ref, *, scale, causal, block_q, block_k, seq_len):
    """dK/dV: one K/V-tile resident, Q/dO blocks stream; causal skips the
    Q-blocks strictly above the diagonal."""
    j = pl.program_id(1)
    kblk = k_ref[0].astype(jnp.float32)    # (bk, d)
    vblk = v_ref[0].astype(jnp.float32)
    bk, d = kblk.shape
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)

    def fold(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), :]
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), :]
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ()))) * scale
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)               # (bq, bk)
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, vblk, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * scale
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))
        return dk, dv

    start_qb = (j * block_k) // block_q if causal else 0
    zeros = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(start_qb, seq_len // block_q, fold,
                               (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    """Blocked flash backward as TWO Pallas kernels (dq; dk+dv), recomputing
    scores against the saved log-sum-exp — the (T, T) matrix never
    materialises, all matmuls on the MXU, f32 accumulators."""
    q, k, v, out, lse = res
    b, h, t, d = q.shape
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    # delta = rowsum(dO * O): one fused elementwise+reduce pass in XLA
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(
        axis=-1, keepdims=True)
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d)
    gf = g.reshape(b * h, t, d)
    lsef = lse.reshape(b * h, t, 1)
    deltaf = delta.reshape(b * h, t, 1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=sc, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=t),
        grid=(b * h, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        interpret=interpret,
        name="mxtpu_flash_dq",
    )(qf, kf, vf, gf, lsef, deltaf)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=sc, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=t),
        grid=(b * h, t // block_k),
        in_specs=[
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, t, d), v.dtype),
        ],
        interpret=interpret,
        name="mxtpu_flash_dkv",
    )(qf, gf, lsef, deltaf, kf, vf)
    return (dq.reshape(b, h, t, d), dk.reshape(b, h, t, d),
            dv.reshape(b, h, t, d))


def _flash_bwd_xla(causal, scale, block_q, block_k, interpret, res, g):
    """Blocked flash backward (pure XLA): recompute scores one K-block at a
    time against the saved log-sum-exp, so the (T, T) matrix never
    materialises in the backward either — O(T·block) live memory, matmuls
    on the MXU.  Kept as the reference implementation the Pallas kernels
    are tested against (the forward itself is a Pallas kernel, so this is
    not a runtime fallback)."""
    q, k, v, out, lse = res
    b, h, t, d = q.shape
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    q_pos = jnp.arange(t)[:, None]
    nkb = t // block_k
    dsum = (gf * out.astype(jnp.float32)).sum(axis=-1, keepdims=True)

    # pass 2 (blocked): gradients per K-block
    def grad_fold(kb, carry):
        dq, dk, dv = carry
        kb_ = jax.lax.dynamic_slice_in_dim(k, kb * block_k, block_k,
                                           2).astype(jnp.float32)
        vb_ = jax.lax.dynamic_slice_in_dim(v, kb * block_k, block_k,
                                           2).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb_) * sc
        if causal:
            k_pos = kb * block_k + jnp.arange(block_k)[None, :]
            mask = (k_pos <= q_pos)[None, None]
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)                              # (b,h,t,bk)
        dvb = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vb_)
        ds = p * (dp - dsum) * sc
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kb_)
        dkb = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        dk = jax.lax.dynamic_update_slice_in_dim(dk, dkb, kb * block_k, 2)
        dv = jax.lax.dynamic_update_slice_in_dim(dv, dvb, kb * block_k, 2)
        return dq, dk, dv

    zeros = jnp.zeros((b, h, t, d), jnp.float32)
    dq, dk, dv = jax.lax.fori_loop(0, nkb, grad_fold,
                                   (zeros, zeros, zeros))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
