"""Pallas TPU kernels for the hot ops (SURVEY.md §7: "Pallas kernels where
XLA fusion is insufficient").

``flash_attention``: blocked attention forward that never materialises the
(T, T) score matrix — Q tiles stay resident in VMEM while K/V blocks stream
through, folded with the online-softmax recurrence (running max ``m``,
normaliser ``l``, f32 accumulator).  The backward pass is two further
Pallas kernels (``_dq_kernel``, ``_dkv_kernel``) recomputing scores against
the saved log-sum-exp under ``jax.custom_vjp`` (flash-style recompute:
O(T) memory in both directions).

All three kernels form the score tile transposed, (block_k, block_q): keys
on sublanes, queries on lanes.  Max and sum over keys are then elementwise
across vregs, and everything a query row owns — ``m``, ``l``, ``lse``,
``delta`` — is a lane-dense (1, block_q) row that broadcasts over sublanes
for free; forward and dQ accumulate (D, block_q) and transpose once a grid
step.

Precision follows the input: every product takes its two operands in the
dtype q, k and v came in (bfloat16 under the AMP policy) and accumulates in
f32; the probabilities are cast to that dtype only as operands of the next
product.  Running max, normaliser, accumulators, ``lse``, ``delta`` and
``exp`` are f32.  ``scale`` is folded once into the resident tile and into
the (D, block) gradient, never into a score tile.  Under ``causal`` only
the blocks the diagonal crosses are masked.

Tile sizes come from ``flash_blocks(t, d, itemsize)``, one pure function
of the shape that the guard ``flash_available`` asks too, so the guard and
the kernels cannot disagree; explicit ``block_q`` / ``block_k`` override it
in all three kernels.

The value heads may have a width of their own (Dv beside the query/key
heads' D: latent attention's 128 beside 192): v, the result and their
gradients carry Dv through all three kernels, the scores and dQ / dK carry
D, nothing is padded, and the blocks are planned at the wider of the two.

Used by ``dot_product_attention`` (ops/attention.py) on TPU for long
sequences, shape-guarded: XLA's fused attention remains the fallback.
``grouped_matmul`` / ``_t``: the routed experts' products over rows sorted by
expert (ops/moe.py); tiles ``grouped_blocks``, guard ``grouped_available``.

``ssd_scan_fwd`` / ``ssd_scan_bwd``: the chunked state-space scan of
``ssm_scan`` (ops/ssm.py) as three kernels, ``mxtpu_ssd_fwd``, ``_states``
and ``_bwd``: the (L, L) blocks and the carried state never leave VMEM;
heads a grid step from ``ssd_blocks``, guard ``ssd_available``.

``kda_scan_fwd`` / ``kda_scan_bwd``: the chunked gated delta rule of
``kda_scan`` (ops/kda.py) as three kernels, ``mxtpu_kda_fwd``, ``_states``
and ``_bwd``: a chunk's (L, L) blocks, its unit-triangular solve and the
carried state never leave VMEM; heads a grid step from ``kda_blocks``, guard
``kda_available``.

``causal_conv_bwd``: the backward of the short causal convolution
``causal_conv1d`` (ops/ssm.py), one pass over the rows, ``mxtpu_conv_bwd``;
blocks from ``conv_blocks``, which returns None where it does not apply.
``gnorm_fwd`` / ``gnorm_bwd``: the gated group norm, one pass over the rows
each way, ``mxtpu_gnorm_fwd`` / ``_bwd``; guard ``gnorm_available``.

All of them are tested in Pallas interpret mode on the CPU harness, compiled
for a described v5e by ``test_pallas_tpu_compile.py`` and run against their
plain forms on the chip by ``tools/tpu_numerics_check.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_available", "flash_blocks",
           "grouped_matmul", "grouped_matmul_t", "grouped_available",
           "grouped_blocks", "ssd_scan_fwd", "ssd_scan_bwd", "ssd_available",
           "ssd_blocks", "kda_scan_fwd", "kda_scan_bwd", "kda_available",
           "kda_blocks", "causal_conv_bwd", "conv_blocks", "gnorm_fwd",
           "gnorm_bwd", "gnorm_available", "gnorm_blocks"]

_NEG_INF = -1e30

# dot_general contractions of two 2-D operands: a @ b.T, a.T @ b, a @ b
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))

# VMEM a kernel may plan for, by ``_vmem_bytes``' estimate at the f32 upper
# bound.  What it admits was compiled, forward and both backward kernels, for
# a v5e (libtpu 0.0.34): the corners T*D = 2**20 at D = 64, 128 and 256 pass
# in f32 and bf16 (39 MB by the estimate at most); T=32768, D=32 in f32,
# which the compiler refused, reads 71 MB and is not admitted.
_VMEM_BUDGET = 48 * 1024 * 1024

# (block_q, block_k), most wanted first: the order measured on the v5e at
# (BH, T, D) = (128, 2048, 64), bf16, causal, the same for all three kernels
# (PERF.md 6, PR 27): block_q, the score tile's lane dimension, counts most.
_TILES = [(bq, bk) for bq in (512, 256, 128) for bk in (512, 256, 128)]


def _vmem_bytes(t, d, itemsize, block_q, block_k):
    """What one grid step of any of the three kernels keeps in VMEM, at
    most: the pipeline's two buffers of every block, the last dimension
    padded to the 128 lanes of a tile (a narrow head costs as much as
    D=128), the lse / delta rows (each padded to 8 sublanes), and the f32
    score tiles the body holds at once (s, p, dp, ds)."""
    lanes = max(d, 128)
    whole = 2 * 2 * t * lanes * itemsize          # the two whole-T operands
    tile = max(block_q, block_k)
    tiles = 4 * 2 * tile * lanes * itemsize       # resident and result tiles
    rows = 2 * 2 * 8 * t * 4                      # lse, delta
    return whole + tiles + rows + 4 * block_q * block_k * 4


def _fits(t, d, itemsize, block_q, block_k):
    return t % block_q == 0 and t % block_k == 0 and \
        _vmem_bytes(t, d, itemsize, block_q, block_k) <= _VMEM_BUDGET


def flash_blocks(t, d, itemsize):
    """``(block_q, block_k)`` of all three kernels for sequence length
    ``t``, head size ``d`` and operands of ``itemsize`` bytes: the first of
    ``_TILES`` that divides ``t`` and fits the VMEM budget; None where none
    does.  In forward and dQ the Q tile (block_q) is resident and K/V stream
    in block_k steps; in dK/dV the K/V tile is resident and Q/dO stream."""
    return next((tile for tile in _TILES if _fits(t, d, itemsize, *tile)),
                None)


def flash_available(q_shape, k_shape=None, v_shape=None, block_q=None,
                    block_k=None):
    """Shape guard: self-attention only (q and k shapes equal, v's equal
    but for its last axis: value heads may be narrower or wider than the
    query/key heads, as a latent-attention layer's 128 beside 192, and the
    kernels then take both widths; grouped-query key/value heads are
    repeated in ``dot_product_attention`` before this is asked, other
    unequal shapes go to XLA), both widths sublane-friendly, T divisible
    into blocks, and each kernel's whole-T residents and score tiles within
    the VMEM budget at the f32 upper bound and the wider of the two widths
    (``flash_blocks`` at 4 bytes; with explicit blocks, those blocks)."""
    if len(q_shape) != 4:
        return False
    if k_shape is not None and tuple(k_shape) != tuple(q_shape):
        return False  # cross-attention -> XLA path
    t, d = q_shape[2], q_shape[3]
    if v_shape is not None:
        if tuple(v_shape[:3]) != tuple(q_shape[:3]) or v_shape[3] % 8:
            return False
        d = max(d, v_shape[3])
    if q_shape[3] % 8 or d > 256:
        return False
    if block_q is None and block_k is None:
        return flash_blocks(t, d, 4) is not None
    return _fits(t, d, 4, block_q or 128, block_k or 128)


def _blocks(t, d, dtype, block_q, block_k):
    """(block_q, block_k) of one call: the chooser's, or the explicit
    pair."""
    if block_q is not None or block_k is not None:
        return block_q or 128, block_k or 128
    blocks = flash_blocks(t, d, jnp.dtype(dtype).itemsize)
    if blocks is None:
        raise ValueError("flash_attention: no tiling for T=%d, D=%d "
                         "(see flash_available)" % (t, d))
    return blocks


def _scaled(x, scale):
    """``x * scale`` in x's dtype, through f32 (the one place scale enters
    a product's operand)."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _scores(k, q, k_pos, q_pos, masked):
    """The transposed score tile k @ q.T, (block_k, block_q) f32; with
    ``masked`` the entries above the diagonal (key after query) are
    -inf-like.  ``k_pos`` is a (block_k, 1) column, ``q_pos`` a (1, block_q)
    row of positions."""
    s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
    if masked:
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
    return s


def _positions(start, block, axis):
    """Positions ``start..start+block`` as a column (axis 0) or a row."""
    shape = (block, 1) if axis == 0 else (1, block)
    return start + jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _stream_kv(fold, carry, j, causal, block_q, block_k, seq_len):
    """Forward's and dQ's loop over the K/V blocks of Q tile ``j``:
    ``fold(masked)(kb, carry)``.  Under ``causal`` the blocks wholly at or
    below every row's diagonal run unmasked, the ones the diagonal crosses
    (ceil, so partial blocks count) masked, the rest not at all."""
    if not causal:
        return jax.lax.fori_loop(0, seq_len // block_k, fold(False), carry)
    whole = (j * block_q + 1) // block_k
    crossed = ((j + 1) * block_q + block_k - 1) // block_k
    carry = jax.lax.fori_loop(0, whole, fold(False), carry)
    return jax.lax.fori_loop(whole, crossed, fold(True), carry)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k, seq_len):
    # refs carry one (bh) slice: q (1, block_q, D), k/v (1, T, D)
    j = pl.program_id(1)
    q = _scaled(q_ref[0], scale)                      # (bq, D), input dtype
    bq, d = q.shape[0], v_ref.shape[2]                # d: the value's width
    q_pos = _positions(j * block_q, bq, 1)

    def fold(masked):
        def body(kb, carry):
            acc, m, l = carry                         # (Dv, bq), (1, bq) x 2
            start = pl.multiple_of(kb * block_k, block_k)
            k = k_ref[0, pl.ds(start, block_k), :]
            v = v_ref[0, pl.ds(start, block_k), :]
            s = _scores(k, q, _positions(start, block_k, 0), q_pos, masked)
            new_m = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - new_m)
            corr = jnp.exp(m - new_m)
            l = l * corr + jnp.sum(p, axis=0, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                v, p.astype(v.dtype), _TN,
                preferred_element_type=jnp.float32)
            return acc, new_m, l
        return body

    carry = (jnp.zeros((d, bq), jnp.float32),
             jnp.full((1, bq), _NEG_INF, jnp.float32),
             jnp.zeros((1, bq), jnp.float32))
    acc, m, l = _stream_kv(fold, carry, j, causal, block_q, block_k, seq_len)
    l = jnp.maximum(l, 1e-30)
    # one transpose a grid step, of whole 128-row tiles: rows 0..D-1 are the
    # output, the rest the log-sum-exp residual for the blocked backward
    lse = jnp.broadcast_to(m + jnp.log(l), (128 - d % 128, bq))
    both = jnp.concatenate([acc / l, lse], axis=0).T
    o_ref[0] = both[:, :d].astype(o_ref.dtype)
    lse_ref[0] = both[:, d:d + 1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=False):
    """Blocked attention over q, k (B, H, T, D) and v (B, H, T, Dv), Dv
    equal to D or not; same semantics as ``attention_reference``, the
    result (B, H, T, Dv).  ``block_q`` / ``block_k`` None: each kernel's
    blocks from ``flash_blocks`` at the wider of D and Dv."""
    return _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k,
                           interpret)[0]


_PARALLEL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"))


def _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret):
    b, h, t, d = q.shape
    dv = v.shape[3]
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q, block_k = _blocks(t, max(d, dv), q.dtype, block_q, block_k)
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, dv)
    kernel = functools.partial(_fwd_kernel, scale=sc, causal=causal,
                               block_q=block_q, block_k=block_k, seq_len=t)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, dv), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32),
        ],
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="mxtpu_flash_fwd",
    )(qf, kf, vf)
    return out.reshape(b, h, t, dv), lse.reshape(b, h, t, 1)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k,
                               interpret)
    return out, (q, k, v, out, lse)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               scale, causal, block_q, block_k, seq_len):
    """dQ: one Q-tile resident, K/V blocks stream (mirrors the forward)."""
    j = pl.program_id(1)
    q = _scaled(q_ref[0], scale)
    do = do_ref[0]
    lse = lse_ref[0, 0]                    # (1, bq) f32
    delta = delta_ref[0, 0]                # (1, bq) f32
    bq, d = q.shape
    q_pos = _positions(j * block_q, bq, 1)

    def fold(masked):
        def body(kb, dq):
            start = pl.multiple_of(kb * block_k, block_k)
            k = k_ref[0, pl.ds(start, block_k), :]
            v = v_ref[0, pl.ds(start, block_k), :]
            s = _scores(k, q, _positions(start, block_k, 0), q_pos, masked)
            p = jnp.exp(s - lse)           # masked entries underflow to 0
            dp = jax.lax.dot_general(v, do, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(k.dtype)
            return dq + jax.lax.dot_general(
                k, ds, _TN, preferred_element_type=jnp.float32)
        return body

    dq = _stream_kv(fold, jnp.zeros((d, bq), jnp.float32), j, causal,
                    block_q, block_k, seq_len) * scale
    if d % 128:                            # transpose whole 128-row tiles
        dq = jnp.concatenate(
            [dq, jnp.zeros((128 - d % 128, bq), jnp.float32)], axis=0)
    dq_ref[0] = dq.T[:, :d].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dk_ref,
                dv_ref, *, scale, causal, block_q, block_k, seq_len):
    """dK/dV: one K/V-tile resident, Q/dO blocks stream; causal skips the
    Q-blocks strictly above the diagonal."""
    j = pl.program_id(1)
    v = v_ref[0]                           # (bk, d)
    k = _scaled(k_ref[0], scale)
    bk, d = k.shape
    k_pos = _positions(j * block_k, bk, 0)

    def fold(masked):
        def body(qb, carry):
            dk, dv = carry
            start = pl.multiple_of(qb * block_q, block_q)
            q = q_ref[0, pl.ds(start, block_q), :]
            do = do_ref[0, pl.ds(start, block_q), :]
            lse = lse_ref[0, qb]                     # (1, bq) f32
            delta = delta_ref[0, qb]
            s = _scores(k, q, k_pos, _positions(start, block_q, 1), masked)
            p = jnp.exp(s - lse)                     # (bk, bq)
            dv = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(q.dtype)
            dk = dk + jax.lax.dot_general(
                ds, q, _NN, preferred_element_type=jnp.float32)
            return dk, dv
        return body

    zeros = jnp.zeros((bk, d), jnp.float32)
    carry = (zeros, zeros if v.shape[1] == d
             else jnp.zeros(v.shape, jnp.float32))
    num_qb = seq_len // block_q
    if causal:
        # Q blocks from the first the diagonal reaches, masked; unmasked
        # from the first whose every row sees the tile's last column
        first = (j * block_k) // block_q
        whole = ((j + 1) * block_k + block_q - 2) // block_q
        carry = jax.lax.fori_loop(first, whole, fold(True), carry)
        carry = jax.lax.fori_loop(whole, num_qb, fold(False), carry)
    else:
        carry = jax.lax.fori_loop(0, num_qb, fold(False), carry)
    dk, dv = carry
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_dq(qf, kf, vf, gf, lsef, deltaf, causal, sc, block_q, block_k,
              interpret):
    """dQ of (BH, T, D) operands (v and dO (BH, T, Dv)); ``lsef`` /
    ``deltaf`` f32 rows (BH, T / block_q, 1, block_q), one a grid step."""
    bh, t, d = qf.shape
    dv = vf.shape[2]
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=sc, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=t),
        grid=(bh, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, dv), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_q, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), qf.dtype),
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="mxtpu_flash_dq",
    )(qf, kf, vf, gf, lsef, deltaf)


def _flash_dkv(qf, kf, vf, gf, lsef, deltaf, causal, sc, block_q, block_k,
               interpret):
    """dK, dV of (BH, T, D) operands; ``lsef`` / ``deltaf`` as for
    ``_flash_dq``, all of a head's rows resident."""
    bh, t, d = qf.shape
    dv = vf.shape[2]
    rows = (t // block_q, 1, block_q)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=sc, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=t),
        grid=(bh, t // block_k),
        in_specs=[
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, dv), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1,) + rows, lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((1,) + rows, lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), kf.dtype),
            jax.ShapeDtypeStruct((bh, t, dv), vf.dtype),
        ],
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="mxtpu_flash_dkv",
    )(qf, gf, lsef, deltaf, kf, vf)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    """Blocked flash backward as TWO Pallas kernels (dq; dk+dv), recomputing
    scores against the saved log-sum-exp — the (T, T) matrix never
    materialises, all matmuls on the MXU, f32 accumulators."""
    q, k, v, out, lse = res
    b, h, t, d = q.shape
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q, block_k = _blocks(t, max(d, v.shape[3]), q.dtype, block_q,
                               block_k)
    # delta = rowsum(dO * O): one fused elementwise+reduce pass in XLA
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(axis=-1)
    # q, k, v, dO flat over heads; lse, delta as one (1, block_q) row a Q block
    args = [x.reshape(b * h, t, x.shape[3]) for x in (q, k, v, g)] + \
        [x.reshape(b * h, t // block_q, 1, block_q) for x in (lse, delta)]
    args += [causal, sc, block_q, block_k, interpret]
    dq = _flash_dq(*args)
    dk, dv = _flash_dkv(*args)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


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
    dq, dk, dv = jax.lax.fori_loop(
        0, nkb, grad_fold, (zeros, zeros, jnp.zeros(v.shape, jnp.float32)))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------- grouped products
# Rows sorted by group (a routed layer's assignments by expert), each
# group's run padded to whole blocks of rows: block ``i`` belongs to group
# ``tile_group[i]``, groups ascending, and the first ``live`` blocks hold
# every run.  ``live`` is a traced scalar and is the grid's extent, so a
# block past it costs no product and no fetch; what the result holds there
# was never written (it is not zero: the caller masks it).  A group's matrix
# is one block of the pipeline whose index does not change along the group's
# run: it is fetched once a run, not once a block.  ``tile_fill[i]`` is the
# number of leading rows of block ``i`` that hold something: a block whose
# upper half holds nothing (a run's last block, half the time) is worked as
# its lower half alone, and the rows past it are not written either.

def _lane_tile(n, fits):
    """The tile of a lane dimension ``n``: all of it where ``fits(tile)``,
    else the multiple of 128 that fits and covers ``n`` in the fewest
    columns (the last tile may hang over the edge; of two that cover alike,
    the larger); None where nothing fits."""
    ok = [t for t in [n] + [t for t in range(1024, 0, -128) if t < n]
          if fits(t)]
    return min(ok, key=lambda t: (-(-n // t) * t, -t)) if ok else None


def _gmm_vmem(block, k, tn, itemsize):
    """One grid step of ``mxtpu_gmm``: the pipeline's two buffers of the
    rows' block, of the group's matrix tile and of the result tile (f32 at
    most), the f32 accumulator and one temporary of the epilogue."""
    return 2 * (block * k + tn * k) * itemsize + 4 * block * tn * 4


def _tgmm_vmem(block, k, tn, itemsize):
    """One grid step of ``mxtpu_tgmm``: two buffers of both row blocks and
    of the result tile, the f32 accumulator and the product added to it."""
    return 2 * (block * (k + tn) + k * tn) * itemsize + 2 * k * tn * 4


def grouped_blocks(block, k, n, itemsize):
    """``(tile of n in grouped_matmul, tile of n in grouped_matmul_t)`` for
    rows in blocks of ``block`` and a group's (k, n) matrix, operands of
    ``itemsize`` bytes; either is None where no tile fits the VMEM budget.
    ``k`` is never cut: the product contracts all of it and the transposed
    product keeps it whole in the result, so a width that is no multiple of
    128 lanes needs no mask."""
    return (_lane_tile(n, lambda t: _gmm_vmem(block, k, t, itemsize)
                       <= _VMEM_BUDGET),
            _lane_tile(n, lambda t: _tgmm_vmem(block, k, t, itemsize)
                       <= _VMEM_BUDGET))


def grouped_available(block, c, f, itemsize):
    """Shape guard of a routed layer's products, rows in blocks of
    ``block`` through matrices of (c, f) and (f, c): the block a whole
    number of 128-row tiles (it is the contraction of the transposed
    product), and a tile for every product within the VMEM budget."""
    return block % 128 == 0 and None not in (
        grouped_blocks(block, c, f, itemsize)
        + grouped_blocks(block, f, c, itemsize))


# the budget the tiles are chosen by, and room for what the estimate leaves
# out (the compiler's default of 16 MiB does not hold a 10 MB matrix twice)
_GROUPED = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_BUDGET + 16 * 1024 * 1024)


def _whole_or_half(fill_ref, block, work):
    """``work(rows)`` on the whole block, or on its lower half where the
    upper half holds nothing."""
    fill = fill_ref[pl.program_id(1)]
    pl.when(fill > block // 2)(lambda: work(block))
    pl.when(fill <= block // 2)(lambda: work(block // 2))


def _gmm_kernel(group_ref, fill_ref, x_ref, w_ref, o_ref, *, dims, act):
    del group_ref                                   # the index maps' alone

    def product(rows):
        acc = jax.lax.dot_general(x_ref[:rows, :], w_ref[...], dims,
                                  preferred_element_type=jnp.float32)
        o_ref[:rows, :] = (acc if act is None else act(acc)).astype(
            o_ref.dtype)
    _whole_or_half(fill_ref, x_ref.shape[0], product)


def grouped_matmul(x, w, tile_group, tile_fill, live, transpose_rhs=False,
                   act=None, out_dtype=None, block_n=None, interpret=False):
    """Row ``i`` of ``x`` (rows, k) times the matrix of the group its block
    belongs to: ``w`` (groups, k, n), or (groups, n, k) with
    ``transpose_rhs``.  Operands as they come, f32 accumulation, ``act`` on
    the f32 accumulator before the cast to ``out_dtype`` (x's by default).
    Returns (rows, n); the blocks from ``live`` on, and the upper half of a
    block that ``tile_fill`` says holds nothing there, are not written."""
    rows, k = x.shape
    nb = tile_group.shape[0]
    block = rows // nb
    n = w.shape[1] if transpose_rhs else w.shape[2]
    tn = block_n or grouped_blocks(block, k, n, x.dtype.itemsize)[0]
    if tn is None:
        raise ValueError("grouped_matmul: no tiling for blocks of %d rows, "
                         "k=%d, n=%d (see grouped_available)" % (block, k, n))
    if transpose_rhs:
        w_spec = pl.BlockSpec((None, tn, k), lambda j, i, g, f: (g[i], j, 0))
    else:
        w_spec = pl.BlockSpec((None, k, tn), lambda j, i, g, f: (g[i], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, dims=_NT if transpose_rhs else _NN,
                          act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(n, tn), live),
            in_specs=[pl.BlockSpec((block, k), lambda j, i, g, f: (i, 0)),
                      w_spec],
            out_specs=pl.BlockSpec((block, tn),
                                   lambda j, i, g, f: (i, j))),
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype or x.dtype),
        compiler_params=_GROUPED,
        interpret=interpret,
        name="mxtpu_gmm",
    )(tile_group, tile_fill, x, w)


def _tgmm_kernel(group_ref, fill_ref, lhs_ref, rhs_ref, o_ref, acc_ref, *,
                 blocks):
    i, last = pl.program_id(1), pl.num_programs(1) - 1
    group = group_ref[i]
    opens = (i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != group)
    closes = (i == last) | (group_ref[jnp.minimum(i + 1, blocks - 1)]
                            != group)

    @pl.when(opens)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def add(rows):
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[:rows, :], rhs_ref[:rows, :], _TN,
            preferred_element_type=jnp.float32)
    _whole_or_half(fill_ref, lhs_ref.shape[0], add)

    @pl.when(closes)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul_t(lhs, rhs, tile_group, tile_fill, live, groups,
                     out_dtype=None, block_n=None, interpret=False):
    """Every group's ``lhs_g.T @ rhs_g`` over the group's own rows: lhs
    (rows, k), rhs (rows, n) -> (groups, k, n).  Summed in an f32 VMEM tile
    over the group's run of blocks and written once, in ``out_dtype``
    (lhs's by default).  A group that owns none of the first ``live``
    blocks is not written: the caller gives every group a block.  The
    upper half of a block that ``tile_fill`` says holds nothing there is
    left out of the sum (the caller has zeros there)."""
    rows, k = lhs.shape
    n = rhs.shape[1]
    nb = tile_group.shape[0]
    block = rows // nb
    tn = block_n or grouped_blocks(block, k, n, lhs.dtype.itemsize)[1]
    if tn is None:
        raise ValueError("grouped_matmul_t: no tiling for blocks of %d rows,"
                         " k=%d, n=%d (see grouped_available)"
                         % (block, k, n))
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, blocks=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(n, tn), live),
            in_specs=[pl.BlockSpec((block, k), lambda j, i, g, f: (i, 0)),
                      pl.BlockSpec((block, tn), lambda j, i, g, f: (i, j))],
            out_specs=pl.BlockSpec((None, k, tn),
                                   lambda j, i, g, f: (g[i], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n),
                                       out_dtype or lhs.dtype),
        compiler_params=_GROUPED,
        interpret=interpret,
        name="mxtpu_tgmm",
    )(tile_group, tile_fill, lhs, rhs)


# ------------------------------------------------------- state-space scan
# The chunked scan of ``ops/ssm.py`` (Mamba-2), forward and backward, with
# nothing of size (L, L) and no carried (P, N) state in HBM.  A grid step
# takes one chunk of L rows of ``heads`` heads of one group, straight out of
# the unsplit ``xBC`` array by column blocks (x: heads * P columns; the
# group's B and C: N columns each), and the chunk axis is the grid's last,
# sequential one: the state of those heads lives in a float32 VMEM scratch
# of (heads * P, N) from chunk to chunk (its gradient, in the backward, from
# the last chunk to the first).
#
# A head narrower than the 128 lanes shares a tile with its neighbours
# (P = 64: two heads side by side).  What differs from head to head inside
# a tile, the (L, L) masked decay, multiplies the whole tile and the head's
# own lanes are selected from the result; what the heads share (``C S^T``,
# ``xw^T B``) is one product a tile.  No lane is shifted.
#
# The per-step scalars are float32 arrays of (T, H), made outside
# (``ops/ssm.py``): ``dt`` after its softplus and ``acs``, the running sum
# of ``A dt`` inside each chunk.  The kernels take both twice, as columns
# (B, H / heads, T, 2 heads) and as rows (B, H / heads, 2 heads, T), ``dt``
# first: a decay ``exp(acs_l - acs_s)`` needs a column and a row, and a
# (L, 1) column cannot be turned into a (1, L) row without a transpose.  The
# backward forms its (L, L) blocks transposed, at (s, l), so that ``m^T dy``
# needs no transpose either; it hands the gradients of dt and acs back as
# columns, and as rows the part of acs's that it sums along lanes, and
# autodiff carries them through the running sum, the softplus and
# ``-exp(A_log)``.

_SSD_HEADS = 8          # at most: a grid step's loop over its heads is unrolled


def _tile_heads(p):
    """Heads of ``p`` lanes side by side in one 128-lane tile."""
    return max(1, 128 // p)


def _ssd_vmem(heads, p, n, chunk, itemsize):
    """What one grid step of the backward, the largest of the three, keeps
    in VMEM: two buffers of x, dy, dx, B, C, of the float32 parts of dB and
    dC and of the entering state, the scratch, the four arrays of scalars
    padded to 128 lanes, and the float32 temporaries of its body ((L, L):
    a dozen; (L, tile): a dozen)."""
    width = heads * p
    blocks = 2 * chunk * (3 * width + 2 * n) * itemsize \
        + 2 * 2 * chunk * n * 4 + 3 * width * n * 4
    scalars = 4 * 2 * max(chunk, 2 * heads) * 128 * 4
    return blocks + scalars + 12 * chunk * (chunk + max(p, 128)) * 4


def ssd_blocks(t, h, p, g, n, chunk, itemsize):
    """Heads a grid step of the three scan kernels works, for T steps of H
    heads of P in G groups with a state of N, chunks of ``chunk``, operands
    of ``itemsize`` bytes: the most (8 at most) that divide a group's
    heads, fill whole 128-lane tiles and fit the VMEM budget.  None where
    the kernels do not apply: T no multiple of the chunk, a chunk that is
    no multiple of 128, a P that neither divides nor is a multiple of the
    128 lanes, an N that is no multiple of them or does not divide H P (B
    and C are column blocks of N of the unsplit array)."""
    if min(t, h, p, g, n, chunk) <= 0 or h % g or t % chunk or chunk % 128 \
            or n % 128 or (h * p) % n or (128 % p and p % 128):
        return None
    return next((k for k in range(min(h // g, _SSD_HEADS), 0, -1)
                 if (h // g) % k == 0 and k % _tile_heads(p) == 0
                 and _ssd_vmem(k, p, n, chunk, itemsize) <= _VMEM_BUDGET),
                None)


def ssd_available(t, h, p, g, n, chunk, itemsize):
    """Shape guard of the scan kernels: ``ssd_blocks`` finds a tiling."""
    return ssd_blocks(t, h, p, g, n, chunk, itemsize) is not None


_SSD = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_BUDGET + 16 * 1024 * 1024)


def _ssd_tiles(heads, p):
    """[(a 128-lane tile's lanes, its heads)] of a step's ``heads`` heads."""
    count = _tile_heads(p)
    return [(slice(i * p, (i + count) * p), range(i, i + count))
            for i in range(0, heads, count)]


def _ssd_own(chunk, p):
    """For each head of a tile, where it lies, as masks made once a grid
    step: its lanes of a (chunk, tile) array and its rows of a (tile, 1)
    column."""
    count = _tile_heads(p)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (chunk, count * p), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (count * p, 1), 0)
    return [[(at >= k * p) & (at < (k + 1) * p) for k in range(count)]
            for at in (lanes, rows)]


def _spread(values, own):
    """One array from a tile's heads' values, each broadcast over the
    head's own positions (``own``: the lanes' or the rows' masks)."""
    out = jnp.broadcast_to(values[-1], own[0].shape)
    for mask, value in zip(own[:-1], values):
        out = jnp.where(mask, value, out)
    return out


def _only(mask, value, count):
    """``value`` on one head's positions of its tile, zero on the other
    heads'."""
    return value if count == 1 else jnp.where(mask, value, 0.0)


def _ssd_ends(col_ref, r, heads):
    """Head ``r``'s chunk as its end sees it, float32: ``left`` (L, 1), the
    decay from each row to the chunk's end, ``dt`` (L, 1), and ``a_end``
    (1, 1), the log of the whole chunk's decay."""
    chunk = col_ref.shape[0]
    a_c = col_ref[:, heads + r:heads + r + 1]
    a_end = col_ref[chunk - 1:chunk, heads + r:heads + r + 1]
    return jnp.exp(a_end - a_c), col_ref[:, r:r + 1], a_end


def _ssd_causal(chunk, transposed=False):
    """s <= l over a chunk's (l, s) block; ``transposed``, over (s, l).
    Made once a grid step."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return rows <= cols if transposed else cols <= rows


def _ssd_decay(col_ref, row_ref, r, heads, causal, transposed=False):
    """Head ``r``'s masked decay inside the chunk, (L, L) float32:
    ``exp(acs_l - acs_s)`` for s <= l, else 0, at (l, s); ``transposed``,
    at (s, l), with ``causal`` the same way.  Either way a column of acs
    against a row of it."""
    a_c = col_ref[:, heads + r:heads + r + 1]
    a_r = row_ref[heads + r:heads + r + 1, :]
    return jnp.exp(jnp.where(causal, a_r - a_c if transposed else a_c - a_r,
                             _NEG_INF))


def _ssd_keep(col_ref, r, heads):
    """What each row of head ``r``'s chunk sees of the entering state,
    ``exp(acs)``, (L, 1)."""
    return jnp.exp(col_ref[:, heads + r:heads + r + 1])


def _ssd_total(ends, own_rows):
    """The whole chunk's decay of a tile's heads down the tile's rows,
    (tile, 1).  The exp comes after the spreading: a (1, 1) value cannot be
    broadcast along sublanes and lanes at once."""
    return jnp.exp(_spread([a_end for _, _, a_end in ends], own_rows))


def _ssd_carry(s_ref, lanes, xf, b, ends, own):
    """A tile's state at the chunk's end from the one at its start:
    ``exp(acs_L) S + (x * to_end)^T B``, the product's operands in b's
    dtype."""
    to_end = _spread([left * dt for left, dt, _ in ends], own[0])
    s_ref[lanes, :] = _ssd_total(ends, own[1]) * s_ref[lanes, :] \
        + jax.lax.dot_general((xf * to_end).astype(b.dtype), b, _TN,
                              preferred_element_type=jnp.float32)


def _ssd_fwd_kernel(x_ref, b_ref, c_ref, col_ref, row_ref, d_ref, y_ref,
                    s_ref, *, heads, p):
    f32 = jnp.float32
    cd = x_ref.dtype
    chunk = x_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    b, c = b_ref[...], c_ref[...]
    cb = jax.lax.dot_general(c, b, _NT, preferred_element_type=f32)
    causal, own = _ssd_causal(chunk), _ssd_own(chunk, p)
    for lanes, tile in _ssd_tiles(heads, p):
        x = x_ref[:, lanes]
        xf = x.astype(f32)
        inside = None
        for k, r in enumerate(tile):
            m = cb * _ssd_decay(col_ref, row_ref, r, heads, causal) \
                * row_ref[r:r + 1, :]
            part = jax.lax.dot_general(m.astype(cd), x, _NN,
                                       preferred_element_type=f32)
            inside = part if k == 0 else jnp.where(own[0][k], part, inside)
        before = jax.lax.dot_general(c, s_ref[lanes, :].astype(cd), _NT,
                                     preferred_element_type=f32)
        keep = _spread([_ssd_keep(col_ref, r, heads) for r in tile], own[0])
        y = inside + keep * before + d_ref[:, lanes] * xf
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        _ssd_carry(s_ref, lanes, xf, b,
                   [_ssd_ends(col_ref, r, heads) for r in tile], own)


def _ssd_states_kernel(x_ref, b_ref, col_ref, before_ref, s_ref, *, heads,
                       p):
    """The state entering each chunk, and nothing else of the forward."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    before_ref[...] = s_ref[...]
    own = _ssd_own(x_ref.shape[0], p)
    for lanes, tile in _ssd_tiles(heads, p):
        _ssd_carry(s_ref, lanes, x_ref[:, lanes].astype(jnp.float32),
                   b_ref[...], [_ssd_ends(col_ref, r, heads) for r in tile],
                   own)


def _ssd_bwd_kernel(x_ref, b_ref, c_ref, dy_ref, col_ref, row_ref, d_ref,
                    before_ref, dx_ref, db_ref, dc_ref, gcol_ref, grow_ref,
                    dd_ref, ds_ref, *, heads, p):
    """One chunk's gradients, the chunks taken last to first.  ``ds_ref``
    carries the gradient of the state that leaves the chunk; ``dd_ref``,
    one block for all chunks, sums dy x over the rows."""
    f32 = jnp.float32
    cd = x_ref.dtype
    chunk = x_ref.shape[0]
    count = _tile_heads(p)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    # the (L, L) blocks transposed, at (s, l): what the backward needs of
    # them, m^T dy and sums along either axis, then takes no transpose
    b, c = b_ref[...], c_ref[...]
    cb = jax.lax.dot_general(b, c, _NT, preferred_element_type=f32)
    causal, own = _ssd_causal(chunk, True), _ssd_own(chunk, p)
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    dcb = jnp.zeros((chunk, chunk), f32)
    db = jnp.zeros(db_ref.shape, f32)
    dc = jnp.zeros(dc_ref.shape, f32)
    for lanes, tile in _ssd_tiles(heads, p):
        x, dy = x_ref[:, lanes], dy_ref[:, lanes]
        xf, dyf = x.astype(f32), dy.astype(f32)
        s, ds = before_ref[lanes, :], ds_ref[lanes, :]
        s_cd, ds_cd = s.astype(cd), ds.astype(cd)
        before = jax.lax.dot_general(c, s_cd, _NT,
                                     preferred_element_type=f32)
        dxw = jax.lax.dot_general(b, ds_cd, _NT, preferred_element_type=f32)
        held = jnp.sum(ds * s, axis=1, keepdims=True)        # (tile, 1)
        dy_before, dxw_x = dyf * before, dxw * xf
        keeps, ends, inside = [], [], None
        for k, r in enumerate(tile):
            mine, my_rows = own[0][k], own[1][k]
            keep = _ssd_keep(col_ref, r, heads)
            left, dt_c, a_end = _ssd_ends(col_ref, r, heads)
            keeps.append(keep)
            ends.append((left, dt_c, a_end))
            # y = m x, m = cb * decay * dt_s: dm, then what it gives cb,
            # dt_s (along each row s) and the two ends of
            # exp(acs_l - acs_s) (dm m: + down its column l, - along its
            # row s)
            decay = _ssd_decay(col_ref, row_ref, r, heads, causal, True)
            reach = decay * dt_c
            m = cb * reach
            dm = jax.lax.dot_general(x, _only(mine, dyf, count).astype(cd),
                                     _NT, preferred_element_type=f32)
            dcb = dcb + dm * reach
            along = jnp.sum(dm * cb * decay, axis=1, keepdims=True)
            grow_ref[r:r + 1, :] = jnp.sum(dm * m, axis=0, keepdims=True)
            part = jax.lax.dot_general(m.astype(cd), dy, _NN,
                                       preferred_element_type=f32)
            inside = part if k == 0 else jnp.where(mine, part, inside)
            # the entering state's share of y, and the chunk's of the
            # state that leaves: columns
            seen = jnp.sum(_only(mine, dy_before, count), axis=1,
                           keepdims=True)
            dte = jnp.sum(_only(mine, dxw_x, count), axis=1, keepdims=True)
            at_end = jnp.sum(dte * left * dt_c, axis=0, keepdims=True) \
                + jnp.exp(a_end) * jnp.sum(_only(my_rows, held, count),
                                           axis=0, keepdims=True)
            gcol_ref[:, r:r + 1] = dte * left + along
            gcol_ref[:, heads + r:heads + r + 1] = keep * seen \
                - dt_c * (dte * left + along) + jnp.where(last, at_end, 0.0)
        to_end = _spread([left * dt_c for left, dt_c, _ in ends], own[0])
        dz = (_spread(keeps, own[0]) * dyf).astype(cd)
        dc = dc + jax.lax.dot_general(dz, s_cd, _NN,
                                      preferred_element_type=f32)
        db = db + jax.lax.dot_general((xf * to_end).astype(cd), ds_cd, _NN,
                                      preferred_element_type=f32)
        dx_ref[:, lanes] = (inside + dxw * to_end
                            + d_ref[:, lanes] * dyf).astype(dx_ref.dtype)
        dd_ref[:, lanes] += jnp.sum(dyf * xf, axis=0, keepdims=True)
        ds_ref[lanes, :] = _ssd_total(ends, own[1]) * ds \
            + jax.lax.dot_general(dz, c, _TN, preferred_element_type=f32)
    dcb = dcb.astype(cd)
    db_ref[...] = db + jax.lax.dot_general(dcb, c, _NN,
                                           preferred_element_type=f32)
    dc_ref[...] = dc + jax.lax.dot_general(dcb, b, _TN,
                                           preferred_element_type=f32)


def _ssd_layout(xbc, dt, acs, d, h, p, g, chunk):
    """What the three calls share: the heads a step works, the grid, the
    scalars as columns and rows, ``d`` spread over its heads' lanes, and
    the column blocks of ``xbc``: x's, B's, C's, for chunk ``c(i)``."""
    bsz, t, width = xbc.shape
    n = (width - h * p) // (2 * g)
    heads = ssd_blocks(t, h, p, g, n, chunk, xbc.dtype.itemsize)
    if heads is None:
        raise ValueError("ssd_scan: no tiling for T=%d, H=%d, P=%d, G=%d, "
                         "N=%d, chunk=%d (see ssd_available)"
                         % (t, h, p, g, n, chunk))
    f32 = jnp.float32
    blocks, per_group = h // heads, h // g // heads
    both = jnp.stack([dt.astype(f32), acs.astype(f32)], axis=2).reshape(
        bsz, t, 2, blocks, heads)
    cols = both.transpose(0, 3, 1, 2, 4).reshape(bsz, blocks, t, 2 * heads)
    rows = both.transpose(0, 3, 2, 4, 1).reshape(bsz, blocks, 2 * heads, t)
    lanes = jnp.repeat(d.astype(f32), p).reshape(1, h * p)
    first_b, first_c = h * p // n, h * p // n + g
    return heads, n, (bsz, blocks, t // chunk), cols, rows, lanes, (
        lambda c: pl.BlockSpec((None, chunk, heads * p),
                               lambda i, j, k: (i, c(k), j)),
        lambda c: pl.BlockSpec((None, chunk, n), lambda i, j, k: (
            i, c(k), first_b + j // per_group)),
        lambda c: pl.BlockSpec((None, chunk, n), lambda i, j, k: (
            i, c(k), first_c + j // per_group)),
        lambda c: pl.BlockSpec((None, None, chunk, 2 * heads),
                               lambda i, j, k: (i, j, c(k), 0)),
        lambda c: pl.BlockSpec((None, None, 2 * heads, chunk),
                               lambda i, j, k: (i, j, 0, c(k))),
        pl.BlockSpec((1, heads * p), lambda i, j, k: (0, j)))


def ssd_scan_fwd(xbc, dt, acs, d, h, p, g, chunk, interpret=False):
    """y (B, T, H P) in xbc's dtype, the skip ``d x`` included.  xbc
    (B, T, H P + 2 G N): x, B and C side by side; dt (B, T, H) after its
    softplus and acs (B, T, H), the running sum of ``A dt`` inside each
    chunk, float32; d (H,)."""
    heads, n, grid, cols, rows, lanes, specs = _ssd_layout(
        xbc, dt, acs, d, h, p, g, chunk)
    x, b, c, col, row, lane = specs
    forward = lambda k: k                                   # noqa: E731
    return pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, heads=heads, p=p),
        grid=grid,
        in_specs=[x(forward), b(forward), c(forward), col(forward),
                  row(forward), lane],
        out_specs=x(forward),
        out_shape=jax.ShapeDtypeStruct(xbc.shape[:2] + (h * p,), xbc.dtype),
        scratch_shapes=[pltpu.VMEM((heads * p, n), jnp.float32)],
        compiler_params=_SSD,
        interpret=interpret,
        name="mxtpu_ssd_fwd",
    )(xbc, xbc, xbc, cols, rows, lanes)


def ssd_scan_bwd(xbc, dt, acs, d, dy, h, p, g, chunk, interpret=False):
    """Gradients of ``ssd_scan_fwd`` for y's cotangent ``dy``: of xbc (in
    its dtype), dt, acs (B, T, H) and d (H,), float32.  Two calls: the
    state entering each chunk, (B, T / chunk, H P, N) float32, formed again
    from the inputs; then the chunks last to first."""
    f32 = jnp.float32
    heads, n, grid, cols, rows, lanes, specs = _ssd_layout(
        xbc, dt, acs, d, h, p, g, chunk)
    x, b, c, col, row, lane = specs
    bsz, t, _ = xbc.shape
    blocks, nc = grid[1], grid[2]
    forward = lambda k: k                                   # noqa: E731
    back = lambda k: nc - 1 - k                             # noqa: E731
    state = lambda c: pl.BlockSpec(                         # noqa: E731
        (None, None, heads * p, n), lambda i, j, k: (i, c(k), j, 0))
    before = pl.pallas_call(
        functools.partial(_ssd_states_kernel, heads=heads, p=p),
        grid=grid,
        in_specs=[x(forward), b(forward), col(forward)],
        out_specs=state(forward),
        out_shape=jax.ShapeDtypeStruct((bsz, nc, h * p, n), f32),
        scratch_shapes=[pltpu.VMEM((heads * p, n), f32)],
        compiler_params=_SSD,
        interpret=interpret,
        name="mxtpu_ssd_states",
    )(xbc, xbc, cols)
    part = pl.BlockSpec((None, chunk, n),
                        lambda i, j, k: (i, back(k), j))
    dx, db, dc, gcols, grows, dd = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, heads=heads, p=p),
        grid=grid,
        in_specs=[x(back), b(back), c(back), x(back), col(back), row(back),
                  lane, state(back)],
        out_specs=[x(back), part, part, col(back),
                   pl.BlockSpec((None, None, heads, chunk),
                                lambda i, j, k: (i, j, 0, back(k))),
                   pl.BlockSpec((None, 1, heads * p),
                                lambda i, j, k: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, h * p), xbc.dtype),
                   jax.ShapeDtypeStruct((bsz, t, blocks * n), f32),
                   jax.ShapeDtypeStruct((bsz, t, blocks * n), f32),
                   jax.ShapeDtypeStruct(cols.shape, f32),
                   jax.ShapeDtypeStruct((bsz, blocks, heads, t), f32),
                   jax.ShapeDtypeStruct((bsz, 1, h * p), f32)],
        scratch_shapes=[pltpu.VMEM((heads * p, n), f32)],
        compiler_params=_SSD,
        interpret=interpret,
        name="mxtpu_ssd_bwd",
    )(xbc, xbc, xbc, dy, cols, rows, lanes, before)
    # a group's dB and dC: the sum of its head blocks' parts
    db, dc = (v.reshape(bsz, t, g, blocks // g, n).sum(3).reshape(
        bsz, t, g * n).astype(xbc.dtype) for v in (db, dc))
    ddt, dacs = gcols.reshape(bsz, blocks, t, 2, heads).transpose(
        3, 0, 2, 1, 4).reshape(2, bsz, t, h)
    dacs = dacs + grows.transpose(0, 3, 1, 2).reshape(bsz, t, h)
    return (jnp.concatenate([dx, db, dc], axis=-1), ddt, dacs,
            dd.reshape(bsz, h, p).sum((0, 2)))




# ------------------------------------------------------- gated delta rule
# The chunked rule of ``ops/kda.py`` (Kimi Delta Attention), forward and
# backward, with nothing of size (T / L, L, L) or (L, L, d), no triangular
# solve and no carried state of every token in HBM.  A grid step takes one
# chunk of L rows of ``heads`` heads, straight out of the op's (B, T, H d)
# arrays by column blocks, and the chunk axis is the grid's last, sequential
# one: the state of those heads lives in float32 VMEM scratch from chunk to
# chunk (its gradient, in the backward, from the last chunk to the first).
# The state is held transposed, (d_v, d_k): the whole chunk's decay
# ``exp(G_L)`` is then a row that broadcasts down the sublanes, and so is
# its gradient.
#
# Inside a grid step the heads are the leading axis of every array, and each
# stage is written once for all of them: elementwise work over
# (heads, L, d) arrays, the products as batched ``dot_general``s.  One head
# alone is a chain of dependent steps (running sum, columns, elimination,
# joins, the products with the state) that leaves the units waiting on each
# other; the heads' chains share nothing, and a stage of all of them at once
# lets them overlap (PERF.md 6, PR 34: a third off the forward).  It is also
# what the host pays for: a body traced and lowered once, not once a head
# (PERF.md 6, PR 35).
#
# The stages: the running log-decay ``G`` (a product with the
# lower-triangular ones, exact: ``_kda_running``); the blocks ``M`` and the
# raw ``A`` (before ``beta``) by 16-row sub-blocks as ``ops/kda.py`` states
# them: a sub-block below the diagonal as a product of rows scaled by
# ``exp(G_i - G_r)`` and columns scaled by ``exp(G_r - G_j)``, r the
# sub-block's first row; the diagonal sub-blocks column by column, element
# by element, ``exp(G_i - G_j)`` formed once for both: the 16 columns are a
# ``fori_loop`` whose body is traced once and unrolled when it is lowered (a
# loop left rolled keeps the columns from overlapping: 9.7 ms a layer for
# 7.3, PERF.md 6, PR 35).  No exponent is above 0.  ``(I + A)^-1`` in float32:
# the 16 x 16 diagonal blocks by forward elimination on the VPU, all at once
# and in the same loop that forms their columns, then the sub-blocks joined
# by forward substitution written as float32 products (a pair of blocks
# ``[[P, 0], [-Q A P, Q]]``, then pairs of pairs).
#
# The backward is two calls.  The states pass runs the chunks forward
# without q, ``M`` or o and hands back the state entering each chunk and
# each chunk's ``(I + A)^-1``; the backward pass forms the blocks again from
# the inputs, keeps the diagonal sub-blocks' ``exp`` in VMEM scratch for its
# second pass over them, and applies the inverse transposed: with ``W =
# T^-1 R``, ``dR = T^-T dW`` and ``dA = -tril(dR W^T, -1)``.
#
# The kernels take ``g``, the float32 log-decay of every key channel
# (``kda_gates`` makes it outside), and ``beta`` as columns,
# (B, H / heads, T, heads); the backward hands both gradients back the same
# way, the log-decay's summed from each row to its chunk's end.

_KDA_SUB = 16           # rows of a sub-block: ops/kda.py's
_KDA_HEADS = 8          # at most: what a grid step keeps alive grows with them
_KDA_CHUNKS = (16, 32, 64, 128)   # sub-blocks joined by halves: 1, 2, 4, 8
_HIGHEST = jax.lax.Precision.HIGHEST

# ``_NT``, ``_TN`` and ``_NN`` a head: the heads of a grid step lead
_HNT = (((2,), (2,)), ((0,), (0,)))
_HTN = (((1,), (1,)), ((0,), (0,)))
_HNN = (((2,), (1,)), ((0,), (0,)))


def _kda_vmem(heads, dk, dv, chunk, itemsize):
    """What one grid step of the backward, the largest of the three, keeps
    in VMEM: two buffers of q, k, v, do and their gradients, of the float32
    log-decay and its gradient, of the entering state and of the chunk's
    inverse; each head's scratch (state, ``G``, k, the column sums and the
    16 columns' ``exp`` of the diagonal sub-blocks; the forward's inverse
    in the making is no larger than two padded columns); the columns of
    beta padded to 128 lanes; and the float32 temporaries of the body, all
    heads' alive at once ((L, d): three dozen a head; (L, L): a dozen)."""
    wide = heads * (2 * dk + dv)
    blocks = 2 * chunk * (2 * wide * itemsize + 2 * heads * dk * 4) \
        + 2 * chunk * heads * dv * itemsize \
        + 2 * heads * (dv * dk + chunk * chunk) * 4
    scratch = heads * (dv * dk + (3 + _KDA_SUB) * chunk * dk
                       + 2 * chunk * 128) * 4
    body = heads * chunk * (36 * max(dk, dv) + 12 * max(chunk, 128)) * 4
    return blocks + scratch + 4 * chunk * 128 * 4 + body


def kda_blocks(t, h, dk, dv, chunk, itemsize):
    """Heads a grid step of the three kernels of the gated delta rule works,
    for T steps of H heads with keys of d_k and values of d_v, chunks of
    ``chunk``, operands of ``itemsize`` bytes: the most (8 at most) that
    divide H and fit the VMEM budget.  None where the kernels do not apply:
    a d_k or d_v that is no multiple of the 128 lanes, a chunk that is not
    16, 32, 64 or 128 rows.  T is any: the caller pads it to whole chunks."""
    if min(t, h, dk, dv) <= 0 or dk % 128 or dv % 128 \
            or chunk not in _KDA_CHUNKS:
        return None
    return next((n for n in range(min(h, _KDA_HEADS), 0, -1)
                 if h % n == 0
                 and _kda_vmem(n, dk, dv, chunk, itemsize) <= _VMEM_BUDGET),
                None)


def kda_available(t, h, dk, dv, chunk, itemsize):
    """Shape guard of the gated delta rule's kernels: ``kda_blocks`` finds a
    tiling."""
    return kda_blocks(t, h, dk, dv, chunk, itemsize) is not None


def _exact_dot(a, b, dims):
    """A float32 product kept float32: the running sums, the solve."""
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _kda_dot(a, b, dims, cd):
    """A product of the rule: operands in the compute dtype ``cd``, float32
    accumulation; float32 operands multiply as float32."""
    return jax.lax.dot_general(
        a.astype(cd), b.astype(cd), dims,
        precision=_HIGHEST if cd == jnp.float32 else None,
        preferred_element_type=jnp.float32)


def _kda_masks(chunk):
    """A chunk's (L, L) geometry, made once a grid step and shared by its
    heads: ``tri`` the lower-triangular ones (running sums down a chunk)
    and ``tri_t``, the upper, bfloat16; ``eye``, float32; ``incl`` j <= i
    and ``strict`` j < i; ``own``, (i, j) inside a diagonal sub-block,
    and ``at``, j counted from that sub-block's first column; ``rows`` and
    ``cols``, i and j; ``sub`` (L, 1), a row's place in its
    sub-block, and ``col`` (1, L)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    incl = cols <= rows
    return {"incl": incl, "strict": cols < rows,
            "tri": jnp.where(incl, 1.0, 0.0).astype(jnp.bfloat16),
            "tri_t": jnp.where(cols >= rows, 1.0, 0.0).astype(jnp.bfloat16),
            "eye": jnp.where(cols == rows, 1.0, 0.0).astype(jnp.float32),
            "rows": rows, "cols": cols, "at": cols - (rows & -_KDA_SUB),
            "own": (cols & -_KDA_SUB) == (rows & -_KDA_SUB),
            "sub": row & (_KDA_SUB - 1),
            "col": jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)}


def _kda_heads(ref, heads):
    """A grid step's (L, heads * d) block with its heads leading:
    (heads, L, d)."""
    d = ref.shape[1] // heads
    return jnp.stack([ref[:, r * d:(r + 1) * d] for r in range(heads)])


def _kda_put(ref, value):
    """``_kda_heads`` back: (heads, L, d) into a (L, heads * d) block."""
    d = value.shape[2]
    for r in range(value.shape[0]):
        ref[:, r * d:(r + 1) * d] = value[r].astype(ref.dtype)


def _kda_by_block(ref, j):
    """Row j of every sub-block of a (heads, L, width) scratch, each spread
    over its sub-block's 16 rows: (heads, L, width).  j may be traced."""
    heads, rows, width = ref.shape
    return jnp.concatenate(
        [jnp.broadcast_to(ref[:, pl.ds(top + j, 1), :],
                          (heads, _KDA_SUB, width))
         for top in range(0, rows, _KDA_SUB)], axis=1)


def _kda_running(tri, g):
    """The running sum of ``g`` (heads, L, d) float32 down the chunk,
    float32: ``tri``, the lower-triangular ones, is exact in bfloat16, so g
    goes through the MXU as its three bfloat16 parts side by side, one
    product a head, and nothing of its 24 bits is lost."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    heads, _, d = g.shape
    hi = g.astype(bf16)
    rest = g - hi.astype(f32)
    mid = rest.astype(bf16)
    low = (rest - mid.astype(f32)).astype(bf16)
    parts = jax.lax.dot_general(
        jnp.broadcast_to(tri, (heads,) + tri.shape),
        jnp.concatenate([hi, mid, low], axis=2), _HNN,
        preferred_element_type=f32)
    return parts[:, :, :d] + parts[:, :, d:2 * d] + parts[:, :, 2 * d:]


def _kda_chunk(qf, kf, g, beta, masks, cd, g_s, k_s, p_s=None, e_s=None,
               tinv=None):
    """A chunk of a grid step's heads, formed from its own rows.  ``qf``
    (None where ``M`` is not wanted) and ``kf`` (heads, L, d_k) float32,
    ``g`` (heads, L, d_k) the float32 log-decay, ``beta`` (heads, L, 1);
    the scratch ``g_s`` and ``k_s`` (heads, L, d_k), ``p_s`` (heads, L, L)
    where the inverse is to be made and ``e_s`` ((16, heads, L, d_k), or
    None); ``tinv`` (heads, L, L), ``(I + A)^-1`` where it is known.
    Leaves the running log-decay in ``g_s``, kf in ``k_s`` and column j's
    ``exp`` of the diagonal sub-blocks in ``e_s[j]``.  Returns ``gc`` the
    running log-decay; ``m`` (heads, L, L) float32, zero above the diagonal
    (None without ``qf``); ``kk``, zero on and above it (the raw ``A``,
    before beta, where ``tinv`` came; ``beta A`` where it did not);
    ``tinv``; and ``below``, for the sub-blocks below the diagonal [(first
    row, rows' scale, columns' scale, the scaled rows (k's, then q's), the
    scaled columns)], float32."""
    f32 = jnp.float32
    heads, chunk, dk = kf.shape
    nb = chunk // _KDA_SUB
    gc = _kda_running(masks["tri"], g)
    g_s[...] = gc
    k_s[...] = kf
    # below the diagonal: a product a row of sub-blocks
    below = []
    k_rows, m_rows = ([jnp.zeros((heads, _KDA_SUB, chunk), f32)]
                      for _ in range(2))
    for b in range(1, nb):
        top = _KDA_SUB * b
        ref = gc[:, top:top + 1]
        er = jnp.exp(gc[:, top:top + _KDA_SUB] - ref)
        ec = jnp.concatenate([jnp.exp(ref - gc[:, :top]),
                              jnp.zeros((heads, chunk - top, dk), f32)],
                             axis=1)
        rows = [kf[:, top:top + _KDA_SUB] * er]
        if qf is not None:
            rows.append(qf[:, top:top + _KDA_SUB] * er)
        rows = jnp.concatenate(rows, axis=1)
        cols = kf * ec
        part = _kda_dot(rows, cols, _HNT, cd)
        k_rows.append(part[:, :_KDA_SUB])
        if qf is not None:
            m_rows.append(part[:, _KDA_SUB:])
        below.append((top, er, ec, rows, cols))
    # the diagonal sub-blocks column by column, all of them at once, and in
    # the same pass the elimination that inverts I + A's diagonal blocks:
    # row j of each is final, and is taken off the rows below it
    solve = tinv is None
    kb = beta * kf if solve else kf
    if solve:
        p_s[...] = jnp.broadcast_to(masks["eye"], p_s.shape)

    def column(j, blocks):
        e = jnp.exp(jnp.minimum(gc - _kda_by_block(g_s, j), 0.0))
        if e_s is not None:
            e_s[j] = e
        t = _kda_by_block(k_s, j) * e
        cols = [jnp.sum(x * t, axis=2, keepdims=True)
                for x in ((kb,) if qf is None else (kb, qf))]
        if solve:
            p_s[...] -= jnp.where(masks["sub"] > j, cols[0], 0.0) \
                * _kda_by_block(p_s, j)
        hit = masks["at"] == j
        return tuple(jnp.where(hit, col, block)
                     for col, block in zip(cols, blocks))
    blocks = jax.lax.fori_loop(
        0, _KDA_SUB, column,
        (jnp.zeros((heads, chunk, chunk), f32),) * (1 if qf is None else 2),
        unroll=True)

    def whole(diagonal, rest, kept):
        return jnp.where(kept, jnp.where(masks["own"], diagonal, rest), 0.0)
    # ``kk``: beta A where the solve is to come, the raw A where it is known
    k_rest = jnp.concatenate(k_rows, axis=1)
    kk = whole(blocks[0], beta * k_rest if solve else k_rest,
               masks["strict"])
    m = None if qf is None else whole(
        blocks[1], jnp.concatenate(m_rows, axis=1), masks["incl"])
    if solve:
        # the sub-blocks joined: pairs, pairs of pairs
        tinv = p_s[...]
        size = _KDA_SUB
        while size < chunk:
            lower = ((masks["rows"] & size) != 0) & (
                (masks["cols"] & -size) == (masks["rows"] & -size) - size)
            tinv = tinv - _exact_dot(tinv, _exact_dot(
                jnp.where(lower, kk, 0.0), tinv, _HNN), _HNN)
            size *= 2
    return gc, m, kk, tinv, below


def _kda_solved(tinv, beta, kf, vf, gc):
    """``(I + A)^-1`` on ``beta V`` and on ``beta K exp G``, one product
    of both side by side: (wv float32, wk float32, ``exp G``)."""
    into = jnp.exp(gc)
    dv = vf.shape[2]
    w = _exact_dot(tinv, jnp.concatenate(
        [beta * vf, beta * (kf * into)], axis=2), _HNN)
    return w[:, :, :dv], w[:, :, dv:], into


def _kda_slot(tinv_ref, r, chunk):
    """Where head ``r``'s (L, L) inverse lies in a grid step's block of
    them: the heads side by side, as many as fill the block's lanes."""
    side = tinv_ref.shape[1] // chunk
    return (slice(r // side * chunk, (r // side + 1) * chunk),
            slice(r % side * chunk, (r % side + 1) * chunk))


def _kda_fwd_kernel(*refs, heads, states):
    """One chunk of ``heads`` heads.  ``states``: the state entering each
    chunk and the chunk's ``(I + A)^-1``, and nothing else of the forward
    (no q, no ``M``, no o)."""
    if states:
        k_ref, v_ref, g_ref, col_ref, before_ref, tinv_ref = refs[:6]
        q_ref = None
    else:
        q_ref, k_ref, v_ref, g_ref, col_ref, o_ref = refs[:6]
    st_ref, g_s, k_s, p_s = refs[6:]
    f32 = jnp.float32
    cd = k_ref.dtype
    chunk = k_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    qf = None if states else _kda_heads(q_ref, heads).astype(f32)
    kf = _kda_heads(k_ref, heads).astype(f32)
    vf = _kda_heads(v_ref, heads).astype(f32)
    beta = _kda_heads(col_ref, heads)
    gc, m, _, tinv, _ = _kda_chunk(qf, kf, _kda_heads(g_ref, heads), beta,
                                   _kda_masks(chunk), cd, g_s, k_s, p_s)
    wv, wk, into = _kda_solved(tinv, beta, kf, vf, gc)
    st = st_ref[...]
    stc = st.astype(cd)
    if states:
        before_ref[...] = st
        for r in range(heads):
            tinv_ref[_kda_slot(tinv_ref, r, chunk)] = tinv[r]
        seen = _kda_dot(wk, stc, _HNT, cd)
    else:
        both = _kda_dot(jnp.concatenate([wk, qf * into], axis=1), stc, _HNT,
                        cd)
        seen = both[:, :chunk]
    uc = (wv - seen).astype(cd)
    if not states:
        _kda_put(o_ref, both[:, chunk:] + _kda_dot(m, uc, _HNN, cd))
    end = gc[:, chunk - 1:]
    st_ref[...] = jnp.exp(end) * st + _kda_dot(
        uc, kf * jnp.exp(end - gc), _HTN, cd)


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, col_ref, do_ref, before_ref,
                    tinv_ref, dq_ref, dk_ref, dv_ref, dg_ref, dcol_ref,
                    ds_ref, g_s, k_s, e_s, c_s, *, heads):
    """One chunk's gradients, the chunks taken last to first.  ``ds_ref``
    carries the gradient of the (transposed) state that leaves the chunk."""
    f32 = jnp.float32
    cd = k_ref.dtype
    chunk = k_ref.shape[0]
    nb = chunk // _KDA_SUB

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    masks = _kda_masks(chunk)
    qf = _kda_heads(q_ref, heads).astype(f32)
    kf = _kda_heads(k_ref, heads).astype(f32)
    vf = _kda_heads(v_ref, heads).astype(f32)
    beta = _kda_heads(col_ref, heads)
    do = _kda_heads(do_ref, heads)
    dv = vf.shape[2]
    # the forward again, from the inputs, the entering state and the
    # chunk's inverse
    gc, m, kk, tinv, below = _kda_chunk(
        qf, kf, _kda_heads(g_ref, heads), beta, masks, cd, g_s, k_s, None,
        e_s, jnp.stack([tinv_ref[_kda_slot(tinv_ref, r, chunk)]
                        for r in range(heads)]))
    wv, wk, into = _kda_solved(tinv, beta, kf, vf, gc)
    st, ds = before_ref[...], ds_ref[...]
    stc, dsc = st.astype(cd), ds.astype(cd)
    qg = qf * into
    end = gc[:, chunk - 1:]
    left = jnp.exp(end - gc)
    kg = kf * left
    uc = (wv - _kda_dot(wk, stc, _HNT, cd)).astype(cd)
    # o = qg S + m u and S' = exp(G_L) S + kg^T u, backwards
    du = _kda_dot(m, do, _HTN, cd) + _kda_dot(kg, dsc, _HNT, cd)
    dm = jnp.where(masks["incl"], _kda_dot(do, uc, _HNT, cd), 0.0)
    dkg = _kda_dot(uc, dsc, _HNN, cd)
    both = jnp.concatenate([do, du.astype(cd)], axis=1)
    through = _kda_dot(both, stc, _HNN, cd)
    dqg, dwk = through[:, :chunk], -through[:, chunk:]
    at_end = jnp.sum(ds * st, axis=1, keepdims=True) * jnp.exp(end)
    ds_ref[...] = jnp.exp(end) * ds + _kda_dot(
        both, jnp.concatenate([qg, -wk], axis=1), _HTN, cd)
    # the solve, transposed: W = T^-1 R, dR = T^-T dW, dA = -tril(dR W^T)
    dr = _exact_dot(tinv, jnp.concatenate([du, dwk], axis=2), _HTN)
    drv, drk = dr[:, :, :dv], dr[:, :, dv:]
    da = -jnp.where(masks["strict"],
                    _kda_dot(drv, wv, _HNT, cd)
                    + _kda_dot(drk, wk, _HNT, cd), 0.0)
    dbeta = jnp.sum(drv * vf, axis=2, keepdims=True) \
        + jnp.sum(drk * (kf * into), axis=2, keepdims=True) \
        + jnp.sum(da * kk, axis=2, keepdims=True)
    dkk = beta * da
    _kda_put(dv_ref, beta * drv)
    dk_into = beta * drk
    # what the three decayed copies give q, k and the running log-decay
    dq = dqg * into
    dkf = dk_into * into + dkg * left
    dgc = dqg * qg + dk_into * (kf * into) - dkg * kg
    to_end = jnp.sum(dkg * kg, axis=1, keepdims=True) + at_end
    # the sub-blocks below the diagonal
    dq_rows, dk_rows, dg_rows = ([jnp.zeros((heads, _KDA_SUB, kf.shape[2]),
                                            f32)] for _ in range(3))
    for top, er, ec, rows, cols in below:
        grads = jnp.where(
            masks["col"] < top,
            jnp.concatenate([dkk[:, top:top + _KDA_SUB],
                             dm[:, top:top + _KDA_SUB]], axis=1), 0.0)
        drows = _kda_dot(grads, cols, _HNN, cd)
        dcols = _kda_dot(grads, rows, _HTN, cd)
        dk_rows.append(drows[:, :_KDA_SUB] * er)
        dq_rows.append(drows[:, _KDA_SUB:] * er)
        seen = drows * rows
        dg_rows.append(seen[:, :_KDA_SUB] + seen[:, _KDA_SUB:])
        dkf = dkf + dcols * ec
        dgc = dgc - dcols * cols
    dq = dq + jnp.concatenate(dq_rows, axis=1)
    dkf = dkf + jnp.concatenate(dk_rows, axis=1)
    dgc = dgc + jnp.concatenate(dg_rows, axis=1)

    # the diagonal sub-blocks, column by column again
    def column(j, sums):
        e = e_s[j]
        hit = masks["at"] == j
        dm_col = jnp.sum(jnp.where(hit, dm, 0.0), axis=2, keepdims=True)
        dk_col = jnp.sum(jnp.where(hit, dkk, 0.0), axis=2, keepdims=True)
        t = _kda_by_block(k_s, j) * e
        down = (dm_col * qf + dk_col * kf) * e
        for b in range(nb):
            c_s[:, pl.ds(_KDA_SUB * b + j, 1), :] = jnp.sum(
                down[:, _KDA_SUB * b:_KDA_SUB * (b + 1)], axis=1,
                keepdims=True)
        return sums[0] + dm_col * t, sums[1] + dk_col * t
    rq, rk = jax.lax.fori_loop(0, _KDA_SUB, column,
                               (jnp.zeros(kf.shape, f32),) * 2, unroll=True)
    up = c_s[...]
    _kda_put(dq_ref, dq + rq)
    _kda_put(dk_ref, dkf + rk + up)
    dgc = dgc + qf * rq + kf * (rk - up)
    # the log-decay's gradient: of the running sum, from each row on
    _kda_put(dg_ref, _kda_running(masks["tri_t"], dgc) + to_end)
    _kda_put(dcol_ref, dbeta)


_KDA = _SSD


def _kda_layout(k, v, beta, h, chunk):
    """What the three calls share: the heads a step works, the widths, the
    grid, beta as columns, and the column blocks of the (B, T, H d) arrays
    for chunk ``c(i)``: keys', values', the columns'."""
    bsz, t, _ = k.shape
    dk, dv = k.shape[2] // h, v.shape[2] // h
    heads = kda_blocks(t, h, dk, dv, chunk, k.dtype.itemsize)
    if heads is None or t % chunk:
        raise ValueError("kda_scan: no tiling for T=%d, H=%d, d_k=%d, "
                         "d_v=%d, chunk=%d (see kda_available)"
                         % (t, h, dk, dv, chunk))
    blocks = h // heads
    cols = beta.astype(jnp.float32).reshape(bsz, t, blocks, heads).transpose(
        0, 2, 1, 3)
    return heads, dk, dv, (bsz, blocks, t // chunk), cols, (
        lambda c: pl.BlockSpec((None, chunk, heads * dk),
                               lambda i, j, n: (i, c(n), j)),
        lambda c: pl.BlockSpec((None, chunk, heads * dv),
                               lambda i, j, n: (i, c(n), j)),
        lambda c: pl.BlockSpec((None, None, chunk, heads),
                               lambda i, j, n: (i, j, c(n), 0)))


def _kda_scratch(heads, chunk, dk, dv, kept):
    """The carried (d_v, d_k) states (or their gradients), ``G`` and k, then
    for the forward the inverse in the making, and for the backward
    (``kept``) the diagonal sub-blocks' ``exp`` by column and the column
    sums."""
    kinds = [(heads, dv, dk), (heads, chunk, dk), (heads, chunk, dk)] + (
        [(_KDA_SUB, heads, chunk, dk), (heads, chunk, dk)] if kept
        else [(heads, chunk, chunk)])
    return [pltpu.VMEM(kind, jnp.float32) for kind in kinds]


def _forward(n):
    return n


# Under ``jax.jit`` so that a model's layers share one trace of each body and
# one copy of it in the lowered module: ``pallas_call`` traces its body at
# every call site (PERF.md 6, PR 35).  XLA inlines the calls.
@functools.partial(jax.jit, static_argnames=("h", "chunk", "interpret"))
def kda_scan_fwd(q, k, v, g, beta, h, chunk, interpret=False):
    """o (B, T, H d_v) in v's dtype: the gated delta rule from a zero
    state.  q, k (B, T, H d_k) as the rule takes them (after their norms, q
    scaled) and v (B, T, H d_v), in the compute dtype; g (B, T, H d_k)
    float32, the log of the decay, at or below 0; beta (B, T, H) float32.
    T is a whole number of chunks."""
    heads, dk, dv, grid, cols, (keys, vals, col) = _kda_layout(
        k, v, beta, h, chunk)
    return pl.pallas_call(
        functools.partial(_kda_fwd_kernel, heads=heads, states=False),
        grid=grid,
        in_specs=[keys(_forward), keys(_forward), vals(_forward),
                  keys(_forward), col(_forward)],
        out_specs=vals(_forward),
        out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype),
        scratch_shapes=_kda_scratch(heads, chunk, dk, dv, False),
        compiler_params=_KDA,
        interpret=interpret,
        name="mxtpu_kda_fwd",
    )(q, k, v, g, cols)


@functools.partial(jax.jit, static_argnames=("h", "chunk", "interpret"))
def kda_scan_bwd(q, k, v, g, beta, do, h, chunk, interpret=False):
    """Gradients of ``kda_scan_fwd`` for o's cotangent ``do``: of q, k and
    v (in their dtypes), of g (B, T, H d_k) and beta (B, T, H), float32.
    Two calls: the state entering each chunk, (B, T / chunk, H, d_v, d_k)
    float32, and each chunk's ``(I + A)^-1``, (L, L) a head, formed again
    from the inputs; then the chunks last to first."""
    f32 = jnp.float32
    heads, dk, dv, grid, cols, (keys, vals, col) = _kda_layout(
        k, v, beta, h, chunk)
    bsz, t, _ = k.shape
    nc = grid[2]
    back = lambda n: nc - 1 - n                             # noqa: E731
    state = lambda c: pl.BlockSpec(                         # noqa: E731
        (None, None, heads, dv, dk), lambda i, j, n: (i, c(n), j, 0, 0))
    # the chunks' inverses, as many heads side by side as fill 128 lanes
    side = max(1, 128 // chunk)
    side = side if heads % side == 0 else 1
    solved = lambda c: pl.BlockSpec(                        # noqa: E731
        (None, None, heads // side * chunk, side * chunk),
        lambda i, j, n: (i, c(n), j, 0))
    before, tinv = pl.pallas_call(
        functools.partial(_kda_fwd_kernel, heads=heads, states=True),
        grid=grid,
        in_specs=[keys(_forward), vals(_forward), keys(_forward),
                  col(_forward)],
        out_specs=[state(_forward), solved(_forward)],
        out_shape=[jax.ShapeDtypeStruct((bsz, nc, h, dv, dk), f32),
                   jax.ShapeDtypeStruct(
                       (bsz, nc, h // side * chunk, side * chunk), f32)],
        scratch_shapes=_kda_scratch(heads, chunk, dk, dv, False),
        compiler_params=_KDA,
        interpret=interpret,
        name="mxtpu_kda_states",
    )(k, v, g, cols)
    dq, dkey, dval, dg, dcols = pl.pallas_call(
        functools.partial(_kda_bwd_kernel, heads=heads),
        grid=grid,
        in_specs=[keys(back), keys(back), vals(back), keys(back), col(back),
                  vals(back), state(back), solved(back)],
        out_specs=[keys(back), keys(back), vals(back), keys(back),
                   col(back)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, f32),
                   jax.ShapeDtypeStruct(cols.shape, f32)],
        scratch_shapes=_kda_scratch(heads, chunk, dk, dv, True),
        compiler_params=_KDA,
        interpret=interpret,
        name="mxtpu_kda_bwd",
    )(q, k, v, g, cols, do, before, tinv)
    return dq, dkey, dval, dg, dcols.transpose(0, 2, 1, 3).reshape(bsz, t, h)


# ------------------------------------------------- the causal convolution
# The backward of ``causal_conv1d`` (ops/ssm.py), one pass over the rows:
# ``y = act(sum_j w_j x[t - K + 1 + j] + b)``, so with ``g = dy act'(y_pre)``
# ``dx[t] = sum_j w_j g[t + K - 1 - j]``, ``dw_j = sum_t x[t] g[t + K - 1 -
# j]`` and ``db = sum_t g[t]``: one shifted g serves dx and dw alike.  A grid
# step takes a block of rows and columns of x and dy with a halo of
# ``_CONV_HALO`` rows: x's before and after the block, dy's after it (each a
# block of its own whose index is clamped at the ends and whose rows there
# are taken as zeros).  x and g are float32 in VMEM scratch; the body works
# 16 rows at a time, a tap being the window of 32 rows from the strip's
# first rolled to it, so that a strip's values stay in registers (the whole
# block at once spilled them: 0.77 ms for a layer's forward and backward
# at (4096, 6144), 0.46 by strips; PERF.md 6, PR 38).  g never leaves VMEM;
# dx leaves once in x's dtype; ``dw`` and ``db`` are summed in float32 over
# the row blocks in one (K + 1, columns) output block that stays in VMEM
# along the sequential row axis, one a sequence.

_CONV_HALO = 16          # rows: a bfloat16 tile's; K - 1 taps at most
# tried on the v5e at (4096, 6144) and (4096, 4096), bfloat16: every pair of
# (512-4096 rows, 128-512 columns) within 10% by wall time (PERF.md 6, PR 38)
_CONV_ROWS = (1024, 512, 256, 128, 64, 32, 16)
_CONV_COLS = (256, 128)


def _conv_vmem(rows, cols, itemsize):
    """What a grid step keeps in VMEM: two buffers of the x, dy and dx
    blocks and of the three halos, and the float32 scratch of x and g; the
    body works a strip of 16 rows at a time, in registers."""
    halo = _CONV_HALO
    return 2 * (3 * rows + 3 * halo) * cols * itemsize \
        + (2 * rows + 3 * halo) * cols * 4


def conv_blocks(t, c, k, itemsize):
    """(rows, columns) of a grid step of ``causal_conv_bwd`` for a sequence
    of T steps and C channels, K taps, operands of ``itemsize`` bytes: the
    most rows, then the most columns, that divide T and C and fit the VMEM
    budget.  None where the kernel does not apply: T no multiple of the
    halo's 16 rows, C no multiple of the 128 lanes, more taps than the halo
    holds."""
    if min(t, c, k) <= 0 or t % _CONV_HALO or c % 128 \
            or k - 1 > _CONV_HALO:
        return None
    return next(((r, n) for r in _CONV_ROWS for n in _CONV_COLS
                 if t % r == 0 and c % n == 0
                 and _conv_vmem(r, n, itemsize) <= _VMEM_BUDGET), None)


def _conv_bwd_kernel(xp_ref, x_ref, xn_ref, dy_ref, dyn_ref, w_ref, b_ref,
                     dx_ref, dw_ref, xs_ref, gs_ref, *, k, act):
    f32 = jnp.float32
    i, last = pl.program_id(2), pl.num_programs(2) - 1
    rows, cols = x_ref.shape
    halo = strip = _CONV_HALO
    xs_ref[:halo] = jnp.where(i > 0, xp_ref[...].astype(f32), 0.0)
    xs_ref[halo:halo + rows] = x_ref[...].astype(f32)
    xs_ref[halo + rows:] = jnp.where(i < last, xn_ref[...].astype(f32), 0.0)
    gs_ref[:rows] = dy_ref[...].astype(f32)
    gs_ref[rows:] = jnp.where(i < last, dyn_ref[...].astype(f32), 0.0)
    w = [w_ref[pl.ds(j, 1), :] for j in range(k)]

    def window(ref, s):         # two strips from strip s, as rows
        return ref[pl.ds(pl.multiple_of(s * strip, strip), 2 * strip), :]

    def shifted(win, at):       # the strip's rows from row ``at`` of win
        return pltpu.roll(win, 2 * strip - at, 0)[:strip] if at else \
            win[:strip]

    # strip by strip, so that a strip's values stay in registers: g over
    # the block's rows and the halo's, from y before the activation ...
    if act is not None:
        def g_strip(s, carry):
            win = window(xs_ref, s)
            y = b_ref[...] + sum(w[j] * shifted(win, halo - k + 1 + j)
                                 for j in range(k))
            at = pl.ds(pl.multiple_of(s * strip, strip), strip)
            gs_ref[at, :] = jax.vjp(act, y)[1](gs_ref[at, :])[0]
            return carry
        jax.lax.fori_loop(0, rows // strip + 1, g_strip, 0)

    # ... then dx and the sums of dw and db, each tap's g shared by both
    def dx_strip(s, sums):
        win = window(gs_ref, s)
        at = pl.ds(pl.multiple_of(s * strip, strip), strip)
        x = xs_ref[pl.ds(pl.multiple_of(s * strip + halo, strip), strip), :]
        taps = [shifted(win, k - 1 - j) for j in range(k)]
        dx_ref[at, :] = sum(w[j] * taps[j] for j in range(k)).astype(
            dx_ref.dtype)
        return tuple(acc + (part[:8] + part[8:]) for acc, part in zip(
            sums, [x * tap for tap in taps] + [taps[k - 1]]))
    sums = jax.lax.fori_loop(0, rows // strip, dx_strip, tuple(
        jnp.zeros((8, cols), f32) for _ in range(k + 1)))

    @pl.when(i == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, f32)
    for j, acc in enumerate(sums):
        dw_ref[pl.ds(j, 1), :] += jnp.sum(acc, axis=0, keepdims=True)


# Under ``jax.jit``: the layers of one shape share one trace and one lowered
# body (PERF.md 6, PR 35).
@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def causal_conv_bwd(data, weight, bias, dy, act=None, interpret=False):
    """Gradients of ``causal_conv1d`` (data (B, T, C), weight (C, K), bias
    (C,) or None, then the elementwise ``act`` if given) for y's cotangent
    ``dy``: of data, weight and bias, each in its own dtype, from float32
    sums.  The kernel ``mxtpu_conv_bwd``; T and C as ``conv_blocks``
    takes them."""
    f32 = jnp.float32
    bsz, t, c = data.shape
    k = weight.shape[1]
    blocks = conv_blocks(t, c, k, data.dtype.itemsize)
    if blocks is None:
        raise ValueError("causal_conv1d: no tiling for T=%d, C=%d, K=%d "
                         "(see conv_blocks)" % (t, c, k))
    rows, cols = blocks
    halo, per = _CONV_HALO, rows // _CONV_HALO
    before = lambda b, j, i: (b, jnp.maximum(i * per - 1, 0), j)  # noqa: E731
    after = lambda b, j, i: (  # noqa: E731
        b, jnp.minimum((i + 1) * per, t // halo - 1), j)
    block = pl.BlockSpec((None, rows, cols), lambda b, j, i: (b, i, j))
    edge = lambda at: pl.BlockSpec((None, halo, cols), at)  # noqa: E731
    row = lambda n: pl.BlockSpec(  # noqa: E731
        (n, cols), lambda b, j, i: (0, j))
    b_row = jnp.zeros((1, c), f32) if bias is None else \
        bias.astype(f32).reshape(1, c)
    dx, dwb = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, k=k, act=act),
        grid=(bsz, c // cols, t // rows),
        in_specs=[edge(before), block, edge(after), block, edge(after),
                  row(k), row(1)],
        out_specs=[block, pl.BlockSpec((None, k + 1, cols),
                                       lambda b, j, i: (b, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(data.shape, data.dtype),
                   jax.ShapeDtypeStruct((bsz, k + 1, c), f32)],
        scratch_shapes=[pltpu.VMEM((rows + 2 * halo, cols), f32),
                        pltpu.VMEM((rows + halo, cols), f32)],
        compiler_params=_SSD,
        interpret=interpret,
        name="mxtpu_conv_bwd",
    )(data, data, data, dy, dy, weight.astype(f32).T, b_row)
    dwb = dwb.sum(0)
    return dx, dwb[:k].T.astype(weight.dtype), (
        None if bias is None else dwb[k].astype(bias.dtype))


# ------------------------------------------------------- the gated group norm
# ``out = u r gamma`` with ``u = x silu(z)`` and ``r = rsqrt(mean_g(u^2) +
# eps)`` over each group of the channels; the backward, with ``v = u r`` and
# ``a = gamma dout``: ``du = r (a - v mean_g(a v))``, ``dx = du silu(z)``,
# ``dz = du x silu'(z)`` and ``dgamma = sum_rows dout v``.  A grid step takes
# a block of rows and one group's columns, so that a block holds whole
# groups; the body works ``_GNORM_STRIP`` rows at a time (the block's rows
# where fewer).  u, r and every sum are float32; the inputs are read once and
# the outputs written once, in their own dtypes.  Nothing passes from the
# forward to the backward but the op's inputs: the backward forms u and r
# again.  ``dgamma`` is summed in a float32 row that stays in VMEM along the
# sequential row axis.

# tried on the v5e at (4096, 4096) in 8 groups, bfloat16, blocks of 512 rows:
# forward and backward 0.36 ms a layer inside the step with strips of 128
# rows, 0.47 with 32, 0.79 with 16 (PERF.md 6)
_GNORM_STRIP = 128
_GNORM_ROWS = (512, 256, 128, 64, 32, 16)     # 16: a bfloat16 tile's
_GNORM = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_BUDGET + 16 * 1024 * 1024)


def _gnorm_vmem(rows, width, itemsize):
    """What a grid step of the backward keeps in VMEM: two buffers of its
    three input and two output blocks (the forward keeps less)."""
    return 2 * 5 * rows * width * itemsize


def gnorm_blocks(t, c, g, itemsize):
    """Rows of a grid step of ``gnorm_fwd`` / ``gnorm_bwd`` over T rows of C
    channels in G groups, operands of ``itemsize`` bytes: the most that
    divide T and fit the VMEM budget.  None where the kernels do not apply:
    C no multiple of G, a group's width no multiple of the 128 lanes, T no
    multiple of 16 rows."""
    if min(t, c, g) <= 0 or c % g or (c // g) % 128 or t % _GNORM_ROWS[-1]:
        return None
    return next((r for r in _GNORM_ROWS if t % r == 0 and _gnorm_vmem(
        r, c // g, itemsize) <= _VMEM_BUDGET), None)


def gnorm_available(t, c, g, itemsize):
    """Whether ``gnorm_blocks`` tiles T rows of C channels in G groups."""
    return gnorm_blocks(t, c, g, itemsize) is not None


def _gnorm_rows(x_ref):
    """(rows of a strip, strips of the block)."""
    strip = min(x_ref.shape[0], _GNORM_STRIP)
    return strip, x_ref.shape[0] // strip


def _gnorm_strip(x_ref, z_ref, s, eps):
    """Strip s's rows: where they lie, x, sigmoid(z), silu(z), u and r,
    float32."""
    rows, _ = _gnorm_rows(x_ref)
    at = pl.ds(pl.multiple_of(s * rows, rows), rows)
    x = x_ref[at, :].astype(jnp.float32)
    z = z_ref[at, :].astype(jnp.float32)
    sig = jax.nn.sigmoid(z)
    gate = z * sig
    u = x * gate
    r = jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)
    return at, x, z, sig, gate, u, r


def _gnorm_fwd_kernel(x_ref, z_ref, w_ref, o_ref, *, eps):
    w = w_ref[...]

    def strip(s, carry):
        at, _, _, _, _, u, r = _gnorm_strip(x_ref, z_ref, s, eps)
        o_ref[at, :] = (u * r * w).astype(o_ref.dtype)
        return carry
    jax.lax.fori_loop(0, _gnorm_rows(x_ref)[1], strip, 0)


def _gnorm_bwd_kernel(x_ref, z_ref, w_ref, do_ref, dx_ref, dz_ref, dw_ref, *,
                      eps):
    w = w_ref[...]

    def strip(s, acc):
        at, x, z, sig, gate, u, r = _gnorm_strip(x_ref, z_ref, s, eps)
        dout = do_ref[at, :].astype(jnp.float32)
        v = u * r
        a = dout * w
        du = r * (a - v * jnp.mean(a * v, axis=-1, keepdims=True))
        dx_ref[at, :] = (du * gate).astype(dx_ref.dtype)
        dz_ref[at, :] = (du * x * (sig * (1.0 + z * (1.0 - sig)))).astype(
            dz_ref.dtype)
        part = dout * v
        return acc + sum(part[k:k + 8] for k in range(0, part.shape[0], 8))
    acc = jax.lax.fori_loop(0, _gnorm_rows(x_ref)[1], strip,
                            jnp.zeros((8, w.shape[1]), jnp.float32))

    @pl.when(pl.program_id(1) == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)
    dw_ref[...] += jnp.sum(acc, axis=0, keepdims=True)


def _gnorm_layout(data, groups):
    """(T, C, rows of a grid step, the block's spec, gamma's row spec) of
    (..., C) arrays taken as (T, C)."""
    c = data.shape[-1]
    t = data.size // c
    rows = gnorm_blocks(t, c, groups, data.dtype.itemsize)
    if rows is None:
        raise ValueError("gated group norm: no tiling for T=%d, C=%d, G=%d "
                         "(see gnorm_blocks)" % (t, c, groups))
    width = c // groups
    return (t, c, rows, pl.BlockSpec((rows, width), lambda j, i: (i, j)),
            pl.BlockSpec((1, width), lambda j, i: (0, j)))


# Under ``jax.jit``: the layers of one shape share one trace and one lowered
# body.
@functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def gnorm_fwd(data, gate, gamma, groups, eps, interpret=False):
    """``data silu(gate)`` normed by its root mean square over each of
    ``groups`` equal groups of the last axis, times ``gamma``, in data's
    dtype: the kernel ``mxtpu_gnorm_fwd``.  data and gate (..., C) of one
    dtype, gamma (C,); the rows as ``gnorm_blocks`` takes them."""
    t, c, rows, block, row = _gnorm_layout(data, groups)
    out = pl.pallas_call(
        functools.partial(_gnorm_fwd_kernel, eps=eps),
        grid=(groups, t // rows),
        in_specs=[block, block, row],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((t, c), data.dtype),
        compiler_params=_GNORM,
        interpret=interpret,
        name="mxtpu_gnorm_fwd",
    )(data.reshape(t, c), gate.reshape(t, c),
      gamma.astype(jnp.float32).reshape(1, c))
    return out.reshape(data.shape)


@functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def gnorm_bwd(data, gate, gamma, dout, groups, eps, interpret=False):
    """Gradients of ``gnorm_fwd`` for its result's cotangent ``dout``: of
    data, gate and gamma, each in its own dtype, from float32 sums.  The
    kernel ``mxtpu_gnorm_bwd``."""
    t, c, rows, block, row = _gnorm_layout(data, groups)
    dx, dz, dw = pl.pallas_call(
        functools.partial(_gnorm_bwd_kernel, eps=eps),
        grid=(groups, t // rows),
        in_specs=[block, block, row, block],
        out_specs=[block, block, row],
        out_shape=[jax.ShapeDtypeStruct((t, c), data.dtype),
                   jax.ShapeDtypeStruct((t, c), gate.dtype),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        compiler_params=_GNORM,
        interpret=interpret,
        name="mxtpu_gnorm_bwd",
    )(data.reshape(t, c), gate.reshape(t, c),
      gamma.astype(jnp.float32).reshape(1, c), dout.reshape(t, c))
    return (dx.reshape(data.shape), dz.reshape(gate.shape),
            dw.reshape(gamma.shape).astype(gamma.dtype))
