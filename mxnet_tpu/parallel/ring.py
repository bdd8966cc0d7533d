"""Ring attention — sequence/context parallelism over the device mesh
(SURVEY.md §5.7: the reference has NO long-context story beyond bucketing +
BPTT; this is the TPU-native capability that replaces it at scale).

Design (Liu et al., Ring Attention; flash-attention online softmax):

- the sequence axis of Q/K/V is sharded across the ``sp`` mesh axis — each
  device holds one block of queries and one block of keys/values;
- queries stay put; K/V blocks rotate around the ring with
  ``jax.lax.ppermute`` (nearest-neighbour ICI hops — bandwidth-optimal, no
  all-gather materialisation of the full sequence);
- each device folds every incoming K/V block into its local attention with
  the numerically-stable online-softmax recurrence (running max ``m``,
  normaliser ``l``, unnormalised output ``o``), so the full (T, T) score
  matrix never exists anywhere;
- causal masking compares *global* positions (block offset = ring index ×
  block length), so device boundaries are invisible to the math;
- the whole loop lives inside one ``shard_map`` region: XLA overlaps the
  ppermute transfer of block i+1 with the matmuls of block i.

Gradients flow through ``ppermute``/``fori_loop`` natively, so ``jax.vjp``
over ``ring_attention`` yields the ring-parallel backward pass for free.
"""
from __future__ import annotations

import functools
import math

__all__ = ["ring_attention", "attention_reference", "sequence_sharding"]


def sequence_sharding(mesh, axis="sp"):
    """NamedSharding placing (B, H, T, D) arrays with T split over ``axis``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec(None, None, axis, None))


def attention_reference(q, k, v, causal=False, scale=None):
    """Plain full-sequence attention (the single-device semantics ring
    attention must reproduce; also the small-sequence fast path)."""
    import jax.numpy as jnp
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        qpos = jnp.arange(tq)[:, None]
        kpos = jnp.arange(tk)[None, :]
        s = jnp.where(kpos <= qpos, s, -jnp.inf)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    return jnp.einsum("bhqk,bhkd->bhqd", p, v) / p.sum(axis=-1,
                                                       keepdims=True)


def ring_attention(q, k, v, mesh, axis="sp", causal=False, scale=None):
    """Attention over sequences sharded along ``axis`` of ``mesh``.

    q, k, v: (B, H, T, D) jax arrays (global views, T sharded over ``axis``).
    Returns the attention output with the same sharding as q.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    d = q.shape[-1]
    sc = scale if scale is not None else 1.0 / math.sqrt(d)

    def local(qb, kb, vb):
        # qb/kb/vb: (B, H, Tl, D) — this device's blocks
        idx = jax.lax.axis_index(axis)
        tl = qb.shape[2]
        q_pos = idx * tl + jnp.arange(tl)              # global query positions
        perm = [(i, (i + 1) % n) for i in range(n)]    # ring: send to right

        def fold(i, o, m, l, kb, vb):
            # block i arrived from rank (idx - i) mod n
            src = (idx - i) % n
            k_pos = src * tl + jnp.arange(tl)
            # scores and the online-softmax state stay in f32 regardless of
            # input dtype: bf16 exp-sums/correction factors accumulated over
            # many ring steps degrade long-context accuracy (the Pallas flash
            # kernel keeps these in f32 for the same reason)
            s = jnp.einsum("bhqd,bhkd->bhqk", qb, kb,
                           preferred_element_type=jnp.float32) * sc
            if causal:
                mask = k_pos[None, :] <= q_pos[:, None]
                s = jnp.where(mask[None, None], s, -jnp.inf)
            blk_max = s.max(axis=-1, keepdims=True)
            new_m = jnp.maximum(m, blk_max)
            # all-masked blocks produce -inf maxima; keep the math finite
            safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
            p = jnp.exp(s - safe_m)
            if causal:
                p = jnp.where(mask[None, None], p, 0.0)
            corr = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m) - safe_m)
            corr = jnp.where(jnp.isneginf(m), 0.0, corr)
            l = l * corr + p.sum(axis=-1, keepdims=True)
            o = o * corr + jnp.einsum("bhqk,bhkd->bhqd", p, vb,
                                      preferred_element_type=jnp.float32)
            return o, new_m, l

        def body(i, carry):
            o, m, l, kb, vb = carry
            o, m, l = fold(i, o, m, l, kb, vb)
            # rotate K/V one hop around the ring (overlaps with next fold)
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            return o, m, l, kb, vb

        o = jnp.zeros(qb.shape, jnp.float32)
        m = jnp.full(qb.shape[:3] + (1,), -jnp.inf, jnp.float32)
        l = jnp.zeros(qb.shape[:3] + (1,), jnp.float32)
        # n-1 rotated folds, then the last block in place: no wasted final hop
        o, m, l, kb, vb = jax.lax.fori_loop(0, n - 1, body,
                                            (o, m, l, kb, vb))
        o, m, l = fold(n - 1, o, m, l, kb, vb)
        return (o / jnp.maximum(l, 1e-30)).astype(qb.dtype)

    spec = P(None, None, axis, None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
