"""Attention operators (NEW capability — the reference has no attention op
anywhere in src/operator, SURVEY.md §5.7; designed TPU-first from the start).

``dot_product_attention`` is the core primitive: (B, H, T, D) Q and K and
(B, H, T, Dv) V in, (B, H, T, Dv) out.  The value heads may have a width of
their own (a latent-attention layer's 128 beside query/key heads of 192):
every rung of the ladder takes it, the flash kernels with both widths and no
padding.  Unequal head counts are grouped-query attention: K and V may come
with fewer heads, H_kv dividing H; query head ``i`` reads key/value head
``i // (H / H_kv)``.  The key/value heads are repeated ahead of the lowering
ladder, so every rung (ring, flash kernels, XLA) sees equal shapes and the
backward sums each group's gradients; equal head counts take the path they
always took.  When a sequence-parallel mesh is active
(``mxnet_tpu.parallel.mesh.set_sequence_mesh``) it lowers to ring attention —
K/V blocks rotating over the ``sp`` mesh axis via ``ppermute`` with
online-softmax accumulation — so the same symbol graph scales from one chip
to a long-context multi-chip ring without changes.

``MultiHeadAttention``-style projections are composed at the symbol level
(models/transformer.py) from FullyConnected/Reshape/transpose, keeping the
MXU-shaped matmuls visible to XLA.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register, parse_bool, parse_float


def _attn_infer(attrs, in_shapes):
    q, v = in_shapes[0], in_shapes[2]
    out = q if q is None or v is None else tuple(q[:-1]) + (v[-1],)
    return list(in_shapes), [out], None


@register("dot_product_attention", arg_names=("query", "key", "value"),
          attr_types={"causal": parse_bool, "scale": parse_float,
                      "impl": str},
          defaults={"causal": False, "scale": None, "impl": "auto"},
          infer_shape=_attn_infer)
def _dot_product_attention(query, key, value, causal=False, scale=None,
                           impl="auto"):
    """Scaled dot-product attention over (B, H, T, D); value (B, H, T, Dv)
    may have a head width of its own and gives the result's; ``scale``
    defaults to 1 / sqrt(D).  Key and value may have H_kv < H heads
    (grouped-query: each is read by H / H_kv query heads, repeated here
    before the ladder).

    Lowering ladder (impl='auto'):
    1. sequence mesh active -> ring attention (multi-chip, ppermute ring);
    2. TPU + flash-friendly shapes (``flash_available``: Dv = D or not)
       + T >= 512 -> Pallas flash kernel
       (blocked online-softmax, no (T, T) score matrix, so its memory is
       O(T) where XLA's backward keeps the scores; kernel times on the
       v5e: PERF.md 5);
    3. otherwise -> the XLA reference expression (fused fine at short T).
    ``impl`` forces 'flash' / 'xla' for testing."""
    from ..parallel import mesh as mesh_mod
    from ..parallel import ring
    from . import pallas_kernels
    groups, rest = divmod(query.shape[1], key.shape[1])
    if rest or key.shape[1] != value.shape[1]:
        raise ValueError("dot_product_attention: %d query heads on %d key "
                         "and %d value heads" % (query.shape[1], key.shape[1],
                                                 value.shape[1]))
    if groups > 1:
        key = jnp.repeat(key, groups, axis=1)
        value = jnp.repeat(value, groups, axis=1)
    mesh, axis = mesh_mod.sequence_mesh()
    if mesh is not None:
        return ring.ring_attention(query, key, value, mesh, axis=axis,
                                   causal=causal, scale=scale)
    use_flash = impl == "flash" or (
        impl == "auto" and jax.default_backend() == "tpu"
        and query.shape[2] >= 512
        and pallas_kernels.flash_available(query.shape, key.shape,
                                           value.shape))
    if use_flash:
        return pallas_kernels.flash_attention(query, key, value, causal,
                                              scale)
    return ring.attention_reference(query, key, value, causal=causal,
                                    scale=scale)


@register("position_ids", arg_names=("data",),
          attr_types={"seq_len": int}, defaults={"seq_len": 0},
          infer_shape=lambda attrs, ins: (list(ins), [ins[0]], None))
def _position_ids(data, seq_len=0):
    """Token positions 0..T-1 broadcast over the batch of a (B, T) input.
    ``seq_len``, when given, must agree with the data width (it exists so
    the position-embedding table size is visible in the symbol attrs)."""
    t = data.shape[-1]
    if seq_len and int(seq_len) != int(t):
        raise ValueError("position_ids: seq_len=%d != data width %d"
                         % (seq_len, t))
    return jnp.broadcast_to(jnp.arange(t, dtype=jnp.float32), data.shape)


@register("softmax_mask", arg_names=("data", "mask"))
def _softmax_mask(data, mask):
    """Masked softmax over the last axis (mask 1=keep, 0=drop)."""
    neg = jnp.finfo(data.dtype).min
    s = jnp.where(mask != 0, data, neg)
    return jax.nn.softmax(s, axis=-1)


@register("LayerNorm", arg_names=("data", "gamma", "beta"),
          attr_types={"axis": int, "eps": parse_float},
          defaults={"axis": -1, "eps": 1e-5},
          infer_shape=lambda attrs, ins: (
              [ins[0],
               None if ins[0] is None else (ins[0][int(attrs.get("axis", -1))],),
               None if ins[0] is None else (ins[0][int(attrs.get("axis", -1))],)],
              [ins[0]], None))
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5):
    """Layer normalization (transformer building block; HBM-friendly fused
    mean/var on the fly — XLA fuses this into neighbouring matmuls)."""
    mu = data.mean(axis=axis, keepdims=True)
    var = ((data - mu) ** 2).mean(axis=axis, keepdims=True)
    xhat = (data - mu) * jax.lax.rsqrt(var + eps)
    return xhat * gamma + beta


def _rms_args(attrs):
    return ["data", "gamma", "gate"] if attrs.get("gated", False) else \
        ["data", "gamma"]


@register("RMSNorm", arg_names=_rms_args,
          attr_types={"eps": parse_float, "num_groups": int,
                      "gated": parse_bool},
          defaults={"eps": 1e-5, "num_groups": 1, "gated": False},
          infer_shape=lambda attrs, ins: (
              [ins[0], None if ins[0] is None else (ins[0][-1],)]
              + [ins[0]] * (len(ins) - 2), [ins[0]], None))
def _rms_norm(data, gamma, gate=None, eps=1e-5, num_groups=1, gated=False):
    """Root-mean-square normalization over the last axis, ``x / sqrt(mean(x^2)
    + eps) * gamma``; with ``num_groups`` > 1 the mean is taken over each of
    that many equal groups of the axis.  ``gated`` adds a third input and
    norms ``x * silu(gate)``, gate first (the gated norm of a state-space
    mixer).  Statistics in float32 whatever the input's dtype; of the
    forward only the inputs are kept.  Gated, on a TPU and for the shapes
    ``gnorm_available`` takes, the kernels of ``ssm.gated_group_norm``;
    else, and ungated always, the ``jax.numpy`` form below."""
    if gated and jax.default_backend() == "tpu" \
            and gate.dtype == data.dtype:
        from . import pallas_kernels, ssm
        c = data.shape[-1]
        if pallas_kernels.gnorm_available(data.size // max(c, 1), c,
                                          int(num_groups),
                                          data.dtype.itemsize):
            return ssm.gated_group_norm(data, gamma, gate, eps,
                                        int(num_groups))

    @jax.checkpoint
    def norm(data, gamma, gate):
        x = data.astype(jnp.float32)
        if gate is not None:
            x = x * jax.nn.silu(gate.astype(jnp.float32))
        g = int(num_groups)
        grouped = x.reshape(x.shape[:-1] + (g, x.shape[-1] // g))
        var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        y = (grouped * jax.lax.rsqrt(var + eps)).reshape(x.shape)
        return (y * gamma.astype(jnp.float32)).astype(data.dtype)
    return norm(data, gamma, gate)
