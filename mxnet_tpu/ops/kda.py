"""Gated delta rule with a decay of its own for every key channel (NEW
capability, no reference analogue): the linear-attention mixer of Kimi Delta
Attention (Kimi Linear, arXiv:2510.26692), per head with a state ``S`` of
(d_k, d_v), decays ``a_t`` in (0, 1)^{d_k} and a step ``beta_t`` in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``kda_scan`` computes it in the chunked form (chunks of L steps).  With
``u_t = beta_t (v_t - (Diag(a_t) S_{t-1})^T k_t)`` the rule is ``S_t =
Diag(a_t) S_{t-1} + k_t u_t^T``, and inside a chunk that enters with state
``S`` and running log-decays ``G_i = sum_{t <= i} log a_t``:

    (I + A) U = beta (V - (K exp G) S)      A_ij = beta_i <k_i, k_j exp(G_i - G_j)>, j < i
    O = (Q exp G) S + M U                   M_ij = <q_i, k_j exp(G_i - G_j)>, j <= i
    S' = Diag(exp G_L) S + (K exp(G_L - G))^T U

so a chunk is matrix products of (L, L) and (L, d) blocks and one solve with
a unit lower-triangular matrix (the WY form): ``(I + A)^-1`` is applied once
to ``beta V`` and to ``beta K exp G``; what is left between chunks is a
short sequential pass of four products a chunk.  One algorithm, two forms of
it, chosen from what the op observes (the backend and the shape, never an
option):

- On a TPU, for the shapes ``pallas_kernels.kda_available`` takes (d_k and
  d_v of whole 128-lane tiles, chunks of 16, 32, 64 or 128; T is padded to
  whole chunks): the kernels ``mxtpu_kda_fwd`` / ``_states`` / ``_bwd``.
  They read q, k, v and the log-decay as column blocks of the op's
  (B, T, H d) arrays where they lie; a chunk's running log-decay, its
  (L, L) blocks, the solve and the carried (d_k, d_v) state stay in VMEM; o
  leaves once, in value's dtype.  The backward is written by hand under one
  ``jax.custom_vjp`` whose residuals are the op's seven inputs: the states
  entering each chunk are formed again by a states-only pass into one
  (T / L, H, d_v, d_k) float32 array (128 MiB at T = 4096 with 32 heads of
  128, alive during that layer's backward alone) beside each chunk's
  ``(I + A)^-1`` (32 MiB), then the chunks are taken last to first with the
  state's gradient carried in VMEM; with ``W = T^-1 R`` the solve's
  gradient is ``dR = T^-T dW`` and ``dA = -tril(dR W^T, -1)``: products
  alone.  The l2 norms, the gates ``kda_gates`` and ``sigmoid(beta)`` are
  made outside, on their (T, H, d) arrays, and autodiff carries the
  kernels' gradients through them to q, k, the gate, beta, ``A_log`` and
  ``dt_bias``.  The two callers of the kernels are under ``jax.jit``: a
  model's mixers share one trace and one lowered copy of each body, which
  is what a run pays every time it starts (PERF.md 6, PR 35).
- Everywhere else: ``kda_chunked``, plain ``jax.numpy``, which is also the
  tests' oracle.  Its backward is autodiff under ``jax.checkpoint``: of the
  forward only the op's inputs are kept, the blocks inside the chunks are
  formed again a few heads at a time (so that no (T / L, L, L, d_k) array
  of all heads exists at once), and the pass between chunks keeps one
  state a chunk, never one a token.

**Never the exponential of a positive sum.**  ``exp(G_i - G_j)`` is needed
for i >= j only, where it is at most 1, but ``exp(G_i) exp(-G_j)`` overflows
float32 once a chunk's running log-decay passes -88, which the published
initialisation reaches (A = 16, dt = 0.1: -1.6 a token, -102 over 64).  The
(L, L) blocks are therefore formed by sub-blocks of 16 rows: a sub-block
below the diagonal against the running sum at its own first row ``r``, as
``(x_i exp(G_i - G_r)) (k_j exp(G_r - G_j))^T`` with both exponents at or
below 0 (rows at or after r, columns before it); a sub-block on the diagonal
directly, element by element, from ``exp(G_i - G_j)`` where i >= j.

Precision, in both forms alike: the log-decay ``-exp(A_log) softplus(gate +
dt_bias)``, its running sums, every ``exp``, ``beta``, the l2 norms, the
triangular solve and the carried state are float32 whatever the input's
dtype; the matrix products take operands in the input's dtype, cast where
the plain form casts them, and accumulate in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import pallas_kernels
from .registry import register, parse_int

_SUB = 16               # rows of a sub-block
_GROUP_BYTES = 64 << 20  # of one group's (T / L, L / 16, 16, 16, d_k) f32 array


def _l2norm(x, eps=1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _blocks(x, k, gs, gc, cd):
    """The (L, L) block ``<x_i, k_j exp(G_i - G_j)>`` for j <= i, zero above
    the diagonal.  x, k (n, c, L, d) float32; gc (n, c, L, d) the running
    log-decay; gs the same by sub-blocks (n, c, L / s, s, d)."""
    f32 = jnp.float32
    n, c, l, d = k.shape
    ns, s = gs.shape[2], gs.shape[3]
    xs, ks = (v.reshape(n, c, ns, s, d) for v in (x, k))
    ref = gs[:, :, :, :1]                        # each sub-block's first row
    rows = (xs * jnp.exp(gs - ref)).astype(cd)
    before = jnp.arange(l)[None, :] < (jnp.arange(ns) * s)[:, None]
    cols = jnp.exp(jnp.where(before[:, :, None], ref - gc[:, :, None],
                             -jnp.inf))          # (n, c, ns, L, d)
    below = jnp.einsum("ncbsd,ncbld->ncbsl", rows,
                       (k[:, :, None] * cols).astype(cd),
                       preferred_element_type=f32).reshape(n, c, l, l)
    tri = jnp.tril(jnp.ones((s, s), bool))[:, :, None]
    seg = jnp.exp(jnp.where(tri, gs[:, :, :, :, None] - gs[:, :, :, None],
                            -jnp.inf))           # (n, c, ns, s, s, d)
    on = jnp.sum(xs[:, :, :, :, None] * ks[:, :, :, None] * seg, axis=-1)
    on = on[:, :, :, :, None, :] * jnp.eye(ns, dtype=f32)[:, None, :, None]
    return below + on.reshape(n, c, l, l)


def _inside_chunks(q, k, v, g, beta):
    """What a chunk needs of its own rows, for ``n`` heads and every chunk
    at once.  q, k (n, c, L, d_k) and v (n, c, L, d_v) in the compute
    dtype, g (n, c, L, d_k) float32 log-decay, beta (n, c, L) float32.
    Returns (M, (I + A)^-1 beta V, (I + A)^-1 beta K exp G, Q exp G,
    K exp(G_L - G), exp G_L)."""
    f32 = jnp.float32
    cd = v.dtype
    n, c, l, d = k.shape
    gc = jnp.cumsum(g, axis=2)
    gs = gc.reshape(n, c, l // _SUB, _SUB, d)
    qf, kf = q.astype(f32), k.astype(f32)
    m = _blocks(qf, kf, gs, gc, cd)
    a = jnp.tril(_blocks(kf, kf, gs, gc, cd), -1) * beta[..., None]
    into = jnp.exp(gc)
    rhs = jnp.concatenate([v.astype(f32), kf * into], axis=-1) \
        * beta[..., None]
    w = jax.scipy.linalg.solve_triangular(a, rhs, lower=True,
                                          unit_diagonal=True)
    dv = v.shape[-1]
    return (m.astype(cd), w[..., :dv], w[..., dv:].astype(cd),
            (qf * into).astype(cd),
            (kf * jnp.exp(gc[:, :, -1:] - gc)).astype(cd),
            jnp.exp(gc[:, :, -1]))


def _between_chunks(m, wv, wk, qg, kg, total):
    """The chunks one after another, all heads at once: (n, c, L, d_v)
    float32.  The state (n, d_k, d_v) is float32; the products read it in
    the compute dtype."""
    f32 = jnp.float32
    cd = m.dtype

    def chunk(s, inp):
        m_c, wv_c, wk_c, qg_c, kg_c, total_c = inp
        sc = s.astype(cd)
        u = wv_c - jnp.einsum("nlk,nkv->nlv", wk_c, sc,
                              preferred_element_type=f32)
        uc = u.astype(cd)
        o = jnp.einsum("nlk,nkv->nlv", qg_c, sc, preferred_element_type=f32) \
            + jnp.einsum("nls,nsv->nlv", m_c, uc, preferred_element_type=f32)
        s = total_c[..., None] * s + jnp.einsum(
            "nlk,nlv->nkv", kg_c, uc, preferred_element_type=f32)
        return s, o
    n, _, _, dk = qg.shape
    s0 = jnp.zeros((n, dk, wv.shape[-1]), f32)
    _, o = jax.lax.scan(chunk, s0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (m, wv, wk, qg, kg, total)))
    return jnp.moveaxis(o, 0, 1)


def kda_chunked(q, k, v, g, beta, chunk=64):
    """The chunked rule.  q, k (B, T, H, d_k) as the rule takes them (after
    their norms, q scaled) and v (B, T, H, d_v), in the compute dtype; g
    (B, T, H, d_k) float32, the log of the decay, at or below 0; beta
    (B, T, H) float32.  Returns o (B, T, H, d_v) float32.  T is padded to
    whole chunks with k = 0, beta = 0 and g = 0, which leave the state as
    it is."""
    bsz, t, h, dk = k.shape
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    nc = (t + pad) // chunk
    heads = bsz * h
    per_head = nc * chunk * _SUB * dk * 4
    group = max(d for d in range(1, heads + 1)
                if heads % d == 0 and d * per_head <= max(_GROUP_BYTES,
                                                          per_head))

    def grouped(x):     # (B, T, H, ...) -> (heads / group, group, c, L, ...)
        x = jnp.moveaxis(x.reshape((bsz, nc, chunk, h) + x.shape[3:]), 3, 1)
        return x.reshape((heads // group, group) + x.shape[2:])
    parts = jax.lax.map(
        jax.checkpoint(lambda args: _inside_chunks(*args)),
        tuple(grouped(x) for x in (q, k, v, g, beta)))
    o = _between_chunks(*(x.reshape((heads,) + x.shape[2:]) for x in parts))
    o = jnp.moveaxis(o.reshape(bsz, h, nc, chunk, -1), 1, 3)
    return o.reshape(bsz, nc * chunk, h, -1)[:, :t]


def kda_gates(gate, beta, a_log, dt_bias, h):
    """(log-decay (B, T, H, d_k), beta (B, T, H)), float32: ``-exp(A_log)
    softplus(gate + dt_bias)`` with one ``A_log`` a head and one
    ``dt_bias`` a channel, and ``sigmoid(beta)``."""
    f32 = jnp.float32
    bsz, t, _ = gate.shape
    step = jax.nn.softplus(gate.astype(f32) + dt_bias.astype(f32))
    g = step.reshape(bsz, t, h, -1) * -jnp.exp(a_log.astype(f32))[:, None]
    return g, jax.nn.sigmoid(beta.astype(f32))


def _rule_inputs(q, k, gate, beta, a_log, dt_bias, h):
    """What the rule takes, from the op's inputs: q and k (B, T, H, d_k)
    after their l2 norms, q scaled, in their dtypes; the float32 log-decay
    (B, T, H, d_k) and beta (B, T, H)."""
    bsz, t, _ = q.shape
    dk = q.shape[2] // h
    qh = (_l2norm(q.reshape(bsz, t, h, dk)) * dk ** -0.5).astype(q.dtype)
    kh = _l2norm(k.reshape(bsz, t, h, dk)).astype(k.dtype)
    return (qh, kh) + kda_gates(gate, beta, a_log, dt_bias, h)


def _scan(q, k, v, gate, beta, a_log, dt_bias, h, chunk):
    bsz, t, _ = q.shape
    qh, kh, g, b = _rule_inputs(q, k, gate, beta, a_log, dt_bias, h)
    o = kda_chunked(qh, kh, v.reshape(bsz, t, h, -1), g, b, chunk)
    return o.astype(v.dtype).reshape(v.shape)


def _kernel_inputs(q, k, v, gate, beta, a_log, dt_bias, h, chunk):
    """``_rule_inputs`` as the kernels take them, (B, T', H d) with T' whole
    chunks: the rows added have k = 0, beta = 0 and a log-decay of 0, which
    leave the state as it is."""
    bsz, t, _ = q.shape
    qh, kh, g, b = _rule_inputs(q, k, gate, beta, a_log, dt_bias, h)
    flat = (qh.reshape(q.shape), kh.reshape(k.shape), v,
            g.reshape(q.shape), b)
    return tuple(jnp.pad(x, ((0, 0), (0, -t % chunk), (0, 0))) for x in flat)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _scan_kernels(q, k, v, gate, beta, a_log, dt_bias, h, chunk,
                  interpret=False):
    """``_scan`` through the Pallas kernels (``pallas_kernels.kda_scan_*``),
    the backward written by hand: the residuals are the seven inputs."""
    o = pallas_kernels.kda_scan_fwd(
        *_kernel_inputs(q, k, v, gate, beta, a_log, dt_bias, h, chunk), h,
        chunk, interpret)
    return o[:, :v.shape[1]]


def _scan_kernels_fwd(q, k, v, gate, beta, a_log, dt_bias, h, chunk,
                      interpret):
    return _scan_kernels(q, k, v, gate, beta, a_log, dt_bias, h, chunk,
                         interpret), (q, k, v, gate, beta, a_log, dt_bias)


def _scan_kernels_bwd(h, chunk, interpret, res, do):
    t = do.shape[1]
    inputs, outside = jax.vjp(
        functools.partial(_kernel_inputs, h=h, chunk=chunk), *res)
    # the kernels' inputs wait for o's cotangent: else XLA may form every
    # layer's states (160 MiB each) as soon as the forward ends and keep them
    # all until their layer's backward.  After the norms and gates, which XLA
    # then takes from the forward and does not form again (PERF.md 6, PR 38)
    inputs, do = jax.lax.optimization_barrier((inputs, do))
    do = jnp.pad(do, ((0, 0), (0, -t % chunk), (0, 0)))
    return outside(pallas_kernels.kda_scan_bwd(*inputs, do, h, chunk,
                                               interpret))


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def _infer(attrs, in_shapes):
    h = int(attrs.get("num_heads"))
    ins = list(in_shapes)
    q, v = ins[0], ins[2]
    if q is not None:
        ins[1] = ins[3] = q
        ins[4] = tuple(q[:-1]) + (h,)
        ins[6] = (q[-1],)
    ins[5] = (h,)
    return ins, [v], None


@register("kda_scan",
          arg_names=("query", "key", "value", "gate", "beta", "a_log",
                     "dt_bias"),
          attr_types={"num_heads": parse_int, "chunk_size": parse_int},
          defaults={"chunk_size": 64}, infer_shape=_infer,
          f32_inputs=("a_log", "dt_bias"))
def _kda_scan(query, key, value, gate, beta, a_log, dt_bias, num_heads=None,
              chunk_size=64):
    """Kimi Delta Attention's rule over a sequence.  query, key and gate
    (B, T, H d_k), value (B, T, H d_v): q, k and v as their convolutions and
    SiLU leave them, the gate raw; beta (B, T, H) logits; a_log (H,) and
    dt_bias (H d_k,), float32 leaves.  Per head: ``q <- l2norm(q) /
    sqrt(d_k)``, ``k <- l2norm(k)`` (eps 1e-6 under the root), the decay
    ``exp(-exp(a_log) softplus(gate + dt_bias))`` for every key channel,
    the step ``sigmoid(beta)``, then the gated delta rule from a zero state.
    Returns o (B, T, H d_v) in value's dtype; of the forward only the inputs
    are kept.  ``chunk_size`` is a whole number of 16-row sub-blocks.  On a
    TPU, for the shapes ``kda_available`` takes, the Pallas kernels; else
    the plain form."""
    h, chunk = int(num_heads), int(chunk_size)
    if chunk % _SUB:
        raise ValueError("kda_scan: chunk_size %d is no multiple of %d"
                         % (chunk, _SUB))
    d_k, d_v = query.shape[2] // h, value.shape[2] // h
    if jax.default_backend() == "tpu" and pallas_kernels.kda_available(
            query.shape[1], h, d_k, d_v, chunk, value.dtype.itemsize):
        return _scan_kernels(query, key, value, gate, beta, a_log, dt_bias,
                             h, chunk)
    core = jax.checkpoint(functools.partial(_scan, h=h, chunk=chunk))
    return core(query, key, value, gate, beta, a_log, dt_bias)
