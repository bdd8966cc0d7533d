"""State-space mixer operators (NEW capability, no reference analogue): the
causal depthwise convolution over time and the selective state-space scan
of Mamba-2 (Dao & Gu 2024, "Transformers are SSMs"), per head with a state
``S`` of (P, N):

    S_t = exp(A dt_t) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

``ssm_scan`` computes it in the chunked form: inside a chunk of L steps the
masked, decay-weighted product ``(C B^T * decay * dt) x`` (matrix products of
(L, L) blocks, MXU work); between chunks the carried state, a short
sequential pass over T / L states.  One algorithm, two forms of it, chosen
from what the op observes (the backend and the shape, never an option):

- On a TPU, for the shapes ``pallas_kernels.ssd_available`` takes (T a
  multiple of the chunk, chunks of whole 128-lane tiles, P and N
  lane-friendly): the kernels ``mxtpu_ssd_fwd`` / ``_states`` / ``_bwd``.
  They read x, B and C as column blocks of ``data`` where it lies; the
  (L, L) blocks and the carried (P, N) state stay in VMEM; y leaves once, in
  data's dtype, the skip ``D x`` added in the epilogue.  The backward is
  written by hand under one ``jax.custom_vjp`` whose residuals are the op's
  five inputs: the states entering each chunk are formed again by a
  states-only pass into one (T / L, H P, N) float32 array (64 MiB at T =
  4096 with 64 heads, alive during that layer's backward alone), then the
  chunks are taken last to first with the state's gradient carried in VMEM.
  The step ``softplus(dt + dt_bias)`` and the running sum of ``A dt``
  inside each chunk are made outside, on their (T, H) arrays, and autodiff
  carries the kernels' gradients through them to dt, ``A_log`` and
  ``dt_bias``.
- Everywhere else: ``ssm_scan_chunked``, plain ``jax.numpy``, which is also
  the tests' oracle.  Its backward is autodiff under ``jax.checkpoint``:
  only the op's inputs are kept from the forward and the (L, L) blocks are
  formed again, group by group (each (heads, T / L, L, L) float32 array is
  128 MiB at T = 4096 with 64 heads, and the backward holds half a dozen).

Precision follows the published kernels, in both forms alike: ``dt``, ``A``,
the decays ``exp(A dt)``, the running sums and the carried state are float32
whatever the input's dtype; the matrix products take operands in the
input's dtype, cast where the plain form casts them, and accumulate in
float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import pallas_kernels
from .nn import ACTIVATIONS
from .registry import register, parse_bool, parse_int, parse_str


# ------------------------------------------------------- causal convolution
def _conv_args(attrs):
    return ["data", "weight"] if attrs.get("no_bias", False) else \
        ["data", "weight", "bias"]


def _conv_infer(attrs, in_shapes):
    data = in_shapes[0]
    ins = list(in_shapes)
    if data is not None:
        ins[1] = (data[-1], int(attrs.get("kernel")))
        ins[2:] = [(data[-1],)] * (len(ins) - 2)
    return ins, [data], None


def _conv_pre(data, weight, bias):
    """The K shifted multiply-adds and the bias, float32: y before its
    activation."""
    k, t = weight.shape[1], data.shape[1]
    padded = jnp.pad(data, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    w = weight.astype(jnp.float32)
    y = sum(padded[:, j:j + t, :] * w[:, j] for j in range(k))
    return y if bias is None else y + bias.astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def causal_conv(data, weight, bias, act):
    """``_causal_conv1d`` with ``act`` an elementwise function or None."""
    y = _conv_pre(data, weight, bias)
    return (act(y) if act else y).astype(data.dtype)


def _conv_fwd(data, weight, bias, act):
    return causal_conv(data, weight, bias, act), (data, weight, bias)


def conv_bwd_plain(data, weight, bias, dy, act=None):
    """The backward in ``jax.numpy``, off the TPU and for the shapes
    ``conv_blocks`` refuses; the kernel's oracle.  ``g = dy
    act'(y_pre)`` with y_pre formed again from the input, ``dx[t] = sum_j
    w_j g[t + K - 1 - j]``, ``dw_j = sum_t x[t - K + 1 + j] g[t]``, ``db =
    sum_t g[t]``, all float32, each returned in its primal's dtype."""
    f32 = jnp.float32
    k, t = weight.shape[1], data.shape[1]
    g = dy.astype(f32)
    if act:
        g = jax.vjp(act, _conv_pre(data, weight, bias))[1](g)[0]
    w = weight.astype(f32)
    ahead = jnp.pad(g, ((0, 0), (0, k - 1), (0, 0)))
    dx = sum(ahead[:, k - 1 - j:k - 1 - j + t] * w[:, j] for j in range(k))
    padded = jnp.pad(data, ((0, 0), (k - 1, 0), (0, 0))).astype(f32)
    dw = jnp.stack([(padded[:, j:j + t] * g).sum((0, 1)) for j in range(k)],
                   axis=1)
    return dx.astype(data.dtype), dw.astype(weight.dtype), (
        None if bias is None else g.sum((0, 1)).astype(bias.dtype))


def _conv_bwd(act, res, dy):
    data, weight, bias = res
    _, t, c = data.shape
    if jax.default_backend() == "tpu" and pallas_kernels.conv_blocks(
            t, c, weight.shape[1], data.dtype.itemsize) is not None:
        return pallas_kernels.causal_conv_bwd(data, weight, bias, dy, act)
    return conv_bwd_plain(data, weight, bias, dy, act)


causal_conv.defvjp(_conv_fwd, _conv_bwd)


@register("causal_conv1d", arg_names=_conv_args,
          attr_types={"kernel": parse_int, "act_type": parse_str,
                      "no_bias": parse_bool},
          defaults={"act_type": None, "no_bias": False},
          infer_shape=_conv_infer)
def _causal_conv1d(data, weight, bias=None, kernel=None, act_type=None,
                   no_bias=False):
    """Depthwise causal convolution over time: data (B, T, C), weight
    (C, K), ``y[t] = sum_j weight[:, j] x[t - (K - 1) + j] + bias`` with
    zeros before t = 0 (torch ``Conv1d(groups=C, padding=K - 1)`` cut to T),
    then ``act_type`` (an ``Activation`` type) if given; ``no_bias`` leaves
    the bias and its input out.  K shifted multiply-adds, accumulated in
    float32.  The backward is written by hand (``jax.custom_vjp``) and its
    residuals are the inputs alone: y before the activation is formed
    again.  On a TPU, for the shapes ``conv_blocks`` tiles, it is one
    pass of the kernel ``mxtpu_conv_bwd`` over the rows, ``dy act'`` kept
    in VMEM; else ``conv_bwd_plain``."""
    del no_bias                     # told by the third input's absence
    return causal_conv(data, weight, bias,
                       ACTIVATIONS[act_type] if act_type else None)


# ------------------------------------------------------------------ the scan
def _group_scan(x, dt, a, b, c):
    """One group's heads.  x (B, c, L, R, P) in the compute dtype, dt
    (B, c, L, R) float32, a (R,) float32, b and c (B, c, L, N): y
    (B, c, L, R, P) float32."""
    f32 = jnp.float32
    cd, l = x.dtype, x.shape[2]
    # per-step log-decay and its running sum inside each chunk: (B,c,R,L)
    dtc = dt.transpose(0, 1, 3, 2)
    acs = jnp.cumsum(dtc * a[None, None, :, None], axis=-1)
    # inside a chunk: y_l = sum_{s <= l} (C_l . B_s) exp(acs_l - acs_s) dt_s x_s
    cb = jnp.einsum("bcln,bcsn->bcls", c, b, preferred_element_type=f32)
    seg = acs[..., :, None] - acs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    decay = jnp.exp(jnp.where(mask, seg, -jnp.inf))
    m = (cb[:, :, None] * decay * dtc[..., None, :]).astype(cd)
    y = jnp.einsum("bcrls,bcsrp->bclrp", m, x, preferred_element_type=f32)
    # what each chunk adds to the state by its end: (B,c,R,P,N)
    to_end = jnp.exp(acs[..., -1:] - acs) * dtc
    xw = (x.astype(f32) * to_end.transpose(0, 1, 3, 2)[..., None]).astype(cd)
    states = jnp.einsum("bcsrp,bcsn->bcrpn", xw, b,
                        preferred_element_type=f32)
    # between chunks: the state carried into each chunk, float32
    total = jnp.exp(acs[..., -1])                        # (B,c,R)

    def carry(s, inp):
        decay_c, add = inp
        return decay_c[..., None, None] * s + add, s
    _, before = jax.lax.scan(
        carry, jnp.zeros(states.shape[:1] + states.shape[2:], f32),
        (jnp.moveaxis(total, 1, 0), jnp.moveaxis(states, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                  # (B,c,R,P,N)
    y_prev = jnp.einsum("bcln,bcrpn->bclrp", c, before.astype(cd),
                        preferred_element_type=f32)
    return y + y_prev * jnp.exp(acs).transpose(0, 1, 3, 2)[..., None]


def ssm_scan_chunked(x, dt, a, b, c, chunk):
    """The chunked scan without the skip term.  x (B, T, H, P) in the
    compute dtype; dt (B, T, H) float32, after its softplus; a (H,) float32,
    negative; b, c (B, T, G, N) with head i reading group i // (H / G).
    Returns y (B, T, H, P) float32.  T is padded to a multiple of ``chunk``
    with dt = 0, which carries the state unchanged and adds nothing.  The
    groups are worked one after another, each under ``jax.checkpoint``, so
    that one group's (L, L) blocks are alive at a time."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // chunk

    def by_group(v, *tail):          # (B, T, g, ...) -> (g, B, c, L, ...)
        return jnp.moveaxis(v.reshape((bsz, nc, chunk, g) + tail), 3, 0)
    y = jax.lax.map(
        jax.checkpoint(lambda args: _group_scan(*args)),
        (by_group(x, r, p), by_group(dt, r), a.reshape(g, r),
         by_group(b, n), by_group(c, n)))                # (g, B, c, L, R, P)
    return jnp.moveaxis(y, 0, 3).reshape(bsz, nc * chunk, h, p)[:, :t]


def _scan_infer(attrs, in_shapes):
    h = int(attrs.get("num_heads"))
    ins = list(in_shapes)
    for i in (2, 3, 4):
        ins[i] = (h,)
    data = ins[0]
    out = None if data is None else \
        tuple(data[:-1]) + (h * int(attrs.get("head_dim")),)
    return ins, [out], None


def _scan(xbc, dt, a_log, d, dt_bias, h, p, g, chunk):
    f32 = jnp.float32
    bsz, t, _ = xbc.shape
    inner = h * p
    n = (xbc.shape[2] - inner) // (2 * g)
    x = xbc[..., :inner].reshape(bsz, t, h, p)
    b = xbc[..., inner:inner + g * n].reshape(bsz, t, g, n)
    c = xbc[..., inner + g * n:].reshape(bsz, t, g, n)
    step = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    y = ssm_scan_chunked(x, step, -jnp.exp(a_log.astype(f32)), b, c, chunk)
    y = y + d.astype(f32)[:, None] * x.astype(f32)
    return y.astype(xbc.dtype).reshape(bsz, t, inner)


def _steps(dt, a_log, dt_bias, chunk):
    """What the kernels take of dt and A, float32 (B, T, H): the step
    ``softplus(dt + dt_bias)`` and the running sum of ``A dt`` inside each
    chunk.  T is a multiple of ``chunk``."""
    f32 = jnp.float32
    step = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    bsz, t, h = step.shape
    acs = jnp.cumsum((step * -jnp.exp(a_log.astype(f32))).reshape(
        bsz, t // chunk, chunk, h), axis=2)
    return step, acs.reshape(bsz, t, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _scan_kernels(xbc, dt, a_log, d, dt_bias, h, p, g, chunk,
                  interpret=False):
    """``_scan`` through the Pallas kernels (``pallas_kernels.ssd_scan_*``),
    the backward written by hand: the residuals are the five inputs."""
    return pallas_kernels.ssd_scan_fwd(
        xbc, *_steps(dt, a_log, dt_bias, chunk), d, h, p, g, chunk,
        interpret)


def _scan_kernels_fwd(xbc, dt, a_log, d, dt_bias, h, p, g, chunk, interpret):
    return _scan_kernels(xbc, dt, a_log, d, dt_bias, h, p, g, chunk,
                         interpret), (xbc, dt, a_log, d, dt_bias)


def _scan_kernels_bwd(h, p, g, chunk, interpret, res, dy):
    xbc, dt, a_log, d, dt_bias = res
    (step, acs), small = jax.vjp(functools.partial(_steps, chunk=chunk),
                                 dt, a_log, dt_bias)
    dxbc, dstep, dacs, dd = pallas_kernels.ssd_scan_bwd(
        xbc, step, acs, d, dy, h, p, g, chunk, interpret)
    ddt, da_log, dbias = small((dstep, dacs))
    return dxbc, ddt, da_log, dd.astype(d.dtype), dbias


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


@register("ssm_scan", arg_names=("data", "dt", "a_log", "d", "dt_bias"),
          attr_types={"num_heads": parse_int, "head_dim": parse_int,
                      "num_groups": parse_int, "chunk_size": parse_int},
          defaults={"num_groups": 1, "chunk_size": 128},
          infer_shape=_scan_infer, f32_inputs=("a_log", "d", "dt_bias"))
def _ssm_scan(data, dt, a_log, d, dt_bias, num_heads=None, head_dim=None,
              num_groups=1, chunk_size=128):
    """Selective state-space scan.  data (B, T, H*P + 2*G*N) holds x, B and
    C side by side, as the convolution leaves them; dt (B, T, H); a_log, d,
    dt_bias (H,), float32 leaves.  The step is ``softplus(dt + dt_bias)``
    (always above 0, so a time-step limit of (0, inf) clamps nothing), the
    decay ``exp(-exp(a_log) dt)``, and ``d`` scales the skip ``D x``.
    Returns y (B, T, H*P) in data's dtype; of the forward only the inputs
    are kept.  On a TPU, for the shapes ``ssd_available`` takes, the Pallas
    kernels; else the plain form."""
    h, p, g, chunk = (int(v) for v in (num_heads, head_dim, num_groups,
                                       chunk_size))
    n = (data.shape[2] - h * p) // (2 * g)
    if jax.default_backend() == "tpu" and pallas_kernels.ssd_available(
            data.shape[1], h, p, g, n, chunk, data.dtype.itemsize):
        return _scan_kernels(data, dt, a_log, d, dt_bias, h, p, g, chunk)
    core = jax.checkpoint(functools.partial(_scan, h=h, p=p, g=g,
                                            chunk=chunk))
    return core(data, dt, a_log, d, dt_bias)


# ------------------------------------------------------- the gated group norm
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gated_group_norm(data, gamma, gate, eps, groups):
    """``RMSNorm(data, gamma, gate, gated=True, num_groups=groups)`` as the
    kernels ``mxtpu_gnorm_fwd`` / ``_bwd``, one pass over the rows each way;
    the residuals are the op's inputs alone.  ``RMSNorm`` takes it on a TPU
    for the shapes ``gnorm_available`` takes; its own ``jax.numpy`` form is
    the oracle."""
    return pallas_kernels.gnorm_fwd(data, gate, gamma, groups, eps)


def _gnorm_fwd(data, gamma, gate, eps, groups):
    return gated_group_norm(data, gamma, gate, eps, groups), (data, gamma,
                                                              gate)


def _gnorm_bwd(eps, groups, res, dout):
    data, gamma, gate = res
    dx, dz, dgamma = pallas_kernels.gnorm_bwd(data, gate, gamma, dout,
                                              groups, eps)
    return dx, dgamma, dz


gated_group_norm.defvjp(_gnorm_fwd, _gnorm_bwd)
