"""Neural-network layers (parity: reference src/operator/{fully_connected,
convolution,pooling,activation,batch_norm,dropout,leaky_relu,lrn,l2_normalization,
instance_norm,deconvolution,upsampling}-inl.h and their cuDNN twins).

TPU-first notes:
- Convolutions lower to ``lax.conv_general_dilated`` — XLA tiles them onto the MXU
  and picks TPU-friendly layouts itself; there is no im2col/cuDNN-algo machinery.
- BatchNorm/activations are jnp expressions that XLA fuses into neighbouring convs
  (replacing the hand-fused cuDNN/MKL paths).
- All layers are rank-polymorphic over 1D/2D/3D spatial dims where MXNet's are.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError
from .registry import (register, parse_bool, parse_float, parse_int, parse_str,
                       parse_tuple)


# --------------------------------------------------------------- FullyConnected
def _fc_args(attrs):
    return ["data", "weight"] if attrs.get("no_bias", False) else \
        ["data", "weight", "bias"]


def _fc_infer(attrs, in_shapes):
    from .registry import shape_is_complete
    nh = int(attrs.get("num_hidden"))
    data = in_shapes[0]
    ins = list(in_shapes)
    if data is not None and shape_is_complete(data[1:]):
        flat = int(_np.prod(data[1:]))
        ins[1] = (nh, flat)
    if len(ins) > 2:
        ins[2] = (nh,)
    out = None if data is None else (data[0], nh)
    return ins, [out], None


def _fc_infer_backward(attrs, out_shapes, in_shapes):
    """Deduce a 2-D data shape from output + weight (nnvm InferShape backward
    half — resolves RNN begin-state batch dims through shared h2h weights)."""
    out = out_shapes[0]
    weight = in_shapes[1] if len(in_shapes) > 1 else None
    ins = [None] * len(in_shapes)
    if out is None:
        return ins
    data = in_shapes[0]
    if weight is not None and (data is None or
                               (len(data) == 2 and 0 in data)):
        ins[0] = (out[0], weight[1])
    elif data is not None and data[0] == 0 and out[0] != 0:
        ins[0] = (out[0],) + tuple(data[1:])
    return ins


@register("FullyConnected", arg_names=_fc_args,
          attr_types={"num_hidden": parse_int, "no_bias": parse_bool},
          defaults={"no_bias": False},
          infer_shape=_fc_infer, infer_shape_backward=_fc_infer_backward)
def _fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False):
    """y = x·Wᵀ + b (parity: fully_connected-inl.h; MXU matmul)."""
    x = data.reshape((data.shape[0], -1))
    y = jnp.dot(x, weight.T)
    if bias is not None:
        y = y + bias
    return y


# ------------------------------------------------------------------ Activation
# act_type -> function; ``silu`` and ``relu2`` (the squared ReLU) are what
# state-space mixers and recent expert layers use
ACTIVATIONS = {
    "relu": lambda x: jnp.maximum(x, 0),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "silu": jax.nn.silu,
    "relu2": lambda x: jnp.square(jnp.maximum(x, 0)),
}


@register("Activation", attr_types={"act_type": parse_str},
          defaults={"act_type": "relu"})
def _activation(data, act_type="relu"):
    if act_type not in ACTIVATIONS:
        raise MXNetError("unknown act_type %s" % act_type)
    return ACTIVATIONS[act_type](data)


def _lrelu_args(attrs):
    return ["data", "gamma"] if attrs.get("act_type", "leaky") == "prelu" else ["data"]


def _lrelu_infer(attrs, in_shapes):
    ins = list(in_shapes)
    if len(ins) > 1 and ins[0] is not None:
        ins[1] = (ins[0][1],)
    return ins, [ins[0]], None


@register("LeakyReLU", arg_names=_lrelu_args,
          attr_types={"act_type": parse_str, "slope": parse_float,
                      "lower_bound": parse_float, "upper_bound": parse_float},
          defaults={"act_type": "leaky", "slope": 0.25, "lower_bound": 0.125,
                    "upper_bound": 0.334},
          input_init_attrs={"gamma": '["Constant", {"value": 0.25}]'},
          infer_shape=_lrelu_infer, needs_rng=True, train_aware=True)
def _leaky_relu(data, gamma=None, rng=None, is_train=False, act_type="leaky",
                slope=0.25, lower_bound=0.125, upper_bound=0.334):
    """(parity: leaky_relu-inl.h; leaky/prelu/elu/rrelu)"""
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * (jnp.exp(data) - 1.0))
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data > 0, data, g * data)
    if act_type == "rrelu":
        if is_train:
            s = jax.random.uniform(rng, data.shape, data.dtype,
                                   lower_bound, upper_bound)
        else:
            s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, s * data)
    raise MXNetError("unknown act_type %s" % act_type)


# ----------------------------------------------------------------- Convolution
def _conv_args(attrs):
    return ["data", "weight"] if attrs.get("no_bias", False) else \
        ["data", "weight", "bias"]


def _conv_out_dim(i, k, s, p, d):
    return (i + 2 * p - (d * (k - 1) + 1)) // s + 1


def _tup(v, n, default):
    v = tuple(v) if v else ()
    return v + (default,) * (n - len(v))


def _conv_infer(attrs, in_shapes):
    data = in_shapes[0]
    nf = int(attrs.get("num_filter"))
    ng = int(attrs.get("num_group", 1))
    kernel = parse_tuple(attrs.get("kernel"))
    nd = len(kernel)
    stride = _tup(parse_tuple(attrs.get("stride", ())), nd, 1)
    pad = _tup(parse_tuple(attrs.get("pad", ())), nd, 0)
    dilate = _tup(parse_tuple(attrs.get("dilate", ())), nd, 1)
    ins = list(in_shapes)
    out = None
    if data is not None:
        ins[1] = (nf, data[1] // ng) + kernel
        spatial = tuple(_conv_out_dim(i, k, s, p, d) for i, k, s, p, d
                        in zip(data[2:], kernel, stride, pad, dilate))
        out = (data[0], nf) + spatial
    if len(ins) > 2:
        ins[2] = (nf,)
    return ins, [out], None


_CONV_ATTRS = {"kernel": parse_tuple, "stride": parse_tuple, "dilate": parse_tuple,
               "pad": parse_tuple, "num_filter": parse_int, "num_group": parse_int,
               "workspace": parse_int, "no_bias": parse_bool,
               "cudnn_tune": parse_str, "cudnn_off": parse_bool, "layout": parse_str}


@register("Convolution", arg_names=_conv_args,
          attr_types=_CONV_ATTRS,
          defaults={"stride": (), "dilate": (), "pad": (), "num_group": 1,
                    "no_bias": False},
          infer_shape=_conv_infer, layout_rule="aware")
def _convolution(data, weight, bias=None, kernel=None, stride=(), dilate=(),
                 pad=(), num_filter=None, num_group=1, workspace=None,
                 no_bias=False, cudnn_tune=None, cudnn_off=False, layout=None):
    """N-D convolution (parity: convolution-inl.h / cudnn_convolution-inl.h).

    Lowered to one XLA conv HLO; `workspace`/`cudnn_*` accepted for API parity
    and ignored (XLA owns algorithm choice on TPU).  With layout='NHWC'
    (injected by the executor's layout pass) ``data`` arrives channel-last —
    the layout the TPU prefers end-to-end; the weight keeps its logical
    (O, I, *k) shape and is transposed here (cheap: weights are small next to
    activations, and XLA folds the transpose into its weight prefetch)."""
    nd = len(kernel)
    stride = _tup(stride, nd, 1)
    dilate = _tup(dilate, nd, 1)
    pad = _tup(pad, nd, 0)
    spatial = "DHW"[-nd:] if nd <= 3 else None
    if spatial is None:
        raise MXNetError("Convolution supports 1-3 spatial dims")
    if layout == "NHWC":
        dn = ("N" + spatial + "C", spatial + "IO", "N" + spatial + "C")
        weight = jnp.transpose(weight, tuple(range(2, 2 + nd)) + (1, 0))
    else:
        dn = ("NC" + spatial, "OI" + spatial, "NC" + spatial)
    out = jax.lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group)
    if bias is not None:
        cshape = ((1,) + (1,) * nd + (-1,)) if layout == "NHWC" \
            else ((1, -1) + (1,) * nd)
        out = out + bias.reshape(cshape)
    return out


@register("Deconvolution", arg_names=_conv_args,
          attr_types=dict(_CONV_ATTRS, adj=parse_tuple, target_shape=parse_tuple),
          defaults={"stride": (), "dilate": (), "pad": (), "adj": (),
                    "num_group": 1, "no_bias": True},
          infer_shape=lambda attrs, ins: _deconv_infer(attrs, ins))
def _deconvolution(data, weight, bias=None, kernel=None, stride=(), dilate=(),
                   pad=(), adj=(), target_shape=None, num_filter=None,
                   num_group=1, workspace=None, no_bias=True, cudnn_tune=None,
                   cudnn_off=False, layout=None):
    """Transposed convolution (parity: deconvolution-inl.h).

    Implemented as an input-dilated conv with a spatially flipped kernel —
    the exact adjoint of `Convolution`, which XLA recognises and maps to MXU."""
    nd = len(kernel)
    stride = _tup(stride, nd, 1)
    dilate_ = _tup(dilate, nd, 1)
    pad_ = _tup(pad, nd, 0)
    adj_ = _tup(adj, nd, 0)
    # dilated ("effective") kernel extents drive all padding math
    # (reference deconvolution-inl.h DilatedKernelSize)
    keff = tuple((k - 1) * d + 1 for k, d in zip(kernel, dilate_))
    if target_shape:
        if len(target_shape) != nd:
            raise MXNetError("Deconvolution target_shape %s must have %d "
                             "spatial dims" % (target_shape, nd))
        # derive pad/adj so the output comes out exactly target-sized:
        # o_pad = ceil(total/2), o_adj = total % 2 (reference
        # deconvolution-inl.h InferPad — floor would shift content a pixel)
        in_sp = data.shape[2:] if layout != "NHWC" else data.shape[1:-1]
        totals = tuple((i - 1) * s + k - t
                       for i, k, s, t in zip(in_sp, keff, stride,
                                             target_shape))
        if any(t < 0 for t in totals):
            raise MXNetError(
                "Deconvolution target_shape %s is larger than the maximal "
                "output for input %s" % (target_shape, tuple(in_sp)))
        pad_ = tuple((t + 1) // 2 for t in totals)
        adj_ = tuple(t % 2 for t in totals)
    # weight layout in MXNet deconv: (in_ch, out_ch/group, *kernel)
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    if num_group > 1:
        cin = data.shape[1]
        w = w.reshape((num_group, cin // num_group) + w.shape[1:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((-1, cin // num_group) + kernel)  # (out, in/g, *k)
    else:
        w = jnp.swapaxes(w, 0, 1)
    spatial = "DHW"[-nd:]
    dn = ("NC" + spatial, "OI" + spatial, "NC" + spatial)
    padding = [(k - 1 - p, k - 1 - p + a)
               for k, p, a in zip(keff, pad_, adj_)]
    out = jax.lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate_, dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


def _deconv_infer(attrs, in_shapes):
    data = in_shapes[0]
    nf = int(attrs.get("num_filter"))
    ng = int(attrs.get("num_group", 1))
    kernel = parse_tuple(attrs.get("kernel"))
    nd = len(kernel)
    stride = _tup(parse_tuple(attrs.get("stride", ())), nd, 1)
    pad = _tup(parse_tuple(attrs.get("pad", ())), nd, 0)
    adj = _tup(parse_tuple(attrs.get("adj", ())), nd, 0)
    dilate = _tup(parse_tuple(attrs.get("dilate", ())), nd, 1)
    keff = tuple((k - 1) * d + 1 for k, d in zip(kernel, dilate))
    target = parse_tuple(attrs.get("target_shape", None) or ())
    if target and len(target) != nd:
        raise MXNetError("Deconvolution target_shape %s must have %d "
                         "spatial dims" % (target, nd))
    ins = list(in_shapes)
    out = None
    if data is not None:
        ins[1] = (data[1], nf // ng) + kernel
        if target:
            # target_shape pins the output size; pad is derived from it
            # (reference deconvolution-inl.h InferShape target_shape branch)
            if any((i - 1) * s + k - t < 0 for i, k, s, t
                   in zip(data[2:], keff, stride, target)):
                raise MXNetError(
                    "Deconvolution target_shape %s is larger than the "
                    "maximal output for input %s" % (target, data[2:]))
            spatial = tuple(target)
        else:
            spatial = tuple((i - 1) * s - 2 * p + k + a for i, k, s, p, a
                            in zip(data[2:], keff, stride, pad, adj))
        out = (data[0], nf) + spatial
    if len(ins) > 2:
        ins[2] = (nf,)
    return ins, [out], None


# --------------------------------------------------------------------- Pooling
def _pool_out_dim(i, k, s, p, convention):
    if convention == "full":
        return int(_np.ceil(float(i + 2 * p - k) / s)) + 1
    return (i + 2 * p - k) // s + 1


def _pool_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], None
    if attrs.get("global_pool", False):
        return in_shapes, [data[:2] + (1,) * (len(data) - 2)], None
    kernel = parse_tuple(attrs.get("kernel"))
    nd = len(kernel)
    stride = _tup(parse_tuple(attrs.get("stride", ())), nd, 1)
    pad = _tup(parse_tuple(attrs.get("pad", ())), nd, 0)
    conv = attrs.get("pooling_convention", "valid")
    spatial = tuple(_pool_out_dim(i, k, s, p, conv)
                    for i, k, s, p in zip(data[2:], kernel, stride, pad))
    return in_shapes, [data[:2] + spatial], None


@register("Pooling", aliases=("Pooling_v1",),
          attr_types={"kernel": parse_tuple, "stride": parse_tuple,
                      "pad": parse_tuple, "pool_type": parse_str,
                      "global_pool": parse_bool, "pooling_convention": parse_str,
                      "layout": parse_str},
          defaults={"stride": (), "pad": (), "pool_type": "max",
                    "global_pool": False, "pooling_convention": "valid"},
          infer_shape=_pool_infer, layout_rule="aware")
def _pooling(data, kernel=None, stride=(), pad=(), pool_type="max",
             global_pool=False, pooling_convention="valid", layout=None):
    """N-D pooling via XLA reduce_window (parity: pooling-inl.h / pool.h)."""
    nd = data.ndim - 2
    sp_axes = tuple(range(1, 1 + nd)) if layout == "NHWC" \
        else tuple(range(2, 2 + nd))
    sp_shape = tuple(data.shape[a] for a in sp_axes)
    if global_pool:
        kernel = sp_shape
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = tuple(kernel)
        stride = _tup(stride, nd, 1)
        pad = _tup(pad, nd, 0)
    # padding, possibly asymmetric for 'full' convention
    pads = []
    for i, k, s, p in zip(sp_shape, kernel, stride, pad):
        out = _pool_out_dim(i, k, s, p, pooling_convention if not global_pool
                            else "valid")
        needed = (out - 1) * s + k - i - p
        pads.append((p, max(needed, p)))
    if layout == "NHWC":
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        padding = [(0, 0)] + pads + [(0, 0)]
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        padding = [(0, 0), (0, 0)] + pads
    if pool_type == "max":
        if not jnp.issubdtype(data.dtype, jnp.floating):
            return jax.lax.reduce_window(data, jnp.iinfo(data.dtype).min,
                                         jax.lax.max, window, strides,
                                         padding)
        return jax.lax.reduce_window(data, -jnp.inf, jax.lax.max, window,
                                     strides, padding)
    ssum = jax.lax.reduce_window(data, 0.0, jax.lax.add,
                                 window, strides, padding)
    if pool_type == "sum":
        return ssum
    if pool_type == "avg":
        # Divisor is the window extent clipped only to dim+pad, computed BEFORE
        # clipping to the valid region (count_include_pad semantics, parity:
        # pool.h:268 — pool_size = (hend-hstart)*(wend-wstart) pre-clip).
        # Static shapes → compute per-axis divisors at trace time.
        cnt = None
        out_spatial = tuple(ssum.shape[a] for a in sp_axes)
        lead = 1 if layout == "NHWC" else 2
        trail = 1 if layout == "NHWC" else 0
        for ax, (i_sz, k, s, p, o_sz) in enumerate(
                zip(sp_shape, kernel, stride, pad, out_spatial)):
            starts = _np.arange(o_sz) * s - p
            ends = _np.minimum(starts + k, i_sz + p)
            d = jnp.asarray((ends - starts).astype(_np.float32))
            d = d.reshape((1,) * lead + (1,) * ax + (o_sz,)
                          + (1,) * (len(out_spatial) - ax - 1)
                          + (1,) * trail)
            cnt = d if cnt is None else cnt * d
        return (ssum / cnt).astype(data.dtype)
    raise MXNetError("unknown pool_type %s" % pool_type)


# ------------------------------------------------------------------- BatchNorm
def _bn_axes(ndim, caxis):
    caxis = caxis % ndim
    axes = tuple(a for a in range(ndim) if a != caxis)
    cshape = tuple(-1 if a == caxis else 1 for a in range(ndim))
    return axes, cshape


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_train_core(x, g, b, eps, caxis=1):
    """Training-mode batch norm with a hand-written backward.

    Autodiff through f32 batch statistics materialises f32 activation-sized
    tensors in the backward pass — 2x the HBM traffic of bf16 on what is
    already the bandwidth-bound part of a conv net.  The custom VJP keeps
    every activation-sized tensor in x.dtype (only the per-channel reductions
    accumulate in f32), which is both faster and *more* accurate than bf16
    statistics.  Returns (out, mean, var) with mean/var in f32."""
    out, mean, var, _inv = _bn_train_fwd_impl(x, g, b, eps, caxis)
    return out, mean, var


def _bn_train_fwd_impl(x, g, b, eps, caxis):
    axes, cshape = _bn_axes(x.ndim, caxis)
    # stats accumulate in at-least-f32 (f64 inputs keep f64 — numeric-gradient
    # tests rely on it); the convert fuses into the reduces, never materialised
    acc = jnp.promote_types(x.dtype, jnp.float32)
    x32 = x.astype(acc)
    mean = jnp.mean(x32, axis=axes)
    var = jnp.mean(jnp.square(x32), axis=axes) - jnp.square(mean)
    var = jnp.maximum(var, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    scale = g.astype(acc) * inv
    shift = b.astype(acc) - mean * scale
    out = x * scale.reshape(cshape).astype(x.dtype) \
        + shift.reshape(cshape).astype(x.dtype)
    return out, mean, var, inv


def _bn_train_core_fwd(x, g, b, eps, caxis):
    out, mean, var, inv = _bn_train_fwd_impl(x, g, b, eps, caxis)
    return (out, mean, var), (x, g, mean, inv)


def _bn_train_core_bwd(eps, caxis, res, cts):
    dy, dmean_ct, dvar_ct = cts
    x, g, mean, inv = res
    return _bn_bwd_shared(caxis, x, g, mean, inv, dy, dmean_ct, dvar_ct)


def _bn_bwd_shared(caxis, x, g, mean, inv, dy, dmean_ct, dvar_ct):
    axes, cshape = _bn_axes(x.ndim, caxis)
    acc = jnp.promote_types(x.dtype, jnp.float32)
    n = 1
    for a in axes:
        n *= x.shape[a]
    n = jnp.asarray(n, acc)
    g32 = g.astype(acc)
    # per-channel f32 reductions over x.dtype elementwise products (the
    # bf16 multiply fuses into the reduce; accumulation is f32)
    sum_dy = jnp.sum(dy.astype(acc), axis=axes)
    sum_dy_x = jnp.sum((dy * x).astype(acc), axis=axes)
    sum_dy_xhat = inv * (sum_dy_x - mean * sum_dy)
    dgamma = sum_dy_xhat
    dbeta = sum_dy
    # cotangent contributions from the (rarely used) mean/var outputs fold
    # into the same per-channel affine form dx = A*dy + B*x + C
    # dL/dv = -1/2 inv^2 g sum(dy*xhat)  (inv^2, not inv^3: the reduction is
    # over dy*xhat, which already carries one factor of inv)
    dvar = -0.5 * inv ** 2 * g32 * sum_dy_xhat + dvar_ct.astype(acc)
    dmean = -inv * g32 * sum_dy + dmean_ct.astype(acc)
    coef_dy = g32 * inv
    coef_x = 2.0 * dvar / n
    coef_1 = dmean / n - coef_x * mean
    dx = dy * coef_dy.reshape(cshape).astype(x.dtype) \
        + x * coef_x.reshape(cshape).astype(x.dtype) \
        + coef_1.reshape(cshape).astype(x.dtype)
    return dx, dgamma.astype(g.dtype), dbeta.astype(g.dtype)


_bn_train_core.defvjp(_bn_train_core_fwd, _bn_train_core_bwd)


# ------------------------------------------------------- fused BatchNorm+ReLU
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_relu_train_core(x, g, b, eps, caxis=1):
    """BatchNorm(train) + ReLU in one op with a hand-written backward.

    The executor fuses BatchNorm->Activation(relu) pairs (the universal conv
    net idiom) onto this op so the backward recomputes the relu mask from the
    saved pre-BN tensor instead of keeping the BN output alive — one fewer
    activation-sized residual read per layer on the HBM-bandwidth-bound path."""
    out, mean, var, _inv = _bn_train_fwd_impl(x, g, b, eps, caxis)
    return jnp.maximum(out, 0), mean, var


def _bn_relu_train_core_fwd(x, g, b, eps, caxis):
    out, mean, var, inv = _bn_train_fwd_impl(x, g, b, eps, caxis)
    return (jnp.maximum(out, 0), mean, var), (x, g, b, mean, inv)


def _bn_relu_train_core_bwd(eps, caxis, res, cts):
    dy, dmean_ct, dvar_ct = cts
    x, g, b, mean, inv = res
    _, cshape = _bn_axes(x.ndim, caxis)
    acc = jnp.promote_types(x.dtype, jnp.float32)
    scale = g.astype(acc) * inv
    shift = b.astype(acc) - mean * scale
    # recompute the pre-activation sign from x (fused elementwise — cheaper
    # than saving the BN output): relu gate on the incoming cotangent
    pre = x * scale.reshape(cshape).astype(x.dtype) \
        + shift.reshape(cshape).astype(x.dtype)
    dy = jnp.where(pre > 0, dy, jnp.zeros((), dy.dtype))
    return _bn_bwd_shared(caxis, x, g, mean, inv, dy, dmean_ct, dvar_ct)


_bn_relu_train_core.defvjp(_bn_relu_train_core_fwd, _bn_relu_train_core_bwd)


# ------------------------------------------------- fused input-BN + stem conv
def _stem_conv(y, w, geom):
    """The stem convolution: ``y`` channel-last, ``w`` logical."""
    _, s, p = geom
    return jax.lax.conv_general_dilated(
        y, jnp.transpose(w, (2, 3, 1, 0)), window_strides=s,
        padding=[(pp, pp) for pp in p],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _ibc_fwd_impl(x, b, w, eps, geom):
    """Forward of the fused input BatchNorm(fix_gamma) + Convolution.

    ``x`` channel-last (N, H, W, C); ``w`` logical (O, C, kh, kw).
    Returns (conv_out_cl, mean, var, inv)."""
    axes, cshape = _bn_axes(x.ndim, -1)
    acc = jnp.promote_types(x.dtype, jnp.float32)
    x32 = x.astype(acc)
    mean = jnp.mean(x32, axis=axes)
    var = jnp.maximum(jnp.mean(jnp.square(x32), axis=axes)
                      - jnp.square(mean), 0.0)
    inv = jax.lax.rsqrt(var + eps)
    shift = b.astype(acc) - mean * inv
    y = x * inv.reshape(cshape).astype(x.dtype) \
        + shift.reshape(cshape).astype(x.dtype)
    out = _stem_conv(y, w, geom)
    return out, mean, var, inv


def _ibc_tap_ranges(in_dim, out_dim, k, s, p):
    """Per-tap inclusive output-index range whose input taps stay in-bounds:
    tap ``t`` at output ``i`` touches input row ``s*i - p + t``."""
    ranges = []
    for t in range(k):
        lo = max(0, -((-(p - t)) // s))   # ceil((p - t) / s), clamped
        hi = min(out_dim - 1, (in_dim - 1 + p - t) // s)
        ranges.append((lo, hi))
    return ranges


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _input_bn_conv_core(x, b, w, eps, geom):
    """BatchNorm(train, fix_gamma) on a no-gradient input, fused with the
    consuming Convolution — the ResNet stem pattern (bn_data -> conv0,
    reference example/image-classification/symbol_resnet.py).

    The only gradients this pattern needs are d(weight) and d(beta); the
    naive backward nevertheless runs a full backward-data convolution into
    the C-channel input grid purely to reduce it to d(beta) = sum(dy) — on
    TPU that dgrad runs at ~4% MXU efficiency (output channels = C = 3 pad
    to the 128-lane MXU).  This VJP computes d(beta) exactly without it:
    summing the transposed conv over the whole input grid collapses, per
    kernel tap, to a rectangle sum of the incoming cotangent over the
    output positions whose tap stays in-bounds — 2D prefix sums give every
    rectangle in one cheap pass, and a tiny einsum with the weights
    finishes the reduction.  d(x) is NOT produced (hard zero): the
    executor only fuses this pattern when the input is declared
    no-gradient."""
    out, mean, var, _ = _ibc_fwd_impl(x, b, w, eps, geom)
    return out, mean, var


def _input_bn_conv_fwd(x, b, w, eps, geom):
    out, mean, var, inv = _ibc_fwd_impl(x, b, w, eps, geom)
    return (out, mean, var), (x, b, w, mean, inv)


def _input_bn_conv_bwd(eps, geom, res, cts):
    g, _dmean_ct, _dvar_ct = cts      # mean/var flow only to x (dropped)
    x, b, w, mean, inv = res
    k, s, p = geom
    _, cshape = _bn_axes(x.ndim, -1)
    acc = jnp.promote_types(x.dtype, jnp.float32)
    # d(weight): standard wgrad with the normalised input recomputed (the
    # per-channel scale/shift fuses into the wgrad conv's input read)
    shift = b.astype(acc) - mean * inv
    y = x * inv.reshape(cshape).astype(x.dtype) \
        + shift.reshape(cshape).astype(x.dtype)

    def conv_of_w(wt):
        return _stem_conv(y, wt, geom)
    _, w_vjp = jax.vjp(conv_of_w, w)
    dw = w_vjp(g)[0]
    # d(beta) = sum over the input grid of dgrad(g, w), computed without the
    # dgrad: per-tap rectangle sums of G = sum_n g via 2D prefix sums
    G = jnp.sum(g.astype(acc), axis=0)              # (Ho, Wo, O)
    P = jnp.pad(jnp.cumsum(jnp.cumsum(G, axis=0), axis=1),
                ((1, 0), (1, 0), (0, 0)))           # (Ho+1, Wo+1, O)
    in_h, in_w = x.shape[1], x.shape[2]
    out_h, out_w = g.shape[1], g.shape[2]
    rows = _ibc_tap_ranges(in_h, out_h, k[0], s[0], p[0])
    cols = _ibc_tap_ranges(in_w, out_w, k[1], s[1], p[1])
    taps = []
    for r0, r1 in rows:
        for c0, c1 in cols:
            if r0 > r1 or c0 > c1:
                taps.append(jnp.zeros((g.shape[3],), acc))
                continue
            taps.append(P[r1 + 1, c1 + 1] - P[r0, c1 + 1]
                        - P[r1 + 1, c0] + P[r0, c0])
    S = jnp.stack(taps).reshape(k[0], k[1], g.shape[3])   # (kh, kw, O)
    db = jnp.einsum("ocij,ijo->c", w.astype(acc), S)
    return jnp.zeros_like(x), db.astype(b.dtype), dw


_input_bn_conv_core.defvjp(_input_bn_conv_fwd, _input_bn_conv_bwd)


def input_bn_conv(x_cl, beta, weight, eps, kernel, stride, pad):
    """Executor entry point: fused train-mode input-BN + conv, channel-last.
    Returns (out_cl, mean, var) with mean/var in f32 for the moving-stat
    update."""
    geom = (tuple(int(v) for v in kernel), tuple(int(v) for v in stride),
            tuple(int(v) for v in pad))
    return _input_bn_conv_core(x_cl, beta, weight, float(eps), geom)


def _bn_infer(attrs, in_shapes):
    data = in_shapes[0]
    c = None if data is None else (data[1],)
    ins = [data] + [c] * (len(in_shapes) - 1)
    nout = 3 if attrs.get("output_mean_var", False) else 1
    outs = [data] + ([c, c] if nout == 3 else [])
    return ins, outs, [c, c]


@register("BatchNorm", arg_names=("data", "gamma", "beta", "moving_mean",
                                  "moving_var"),
          aux_names=("moving_mean", "moving_var"),
          num_outputs=lambda attrs: 3 if attrs.get("output_mean_var", False) else 1,
          attr_types={"eps": parse_float, "momentum": parse_float,
                      "fix_gamma": parse_bool, "use_global_stats": parse_bool,
                      "output_mean_var": parse_bool, "layout": parse_str},
          defaults={"eps": 1e-3, "momentum": 0.9, "fix_gamma": True,
                    "use_global_stats": False, "output_mean_var": False},
          infer_shape=_bn_infer, train_aware=True, layout_rule="aware")
def _batch_norm(data, gamma, beta, moving_mean, moving_var, is_train=False,
                eps=1e-3, momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, layout=None):
    """Batch normalization (parity: batch_norm-inl.h / cudnn_batch_norm).

    Returns (out[, mean, var], new_moving_mean, new_moving_var); the trailing two
    are auxiliary-state updates collected by the executor."""
    caxis = -1 if layout == "NHWC" else 1
    _, cshape = _bn_axes(data.ndim, caxis)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    # statistics and the affine math are f32 even for bf16 data (bf16
    # mean/var over large N*H*W loses precision); every activation-sized
    # tensor stays in data.dtype — forward via fused convert-into-reduce,
    # backward via the hand-written VJP of _bn_train_core
    if is_train and not use_global_stats:
        out, mean, var = _bn_train_core(data, g, beta, float(eps), caxis)
        mom = jnp.float32(momentum)
        new_mm = moving_mean * mom + mean.astype(moving_mean.dtype) * (1 - mom)
        new_mv = moving_var * mom + var.astype(moving_var.dtype) * (1 - mom)
    else:
        acc = jnp.promote_types(data.dtype, jnp.float32)
        mean = jax.lax.stop_gradient(moving_mean).astype(acc)
        var = jax.lax.stop_gradient(moving_var).astype(acc)
        new_mm, new_mv = moving_mean, moving_var
        inv = jax.lax.rsqrt(var + eps)
        scale = g.astype(acc) * inv
        shift = beta.astype(acc) - mean * scale
        out = data * scale.reshape(cshape).astype(data.dtype) \
            + shift.reshape(cshape).astype(data.dtype)
    if output_mean_var:
        return out, mean, var, new_mm, new_mv
    return out, new_mm, new_mv


@register("_BatchNormReLU", arg_names=("data", "gamma", "beta", "moving_mean",
                                       "moving_var"),
          aux_names=("moving_mean", "moving_var"), num_outputs=1,
          attr_types={"eps": parse_float, "momentum": parse_float,
                      "fix_gamma": parse_bool, "use_global_stats": parse_bool,
                      "output_mean_var": parse_bool, "layout": parse_str},
          defaults={"eps": 1e-3, "momentum": 0.9, "fix_gamma": True,
                    "use_global_stats": False, "output_mean_var": False},
          infer_shape=_bn_infer, train_aware=True, layout_rule="aware",
          hidden=True)
def _batch_norm_relu(data, gamma, beta, moving_mean, moving_var,
                     is_train=False, eps=1e-3, momentum=0.9, fix_gamma=True,
                     use_global_stats=False, output_mean_var=False,
                     layout=None):
    """Executor-fused BatchNorm+ReLU (no reference analogue; the reference
    relies on cuDNN fusing these — here the fusion also rewrites the backward
    to recompute the relu mask rather than save the BN output)."""
    caxis = -1 if layout == "NHWC" else 1
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if is_train and not use_global_stats:
        out, mean, var = _bn_relu_train_core(data, g, beta, float(eps), caxis)
        mom = jnp.float32(momentum)
        new_mm = moving_mean * mom + mean.astype(moving_mean.dtype) * (1 - mom)
        new_mv = moving_var * mom + var.astype(moving_var.dtype) * (1 - mom)
        return out, new_mm, new_mv
    res = _batch_norm(data, gamma, beta, moving_mean, moving_var,
                      is_train=is_train, eps=eps, momentum=momentum,
                      fix_gamma=fix_gamma, use_global_stats=use_global_stats,
                      layout=layout)
    return (jnp.maximum(res[0], 0),) + tuple(res[1:])


@register("InstanceNorm", arg_names=("data", "gamma", "beta"),
          attr_types={"eps": parse_float}, defaults={"eps": 1e-3},
          infer_shape=lambda attrs, ins: (
              [ins[0]] + [None if ins[0] is None else (ins[0][1],)] * 2,
              [ins[0]], None))
def _instance_norm(data, gamma, beta, eps=1e-3):
    """(parity: instance_norm-inl.h)"""
    axes = tuple(range(2, data.ndim))
    cshape = (1, -1) + (1,) * (data.ndim - 2)
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    return (data - mean) * jax.lax.rsqrt(var + eps) * gamma.reshape(cshape) \
        + beta.reshape(cshape)


@register("L2Normalization", attr_types={"eps": parse_float, "mode": parse_str},
          defaults={"eps": 1e-10, "mode": "instance"})
def _l2_normalization(data, eps=1e-10, mode="instance"):
    """(parity: l2_normalization-inl.h; modes instance/channel/spatial)"""
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
    elif mode == "channel":
        axes = (1,)
    elif mode == "spatial":
        axes = tuple(range(2, data.ndim))
    else:
        raise MXNetError("unknown mode %s" % mode)
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps)
    return data / norm


@register("LRN", attr_types={"alpha": parse_float, "beta": parse_float,
                             "knorm": parse_float, "nsize": parse_int,
                             "layout": parse_str},
          defaults={"alpha": 1e-4, "beta": 0.75, "knorm": 2.0, "nsize": 5},
          layout_rule="aware")
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, layout=None):
    """Local response norm across channels (parity: lrn-inl.h).

    Layout-aware: under the executor's channel-last flow the window sum
    runs over the minor axis directly — before this, every LRN forced a
    physical NCHW relayout of its (large, early-network) activations in
    both directions of the train step (the AlexNet profile's top cost)."""
    caxis = (data.ndim - 1) if layout == "NHWC" else 1
    sq = jnp.square(data)
    half = nsize // 2
    pads = [(0, 0)] * data.ndim
    pads[caxis] = (half, half)
    sq = jnp.pad(sq, pads)
    window = [1] * data.ndim
    window[caxis] = nsize
    ssum = jax.lax.reduce_window(sq, 0.0, jax.lax.add, tuple(window),
                                 (1,) * data.ndim, [(0, 0)] * data.ndim)
    return data / jnp.power(knorm + alpha * ssum / nsize, beta)


# --------------------------------------------------------------------- Dropout
@register("Dropout", attr_types={"p": parse_float}, defaults={"p": 0.5},
          needs_rng=True, train_aware=True)
def _dropout(data, rng=None, is_train=False, p=0.5):
    """Inverted dropout (parity: dropout-inl.h)."""
    if not is_train or p <= 0.0:
        return data
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, data.shape)
    return jnp.where(mask, data / keep, 0.0).astype(data.dtype)


# ------------------------------------------------------------------ UpSampling
@register("UpSampling",
          arg_names=lambda attrs: ["arg%d" % i for i in range(
              int(attrs.get("num_args", 1)))],
          key_var_num_args="num_args",
          attr_types={"scale": parse_int, "num_filter": parse_int,
                      "sample_type": parse_str, "multi_input_mode": parse_str,
                      "num_args": parse_int, "workspace": parse_int},
          defaults={"scale": 1, "sample_type": "nearest",
                    "multi_input_mode": "concat"})
def _upsampling(*args, num_args=None, scale=1, num_filter=0,
                sample_type="nearest", multi_input_mode="concat", workspace=None):
    """(parity: upsampling-inl.h; nearest repeat / bilinear resize)"""
    outs = []
    data = args[0]
    target = (data.shape[2] * scale, data.shape[3] * scale)
    for x in args:
        if sample_type == "nearest":
            y = jnp.repeat(jnp.repeat(x, target[0] // x.shape[2], axis=2),
                           target[1] // x.shape[3], axis=3)
        else:
            y = jax.image.resize(x, x.shape[:2] + target, method="bilinear")
        outs.append(y)
    if len(outs) == 1:
        return outs[0]
    if multi_input_mode == "sum":
        out = outs[0]
        for y in outs[1:]:
            out = out + y
        return out
    return jnp.concatenate(outs, axis=1)


# --------------------------------------------------------------------- softmax
@register("softmax", attr_types={"axis": parse_int, "temperature": parse_float},
          defaults={"axis": -1, "temperature": None})
def _softmax(data, axis=-1, temperature=None):
    x = data / temperature if temperature else data
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax", attr_types={"axis": parse_int, "temperature": parse_float},
          defaults={"axis": -1, "temperature": None})
def _log_softmax(data, axis=-1, temperature=None):
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)


@register("SoftmaxActivation", attr_types={"mode": parse_str},
          defaults={"mode": "instance"})
def _softmax_activation(data, mode="instance"):
    """(parity: softmax_activation-inl.h)"""
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1),
                          axis=-1).reshape(data.shape)
