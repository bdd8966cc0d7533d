"""Plain reference for the ``resnet`` family: pre-activation ResNet (He et al.,
"Identity mappings", as MXNet's ``symbols/resnet.py`` builds it) in
straightforward float32 ``jax.numpy``.  Imports nothing of the program.

Departures from the paper, matching the symbol: a BatchNorm on the input
whose scale is fixed at 1 (``fix_gamma``); batch statistics with the biased
variance; eps 2e-5.  Each residual unit is rematerialised in the backward
pass so that a batch of 256 at 224x224 fits beside nothing else on one chip
(the arithmetic is unchanged)."""
import jax
import jax.numpy as jnp


def param_shapes(cfg):
    """name -> shape of every trained leaf, in the symbol's naming."""
    c_in, _, _ = cfg["image_shape"]
    fl = cfg["filter_list"]
    shapes = {"bn_data_gamma": (c_in,), "bn_data_beta": (c_in,),
              "conv0_weight": (fl[0], c_in, 7, 7),
              "bn0_gamma": (fl[0],), "bn0_beta": (fl[0],)}
    prev = fl[0]
    for s, units in enumerate(cfg["units"]):
        out = fl[s + 1]
        mid = out // 4
        for u in range(units):
            n = "stage%d_unit%d" % (s + 1, u + 1)
            shapes.update({
                n + "_bn1_gamma": (prev,), n + "_bn1_beta": (prev,),
                n + "_conv1_weight": (mid, prev, 1, 1),
                n + "_bn2_gamma": (mid,), n + "_bn2_beta": (mid,),
                n + "_conv2_weight": (mid, mid, 3, 3),
                n + "_bn3_gamma": (mid,), n + "_bn3_beta": (mid,),
                n + "_conv3_weight": (out, mid, 1, 1)})
            if u == 0:
                shapes[n + "_sc_weight"] = (out, prev, 1, 1)
            prev = out
    shapes.update({"bn1_gamma": (prev,), "bn1_beta": (prev,),
                   "fc1_weight": (cfg["num_classes"], prev),
                   "fc1_bias": (cfg["num_classes"],)})
    return shapes


def _conv(x, w, stride, pad, q):
    x, w = q(x), q(w)
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST)
    return q.back(y)


def _bn(x, gamma, beta, eps):
    mean = x.mean(axis=(0, 2, 3), keepdims=True)
    var = ((x - mean) ** 2).mean(axis=(0, 2, 3), keepdims=True)
    xhat = (x - mean) * jax.lax.rsqrt(var + eps)
    return xhat * gamma.reshape(1, -1, 1, 1) + beta.reshape(1, -1, 1, 1)


def _unit(p, x, n, stride, match, eps, q):
    a1 = jax.nn.relu(_bn(x, p[n + "_bn1_gamma"], p[n + "_bn1_beta"], eps))
    c1 = _conv(a1, p[n + "_conv1_weight"], 1, 0, q)
    a2 = jax.nn.relu(_bn(c1, p[n + "_bn2_gamma"], p[n + "_bn2_beta"], eps))
    c2 = _conv(a2, p[n + "_conv2_weight"], stride, 1, q)
    a3 = jax.nn.relu(_bn(c2, p[n + "_bn3_gamma"], p[n + "_bn3_beta"], eps))
    c3 = _conv(a3, p[n + "_conv3_weight"], 1, 0, q)
    short = x if match else _conv(a1, p[n + "_sc_weight"], stride, 0, q)
    return c3 + short


def mean_loss(params, data, label, cfg, q):
    """Mean cross-entropy of the batch.  ``q`` quantises the operands of
    every convolution and matrix product (identity for the reference, a
    lower precision for the control)."""
    eps = cfg["bn_eps"]
    p = params
    x = _bn(data, jnp.ones_like(p["bn_data_gamma"]), p["bn_data_beta"], eps)
    x = _conv(x, p["conv0_weight"], 2, 3, q)
    x = jax.nn.relu(_bn(x, p["bn0_gamma"], p["bn0_beta"], eps))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              ((0, 0), (0, 0), (1, 1), (1, 1)))
    for s, units in enumerate(cfg["units"]):
        for u in range(units):
            n = "stage%d_unit%d" % (s + 1, u + 1)
            stride = 1 if (s == 0 or u > 0) else 2
            keys = [k for k in p if k.startswith(n + "_")]
            unit = jax.checkpoint(
                lambda sub, x, n=n, stride=stride, u=u:
                _unit(sub, x, n, stride, u > 0, eps, q))
            x = unit({k: p[k] for k in keys}, x)
    x = jax.nn.relu(_bn(x, p["bn1_gamma"], p["bn1_beta"], eps))
    x = x.mean(axis=(2, 3))
    logits = jnp.dot(q(x), q(p["fc1_weight"]).T,
                     precision=jax.lax.Precision.HIGHEST)
    logits = q.back(logits) + p["fc1_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, label.astype(jnp.int32).reshape(-1, 1), axis=1)
    return -picked.mean()
