"""The reference's training loop: mean loss, its gradient, and the optimizer
rule of the configuration, in plain float32.  ``follow`` drives the first
steps and returns the readings that decide ``correct``: each step's loss,
the norm of every leaf's first gradient, of the optimizer's first slot after
the last step, and of every leaf's change after the last step.  Imports
nothing of the program.

The quantisers stand for the precision of the matrix products: ``Exact`` is
the reference (float32 operands, ``HIGHEST`` products); ``Fp8`` is the
control, the step below the bfloat16 that the configurations state: e4m3
operands forward, e5m2 cotangents backward, each scaled by its tensor's
largest magnitude, as fp8 training recipes do."""
import importlib

import jax
import jax.numpy as jnp


class Exact:
    def __call__(self, x):
        return x

    def back(self, y):
        return y


def _fake_quant(x, dtype, top):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _q_fwd(x):
    return _fake_quant(x, jnp.float8_e4m3fn, 448.0)


_q_fwd.defvjp(lambda x: (_q_fwd(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _q_bwd(y):
    return y


_q_bwd.defvjp(lambda y: (y, None),
              lambda _, g: (_fake_quant(g, jnp.float8_e5m2, 57344.0),))


class Fp8:
    """Operands of every product in e4m3 (straight-through backward); the
    cotangent entering every product's backward in e5m2."""

    def __call__(self, x):
        return _q_fwd(x)

    def back(self, y):
        return _q_bwd(y)


def _round_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def _r_fwd(x):
    return _round_bf16(x)


_r_fwd.defvjp(lambda x: (_r_fwd(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _r_bwd(y):
    return y


_r_bwd.defvjp(lambda y: (y, None), lambda _, g: (_round_bf16(g),))


class Bf16:
    """The precision the configurations state, as a witness beside the
    program: operands of every product and the cotangent entering its
    backward rounded to bfloat16, all else float32."""

    def __call__(self, x):
        return _r_fwd(x)

    def back(self, y):
        return _r_bwd(y)


QUANTISERS = {"float32": Exact, "bfloat16": Bf16, "fp8": Fp8}


def family(cfg):
    return importlib.import_module(
        "benchmark.reference." + cfg["family"])


def wd_mult(name):
    """The rule the program's optimizers inherit from MXNet: weight decay on
    ``*_weight`` and ``*_gamma`` only."""
    return 1.0 if name.endswith(("_weight", "_gamma")) else 0.0


def _update(opt, name, w, g, state, t):
    """One step of the configuration's optimizer on one leaf; ``g`` is the
    gradient of the mean loss, ``t`` the 1-based step."""
    g = g + opt.get("wd", 0.0) * wd_mult(name) * w
    lr = opt["learning_rate"]
    if opt["name"] == "sgd":
        mom = opt["momentum"] * state[0] - lr * g
        return w + mom, (mom,)
    if opt["name"] == "adam":
        b1, b2 = opt["beta1"], opt["beta2"]
        m = b1 * state[0] + (1 - b1) * g
        v = b2 * state[1] + (1 - b2) * g * g
        tf = jnp.asarray(t, jnp.float32)
        step = lr * jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
        return w - step * m / (jnp.sqrt(v) + opt["epsilon"]), (m, v)
    raise ValueError("no reference rule for optimizer %r" % opt["name"])


def init_state(opt, params):
    n = {"sgd": 1, "adam": 2}[opt["name"]]
    return {k: tuple(jnp.zeros_like(v) for _ in range(n))
            for k, v in params.items()}


def leaf_norms(tree, splits=None):
    """{leaf: L2 norm}.  A leaf that stacks several matrices along its first
    axis (the family's ``SPLIT``: suffix -> parts, as q, k and v in one
    ``qkv`` leaf) is read part by part, ``name#i``, because the parts are
    leaves in all but storage: the key's bias has no gradient under softmax
    while the query's and the value's have."""
    out = {}
    for k, v in tree.items():
        v = v.astype(jnp.float32)
        parts = next((n for suf, n in (splits or {}).items()
                      if k.endswith(suf)), 1)
        if parts == 1:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(v)))
        else:
            for i, piece in enumerate(jnp.split(v, parts, axis=0)):
                out["%s#%d" % (k, i)] = jnp.sqrt(jnp.sum(jnp.square(piece)))
    return out


def make_step(cfg, quantiser="float32"):
    """jitted (params, state, data, label, t) -> (loss, grad norms, params,
    state); params and state are donated."""
    fam = family(cfg)
    q = QUANTISERS[quantiser]()
    opt = cfg["optimizer"]
    splits = getattr(fam, "SPLIT", None)

    def step(params, state, data, label, t):
        loss, grads = jax.value_and_grad(fam.mean_loss)(
            params, data, label, cfg, q)
        new_p, new_s = {}, {}
        for k in params:
            new_p[k], new_s[k] = _update(opt, k, params[k], grads[k],
                                         state[k], t)
        return loss, leaf_norms(grads, splits), new_p, new_s
    return jax.jit(step, donate_argnums=(0, 1))


def follow(cfg, make_params, batches, quantiser="float32", step=None):
    """Drive ``len(batches)`` steps from ``make_params()`` (a fresh copy of
    the seed's weights each call) over ``batches`` [(data, label), ...].
    Returns {"loss": {step: mean loss}, "grad": {leaf: norm of step 1's gradient},
    "moment": {leaf: norm of the optimizer's first slot after the last step},
    "change": {leaf: norm of the change after the last step}} as floats."""
    step = step or make_step(cfg, quantiser)
    params = make_params()
    state = init_state(cfg["optimizer"], params)
    losses, first = [], None
    for t, (data, label) in enumerate(batches, 1):
        loss, gnorm, params, state = step(params, state, data, label, t)
        losses.append(loss)
        if first is None:
            first = gnorm
    splits = getattr(family(cfg), "SPLIT", None)
    moment = jax.jit(lambda s: leaf_norms({k: v[0] for k, v in s.items()},
                                          splits))(state)
    del state
    start = make_params()
    change = jax.jit(lambda a, b: leaf_norms(
        {k: a[k] - b[k] for k in a}, splits))(params, start)
    host = jax.device_get({"loss": losses, "grad": first, "moment": moment,
                           "change": change})
    return {"loss": {t: float(x) for t, x in enumerate(host["loss"], 1)},
            **{side: {k: float(v) for k, v in host[side].items()}
               for side in ("grad", "moment", "change")}}
