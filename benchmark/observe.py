"""The readings of the program's first steps, taken on the device from what
the timed entry itself holds: a step's mean loss from the softmax rows it
returned, every leaf's first gradient as the optimizer got it (worked back
from the optimizer state after one step, where the entry can stop there),
the optimizer's first slot and every leaf's change after the last of the
first steps.  The seed's weights are drawn again inside these
programs rather than kept, so that no second copy of the model stays on the
device."""
from benchmark import gen
from benchmark.reference.train import leaf_norms, wd_mult


def mean_loss_fn():
    import jax
    import jax.numpy as jnp

    def loss(probs, label):
        probs = probs.reshape(-1, probs.shape[-1])
        picked = jnp.take_along_axis(
            probs, label.astype(jnp.int32).reshape(-1, 1), axis=1)
        return -jnp.log(picked).mean()
    return jax.jit(loss)


def grad_norms_fn(shapes, init, opt, splits=None):
    """jitted (optimizer state after step 1, seed key) -> {leaf: norm of the
    gradient of the mean loss}."""
    import jax
    make = gen.weights_fn(shapes, init)
    lr, wd = opt["learning_rate"], opt.get("wd", 0.0)

    def norms(state, key):
        w0 = make(key)
        out = {}
        for k in shapes:
            first = state[k][0]
            if opt["name"] == "sgd":         # mom1 = -lr (g + wd w0)
                g = -first / lr
            elif opt["name"] == "adam":      # m1 = (1 - beta1)(g + wd w0)
                g = first / (1.0 - opt["beta1"])
            else:
                raise ValueError(opt["name"])
            out[k] = g - wd * wd_mult(k) * w0[k]
        return leaf_norms(out, splits)
    return jax.jit(norms)


def change_norms_fn(shapes, init, splits=None):
    """jitted (params now, seed key) -> {leaf: norm of params - seed's}."""
    import jax
    make = gen.weights_fn(shapes, init)

    def norms(params, key):
        w0 = make(key)
        return leaf_norms({k: params[k] - w0[k] for k in shapes}, splits)
    return jax.jit(norms)


def moment_norms_fn(shapes, splits=None):
    """jitted (optimizer state) -> {leaf: norm of its first slot}: Adam's
    first moment, SGD's momentum."""
    import jax
    return jax.jit(lambda state: leaf_norms(
        {k: state[k][0] for k in shapes}, splits))


def to_host(observed):
    import jax
    host = jax.device_get(observed)
    return {side: {k: float(v) for k, v in leaves.items()}
            for side, leaves in host.items()}
