"""Everything a run draws from ``--seed``: weights on the device in one jitted
call, image batches on the device for ``fit`` traffic, token batches on the
device for ``run_steps`` traffic.  The program and the plain reference are both handed
what this file makes; neither makes its own."""
import math


def _key(seed):
    """A jax key from any whole number (seeds pass 2**31)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def weights_fn(shapes, init):
    """``seed key -> {name: f32 array}`` for the leaves in ``shapes``
    (name -> shape), by the rule of the leaf's name and rank that the
    configuration's ``init`` states.  Not jitted: callers jit it alone
    (set-up) or inside a larger program (the norms of the first steps)."""
    import jax
    import jax.numpy as jnp
    names = sorted(shapes)
    b_std = float(init.get("beta_bias_std", 0.0))

    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape = tuple(shapes[name])
            noise = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
            if name.endswith("_gamma"):
                out[name] = 1.0 + 0.1 * noise
            elif name.endswith(("_beta", "_bias")):
                out[name] = b_std * noise
            elif len(shape) == 4:           # conv, OIHW: He normal
                fan_in = shape[1] * shape[2] * shape[3]
                out[name] = math.sqrt(2.0 / fan_in) * noise
            else:
                std = init.get("matrix_std", init.get("fc_std"))
                out[name] = float(std) * noise
        return out
    return make


def make_weights(shapes, init, seed):
    import jax
    return jax.jit(weights_fn(shapes, init))(_key(seed))


def device_tokens(seed, steps, batch, seq_len, vocab):
    """``steps`` batches of ``batch x seq_len`` token ids and their next-token
    labels, drawn on the device: (data int32 [steps,B,T], label f32)."""
    import jax
    import jax.numpy as jnp

    def draw(key):
        toks = jax.random.randint(key, (steps, batch, seq_len + 1), 0, vocab,
                                  jnp.int32)
        return toks[:, :, :-1], toks[:, :, 1:].astype(jnp.float32)
    return jax.jit(draw)(jax.random.fold_in(_key(seed), 0x70C))


def device_images(seed, batches, batch, image_shape, classes, low=-1.0,
                  high=1.0):
    """``batches`` batches of f32 images in [low, high) and class ids, drawn
    on the device in one jitted call: ([batches, B, C, H, W], [batches,
    B])."""
    import jax
    import jax.numpy as jnp

    def draw(key):
        kx, ky = jax.random.split(key)
        x = jax.random.uniform(kx, (batches, batch) + tuple(image_shape),
                               jnp.float32, low, high)
        y = jax.random.randint(ky, (batches, batch), 0, classes)
        return x, y.astype(jnp.float32)
    return jax.jit(draw)(jax.random.fold_in(_key(seed), 0x1A6))
