"""Plain reference for the ``transformer`` family: the OPT decoder block
(Zhang et al. 2022: pre-LayerNorm, ReLU MLP of 4x, learned positions, causal
multi-head attention, biases everywhere) in straightforward float32
``jax.numpy``: the full (T, T) score matrix, no kernel, no cache.  Imports
nothing of the program.  Departures are those the configuration lists under
``assumed`` (own output head, 2048-row position table, no dropout).

Each block is rematerialised in the backward pass so that 4 x 2048 tokens
fit; the arithmetic is unchanged."""
import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
# q, k and v are one leaf of 3C rows: read their norms apart
SPLIT = {"_qkv_weight": 3, "_qkv_bias": 3}


def param_shapes(cfg):
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"embed_weight": (v, c),
              "pos_embed_weight": (cfg["max_position_embeddings"], c),
              "final_ln_gamma": (c,), "final_ln_beta": (c,),
              "lm_head_weight": (v, c), "lm_head_bias": (v,)}
    for i in range(cfg["num_hidden_layers"]):
        n = "layer%d" % i
        shapes.update({
            n + "_ln1_gamma": (c,), n + "_ln1_beta": (c,),
            n + "_qkv_weight": (3 * c, c), n + "_qkv_bias": (3 * c,),
            n + "_proj_weight": (c, c), n + "_proj_bias": (c,),
            n + "_ln2_gamma": (c,), n + "_ln2_beta": (c,),
            n + "_mlp1_weight": (cfg["ffn_dim"], c),
            n + "_mlp1_bias": (cfg["ffn_dim"],),
            n + "_mlp2_weight": (c, cfg["ffn_dim"]),
            n + "_mlp2_bias": (c,)})
    return shapes


def _ln(x, g, b, eps):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _fc(x, w, b, q):
    return q.back(jnp.dot(q(x), q(w).T, precision=HI)) + b


def _block(p, x, n, batch, seq, heads, eps, q):
    c = x.shape[-1]
    d = c // heads
    h = _ln(x, p[n + "_ln1_gamma"], p[n + "_ln1_beta"], eps)
    qkv = _fc(h, p[n + "_qkv_weight"], p[n + "_qkv_bias"], q)
    qkv = qkv.reshape(batch, seq, 3, heads, d).transpose(2, 0, 3, 1, 4)
    qq, kk, vv = qkv[0], qkv[1], qkv[2]                  # (B, H, T, D)
    s = q.back(jnp.einsum("bhtd,bhsd->bhts", q(qq), q(kk), precision=HI))
    s = s * (1.0 / (d ** 0.5))
    mask = jnp.tril(jnp.ones((seq, seq), bool))
    s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = q.back(jnp.einsum("bhts,bhsd->bhtd", q(a), q(vv), precision=HI))
    o = o.transpose(0, 2, 1, 3).reshape(batch * seq, c)
    x = x + _fc(o, p[n + "_proj_weight"], p[n + "_proj_bias"], q)
    h = _ln(x, p[n + "_ln2_gamma"], p[n + "_ln2_beta"], eps)
    h = jax.nn.relu(_fc(h, p[n + "_mlp1_weight"], p[n + "_mlp1_bias"], q))
    return x + _fc(h, p[n + "_mlp2_weight"], p[n + "_mlp2_bias"], q)


def mean_loss(params, data, label, cfg, q):
    """Mean next-token cross-entropy over the batch's B x T positions."""
    p = params
    batch, seq = data.shape
    eps = cfg["layer_norm_eps"]
    heads = cfg["num_attention_heads"]
    x = p["embed_weight"][data.astype(jnp.int32)] \
        + p["pos_embed_weight"][jnp.arange(seq)][None]
    x = x.reshape(batch * seq, -1)
    for i in range(cfg["num_hidden_layers"]):
        n = "layer%d" % i
        keys = [k for k in p if k.startswith(n + "_")]
        block = jax.checkpoint(
            lambda sub, x, n=n: _block(sub, x, n, batch, seq, heads, eps, q))
        x = block({k: p[k] for k in keys}, x)
    x = _ln(x, p["final_ln_gamma"], p["final_ln_beta"], eps)
    logits = _fc(x, p["lm_head_weight"], p["lm_head_bias"], q)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, label.astype(jnp.int32).reshape(-1, 1), axis=1)
    return -picked.mean()
