"""Plain reference for the ``hybrid_lm`` family: the autoregressive
language-model tower of a Nemotron-H style hybrid (``model_type`` nemotron_h)
in straightforward float32 ``jax.numpy``, ``HIGHEST`` products, no kernel, no
cache.  Imports nothing of the program.

Every layer is ``x <- x + part(RMSNorm(x))`` with one part, named by the
pattern string; all norms are RMSNorm (eps from the config) with a learned
scale; no bias anywhere but the convolution's; after the last layer a final
RMSNorm and an untied head.  With h the hidden size:

``M``, Mamba-2 mixer.  d_inner = heads x head size (the config's
  ``mamba_num_heads`` x ``mamba_head_dim``; ``expand`` is not used), G groups,
  state N.  ``in_proj``: h -> d_inner (gate z) + d_inner + 2 G N (xBC) + heads
  (dt).  ``xBC <- silu(conv1d(xBC))``, causal, depthwise, kernel K, with bias;
  split into x (heads x P), B, C (G x N; head i reads group i // (heads / G)).
  ``dt <- softplus(dt + dt_bias)`` (``time_step_limit`` (0, inf) clamps
  nothing).  ``A = -exp(A_log)``, one scalar a head.  Per head, state S of
  P x N:  ``S_t = exp(A dt_t) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t``, computed here AS THAT RECURRENCE over t (the
  program uses the chunked form; the two share no algorithm).  Then the gated
  norm, gate first: ``y <- RMSNorm_groups(y * silu(z))`` over G groups of
  d_inner / G; ``out_proj``: d_inner -> h.
``E``, expert layer.  Router ``s = sigmoid(W_r u)`` over ALL routed experts;
  the k largest of ``s + b`` (b the selection bias: it takes part in the
  choice only and has no gradient); weights ``s[chosen] / (sum + 1e-20)`` x
  the routed scaling factor.  Routed expert, not gated:
  ``down(relu(up(u))^2)``; one shared expert of the same form on every token.
  Output ``sum_chosen w_e expert_e(u) + shared(u)``, of which this reference,
  like the program, computes the part that the experts HELD here give
  (experts ``first_expert`` .. + ``n_routed_experts``; the configuration's
  deployment): each held expert on every token, weighted by the token's
  routing weight for it, zero where it was not chosen.
``*``, attention.  Hq query heads on Hkv key/value heads of size D (query
  head i reads key/value head i // (Hq / Hkv)), causal, scale 1/sqrt(D), the
  full (T, T) scores, no bias, no rotary embedding.

Departures are those the configuration lists under ``assumed`` (no rotary,
no second tower, the share of experts and of the vocabulary).

Computed in blocks so that it fits beside 10 GiB of float32 parameters,
gradients and Adam state: each layer, each block of ``TIME_BLOCK`` steps of
the recurrence and each key/value head's group of query heads is under
``jax.checkpoint``.  The mathematics is unchanged.

The quantiser ``q`` goes round the operands of every product (``q.back``
round its result), as in ``reference/transformer.py``; in the recurrence the
products are x B^T and S C, so x, B and C are their operands."""
import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
TIME_BLOCK = 64


def _dims(cfg):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return h, p, g, n


def param_shapes(cfg):
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p, g, n = _dims(cfg)
    inner, xbc = h * p, h * p + 2 * g * n
    d = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    routed = cfg["published"]["n_routed_experts"]
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"]
    shapes = {"embed_weight": (v, c), "final_norm_gamma": (c,),
              "lm_head_weight": (v, c)}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        l = "layer%d" % i
        shapes[l + "_norm_gamma"] = (c,)
        if kind == "M":
            shapes.update({
                l + "_in_proj_weight": (inner + xbc + h, c),
                l + "_conv_weight": (xbc, cfg["conv_kernel"]),
                l + "_conv_bias": (xbc,),
                l + "_A_log": (h,), l + "_D_gamma": (h,),
                l + "_dt_bias": (h,), l + "_ssm_norm_gamma": (inner,),
                l + "_out_proj_weight": (c, inner)})
        elif kind == "E":
            shapes.update({
                l + "_router_weight": (routed, c),
                l + "_router_bias": (routed,),
                l + "_experts_up_weight": (held, f, c),
                l + "_experts_down_weight": (held, c, f),
                l + "_shared_up_weight": (fs, c),
                l + "_shared_down_weight": (c, fs)})
        elif kind == "*":
            shapes.update({
                l + "_q_weight": (hq * d, c), l + "_k_weight": (hkv * d, c),
                l + "_v_weight": (hkv * d, c),
                l + "_o_proj_weight": (c, hq * d)})
        else:
            raise ValueError("no reference for layer kind %r" % kind)
    return shapes


def _rms(x, gamma, eps, groups=1):
    xg = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    var = jnp.mean(xg * xg, axis=-1, keepdims=True)
    return (xg * jax.lax.rsqrt(var + eps)).reshape(x.shape) * gamma


def _fc(x, w, q):
    return q.back(jnp.dot(q(x), q(w).T, precision=HI))


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _recurrence(x, dt, a, b, c):
    """x (T, H, P), dt (T, H), a (H,), b and c (T, H, N), one sequence:
    y (T, H, P) by the recurrence over t, in checkpointed blocks of time."""
    t = x.shape[0]
    pad = -t % TIME_BLOCK

    def blocks(v):
        v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return v.reshape((-1, TIME_BLOCK) + v.shape[1:])

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(a * dt_t)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def block(s, inp):
        return jax.lax.scan(step, s, inp)

    s0 = jnp.zeros(x.shape[1:] + (b.shape[-1],), jnp.float32)
    _, y = jax.lax.scan(block, s0, (blocks(x), blocks(dt), blocks(b),
                                    blocks(c)))
    return y.reshape((-1,) + x.shape[1:])[:t]


def _mamba(p, u, l, batch, seq, cfg, q):
    h, hp, g, n = _dims(cfg)
    inner = h * hp
    proj = _fc(u, p[l + "_in_proj_weight"], q)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * g * n],
                  proj[:, 2 * inner + 2 * g * n:])
    xbc = xbc.reshape(batch, seq, inner + 2 * g * n)
    k = cfg["conv_kernel"]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    w = p[l + "_conv_weight"]
    xbc = sum(padded[:, j:j + seq] * w[:, j] for j in range(k)) \
        + p[l + "_conv_bias"]
    xbc = jax.nn.silu(xbc)
    x = q(xbc[..., :inner]).reshape(batch, seq, h, hp)
    b = q(xbc[..., inner:inner + g * n]).reshape(batch, seq, g, n)
    c = q(xbc[..., inner + g * n:]).reshape(batch, seq, g, n)
    b, c = (jnp.repeat(v, h // g, axis=2) for v in (b, c))
    dt = jax.nn.softplus(dt.reshape(batch, seq, h) + p[l + "_dt_bias"])
    a = -jnp.exp(p[l + "_A_log"])
    y = q.back(jax.vmap(_recurrence, in_axes=(0, 0, None, 0, 0))(
        x, dt, a, b, c))
    y = y + p[l + "_D_gamma"][:, None] * x
    y = y.reshape(batch * seq, inner) * jax.nn.silu(z)
    y = _rms(y, p[l + "_ssm_norm_gamma"], cfg["norm_eps"], g)
    return _fc(y, p[l + "_out_proj_weight"], q)


def route(u, w_r, bias, k, scale):
    """(chosen (N, k), weights (N, k)) of the sigmoid router."""
    s = jax.nn.sigmoid(jnp.dot(u, w_r.T, precision=HI))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    w = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, w / (w.sum(axis=1, keepdims=True) + 1e-20) * scale


def _experts(p, u, l, cfg, q):
    first = cfg.get("deployment", {}).get("first_expert", 0)
    chosen, w = route(u, p[l + "_router_weight"], p[l + "_router_bias"],
                      cfg["num_experts_per_tok"],
                      cfg["routed_scaling_factor"])
    up, down = p[l + "_experts_up_weight"], p[l + "_experts_down_weight"]

    def held_expert(out, args):
        e, up_e, down_e = args
        # the token's routing weight for this expert, 0 where not chosen
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=1)
        return out + w_e[:, None] * _fc(_relu2(_fc(u, up_e, q)), down_e,
                                        q), None
    out, _ = jax.lax.scan(held_expert, jnp.zeros_like(u),
                          (jnp.arange(up.shape[0]), up, down))
    if l + "_shared_up_weight" in p:
        out = out + _fc(_relu2(_fc(u, p[l + "_shared_up_weight"], q)),
                        p[l + "_shared_down_weight"], q)
    return out


def _attention(p, u, l, batch, seq, cfg, q):
    d = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]

    def heads(w, n):
        return _fc(u, w, q).reshape(batch, seq, n, d).transpose(0, 2, 1, 3)
    qq = heads(p[l + "_q_weight"], hq).reshape(batch, hkv, hq // hkv, seq, d)
    kk, vv = heads(p[l + "_k_weight"], hkv), heads(p[l + "_v_weight"], hkv)
    mask = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def group(q_g, k_g, v_g):            # (B, R, T, D), (B, T, D), (B, T, D)
        s = q.back(jnp.einsum("brtd,bsd->brts", q(q_g), q(k_g),
                              precision=HI)) * (1.0 / d ** 0.5)
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return q.back(jnp.einsum("brts,bsd->brtd", q(a), q(v_g),
                                 precision=HI))
    o = jnp.stack([group(qq[:, j], kk[:, j], vv[:, j])
                   for j in range(hkv)], axis=1)             # (B,Hkv,R,T,D)
    o = o.reshape(batch, hq, seq, d).transpose(0, 2, 1, 3)
    return _fc(o.reshape(batch * seq, hq * d), p[l + "_o_proj_weight"], q)


def forward(params, data, cfg, q):
    """Logits (B*T, vocabulary)."""
    p = params
    batch, seq = data.shape
    eps = cfg["norm_eps"]
    x = p["embed_weight"][data.astype(jnp.int32)].reshape(
        batch * seq, cfg["hidden_size"])
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        l = "layer%d" % i

        def layer(sub, x, l=l, kind=kind):
            u = _rms(x, sub[l + "_norm_gamma"], eps)
            if kind == "M":
                return x + _mamba(sub, u, l, batch, seq, cfg, q)
            if kind == "E":
                return x + _experts(sub, u, l, cfg, q)
            return x + _attention(sub, u, l, batch, seq, cfg, q)
        x = jax.checkpoint(layer)(
            {k: v for k, v in p.items() if k.startswith(l + "_")}, x)
    x = _rms(x, p["final_norm_gamma"], eps)
    return _fc(x, p["lm_head_weight"], q)


def mean_loss(params, data, label, cfg, q):
    """Mean next-token cross-entropy over the batch's B x T positions."""
    logp = jax.nn.log_softmax(forward(params, data, cfg, q), axis=-1)
    picked = jnp.take_along_axis(
        logp, label.astype(jnp.int32).reshape(-1, 1), axis=1)
    return -picked.mean()
