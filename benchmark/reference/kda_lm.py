"""Plain reference for the ``kda_lm`` family: a Kimi Linear style decoder
(``model_type`` kimi_linear; Kimi Linear, arXiv:2510.26692) in
straightforward float32 ``jax.numpy``, ``HIGHEST`` products, no kernel, no
cache.  Imports nothing of the program.

Every layer is ``x <- x + mixer(RMSNorm(x))`` and then ``x <- x +
ffn(RMSNorm(x))``; all norms are RMSNorm (``rms_norm_eps``) with a learned
scale; no bias anywhere but ``dt_bias``; after the last layer a final RMSNorm
and an untied head.  Layer i (from 1) mixes by KDA where
``linear_attn_config.kda_layers`` names it and by MLA where
``full_attn_layers`` does; its feed-forward is dense for i <=
``first_k_dense_replace`` and an expert layer after.  The leaves are named
by part, ``layer<j>_`` with j counting mixers and feed-forwards together
from 0 (layer 1's mixer is ``layer0``, its feed-forward ``layer1``).  With
C the hidden size:

KDA mixer, H heads of d (``linear_attn_config``), decay and state float32:
  ``q = l2norm(silu(conv(W_q u))) / sqrt(d)``, ``k = l2norm(silu(conv(W_k
  u)))``, ``v = silu(conv(W_v u))``: each projection C -> H d, each
  convolution causal, depthwise, ``short_conv_kernel_size`` taps, no bias;
  the l2 norm over a head's d channels, eps 1e-6 under the root.
  ``g_t = -exp(A_log_h) softplus(W_fb W_fa u_t + dt_bias)`` for every key
  channel (C -> d -> H d), ``a_t = exp(g_t)``; ``beta_t = sigmoid(w_b
  u_t)``, one a head.  Per head, state S of d x d from zero:
  ``S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``, computed here AS THAT RECURRENCE over t (the program
  uses a chunked form with a triangular solve; the two share no algebra).
  ``y = W_o (RMSNorm_head(o; gamma) * sigmoid(W_gb W_ga u))``, the norm over
  each head's d channels with one gamma of d.
MLA mixer (``q_lora_rank`` null, ``mla_use_nope``: no query compression, no
  rotation; the ``qk_rope_head_dim`` channels are plain channels):
  ``q = W_q u`` as heads of nope + rope; ``[c ; k_r] = W_kva u`` (rank +
  rope), ``c <- RMSNorm(c)``; ``[k_c,h ; v_h] = W_kvb c`` as heads of nope +
  ``v_head_dim``; ``k_h = [k_c,h ; k_r]``, k_r the same for every head;
  causal softmax of ``q_h k_h^T / sqrt(nope + rope)`` on ``v_h``, the full
  masked (T, T) scores, by blocks of queries; ``W_o``.
Dense feed-forward: ``W_down(silu(W_gate u) * W_up u)``.
Expert layer: ``s = sigmoid(W_r u)`` over ALL routed experts; the k largest
  of ``s + b`` (b the selection bias: it takes part in the choice only and
  has no gradient; one group, no group limit); weights ``s[chosen] / (sum +
  1e-20)`` x ``routed_scaling_factor``; every expert a SwiGLU
  ``down(silu(gate u) * up u)``; one shared expert of the same form on every
  token.  Of ``sum_chosen w_e expert_e(u) + shared(u)`` this reference, like
  the program, computes the part that the experts HELD here give (experts
  ``first_expert`` .. + ``num_experts``; the configuration's deployment):
  each held expert on every token, weighted by the token's routing weight
  for it, zero where it was not chosen.

Departures from the published description are those the configuration lists
under ``assumed`` (the low-rank gates' rank and their lack of bias, the
convolutions without bias, the l2 norm's eps, the share of experts and of
the vocabulary, no multi-token prediction).

Computed in blocks so that it fits beside 9 GiB of float32 parameters,
gradients and Adam state: each part, each block of ``TIME_BLOCK`` steps of
the recurrence and each block of ``QUERY_BLOCK`` queries is under
``jax.checkpoint`` (a state kept for every token would be 8.6 GB a layer at
T = 4096).  The mathematics is unchanged.

The quantiser ``q`` goes round the operands of every product (``q.back``
round its result), as in ``reference/transformer.py``; in the recurrence the
products are ``k^T S``, ``k u^T`` and ``S^T q``, so q, k and v are their
operands."""
import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
TIME_BLOCK = 64
QUERY_BLOCK = 512


def parts(cfg):
    """The parts on the residual stream, in order: a letter for each
    layer's mixer (``K`` KDA, ``L`` MLA) and feed-forward (``D`` dense,
    ``E`` experts)."""
    la = cfg["linear_attn_config"]
    out = ""
    for i in range(1, cfg["num_hidden_layers"] + 1):
        if (i in la["kda_layers"]) == (i in la["full_attn_layers"]):
            raise ValueError("layer %d is not one of KDA and MLA" % i)
        out += "K" if i in la["kda_layers"] else "L"
        out += "D" if i <= cfg["first_k_dense_replace"] else "E"
    return out


def param_shapes(cfg):
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    la = cfg["linear_attn_config"]
    h, d = la["num_heads"], la["head_dim"]
    rank_g = d            # the two low-rank gates' rank: see ``assumed``
    hq, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    routed = cfg["published"]["num_experts"]
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs = f * cfg["num_shared_experts"]
    shapes = {"embed_weight": (v, c), "final_norm_gamma": (c,),
              "lm_head_weight": (v, c)}
    for i, kind in enumerate(parts(cfg)):
        l = "layer%d" % i
        shapes[l + "_norm_gamma"] = (c,)
        if kind == "K":
            for n in "qkv":
                shapes[l + "_%s_proj_weight" % n] = (h * d, c)
                shapes[l + "_%s_conv_weight" % n] = (
                    h * d, la["short_conv_kernel_size"])
            shapes.update({
                l + "_f_a_proj_weight": (rank_g, c),
                l + "_f_b_proj_weight": (h * d, rank_g),
                l + "_A_log": (h,), l + "_dt_bias": (h * d,),
                l + "_b_proj_weight": (h, c),
                l + "_g_a_proj_weight": (rank_g, c),
                l + "_g_b_proj_weight": (h * d, rank_g),
                l + "_o_norm_gamma": (d,),
                l + "_o_proj_weight": (c, h * d)})
        elif kind == "L":
            shapes.update({
                l + "_q_proj_weight": (hq * (nope + rope), c),
                l + "_kv_a_proj_weight": (rank + rope, c),
                l + "_kv_a_norm_gamma": (rank,),
                l + "_kv_b_proj_weight": (hq * (nope + dv), rank),
                l + "_o_proj_weight": (c, hq * dv)})
        elif kind == "D":
            w = cfg["intermediate_size"]
            shapes.update({l + "_mlp_gate_weight": (w, c),
                           l + "_mlp_up_weight": (w, c),
                           l + "_mlp_down_weight": (c, w)})
        else:
            shapes.update({
                l + "_router_weight": (routed, c),
                l + "_router_bias": (routed,),
                l + "_experts_gate_weight": (held, f, c),
                l + "_experts_up_weight": (held, f, c),
                l + "_experts_down_weight": (held, c, f),
                l + "_shared_gate_weight": (fs, c),
                l + "_shared_up_weight": (fs, c),
                l + "_shared_down_weight": (c, fs)})
    return shapes


def _rms(x, gamma, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gamma


def _fc(x, w, q):
    return q.back(jnp.dot(q(x), q(w).T, precision=HI))


def _swiglu(u, gate, up, down, q):
    return _fc(jax.nn.silu(_fc(u, gate, q)) * _fc(u, up, q), down, q)


def delta_rule(qq, kk, vv, g, beta):
    """qq, kk (T, H, dk), vv (T, H, dv), g (T, H, dk) the log of the decay,
    beta (T, H), one sequence: o (T, H, dv) by the recurrence over t, in
    checkpointed blocks of time."""
    t = qq.shape[0]
    pad = -t % TIME_BLOCK

    def blocks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((-1, TIME_BLOCK) + x.shape[1:])

    def step(s, inp):                   # s (H, dk, dv); sums, not dots
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, :, None] * s
        seen = jnp.sum(k_t[:, :, None] * s, axis=1)
        s = s + k_t[:, :, None] * (b_t[:, None] * (v_t - seen))[:, None, :]
        return s, jnp.sum(q_t[:, :, None] * s, axis=1)

    @jax.checkpoint
    def block(s, inp):
        return jax.lax.scan(step, s, inp)

    s0 = jnp.zeros(kk.shape[1:] + (vv.shape[-1],), jnp.float32)
    _, o = jax.lax.scan(block, s0, tuple(
        blocks(x) for x in (qq, kk, vv, g, beta)))
    return o.reshape((-1,) + vv.shape[1:])[:t]


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(p, u, l, batch, seq, cfg, q):
    la = cfg["linear_attn_config"]
    h, d, taps = la["num_heads"], la["head_dim"], \
        la["short_conv_kernel_size"]

    def conved(n):
        x = _fc(u, p[l + "_%s_proj_weight" % n], q).reshape(batch, seq, h * d)
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        w = p[l + "_%s_conv_weight" % n]
        x = sum(padded[:, j:j + seq] * w[:, j] for j in range(taps))
        return jax.nn.silu(x).reshape(batch, seq, h, d)
    qq = _l2norm(conved("q")) * d ** -0.5
    kk, vv = _l2norm(conved("k")), conved("v")
    low = _fc(_fc(u, p[l + "_f_a_proj_weight"], q),
              p[l + "_f_b_proj_weight"], q)
    g = -jnp.exp(p[l + "_A_log"])[:, None] * jax.nn.softplus(
        low + p[l + "_dt_bias"]).reshape(batch, seq, h, d)
    beta = jax.nn.sigmoid(_fc(u, p[l + "_b_proj_weight"], q)).reshape(
        batch, seq, h)
    o = q.back(jax.vmap(delta_rule)(q(qq), q(kk), q(vv), g, beta))
    o = _rms(o, p[l + "_o_norm_gamma"], cfg["rms_norm_eps"])
    gate = _fc(_fc(u, p[l + "_g_a_proj_weight"], q),
               p[l + "_g_b_proj_weight"], q)
    return _fc(o.reshape(batch * seq, h * d) * jax.nn.sigmoid(gate),
               p[l + "_o_proj_weight"], q)


def _mla(p, u, l, batch, seq, cfg, q):
    hq, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])

    def heads(x, width):
        return x.reshape(batch, seq, hq, width).transpose(0, 2, 1, 3)
    qq = heads(_fc(u, p[l + "_q_proj_weight"], q), nope + rope)
    kv_a = _fc(u, p[l + "_kv_a_proj_weight"], q)
    latent = _rms(kv_a[:, :rank], p[l + "_kv_a_norm_gamma"],
                  cfg["rms_norm_eps"])
    kv = heads(_fc(latent, p[l + "_kv_b_proj_weight"], q), nope + dv)
    shared = kv_a[:, rank:].reshape(batch, 1, seq, rope)
    kk = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(shared, (batch, hq, seq, rope))],
        axis=-1)
    vv = kv[..., nope:]
    block = min(QUERY_BLOCK, seq)
    pad = -seq % block
    rows = jnp.pad(qq, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        batch, hq, -1, block, nope + rope)

    @jax.checkpoint
    def queries(args):                  # one block of queries, every key
        first, q_b = args
        s = q.back(jnp.einsum("bhtd,bhsd->bhts", q(q_b), q(kk),
                              precision=HI)) * (nope + rope) ** -0.5
        seen = jnp.arange(seq)[None, :] <= (first + jnp.arange(block))[:, None]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return q.back(jnp.einsum("bhts,bhsd->bhtd", q(a), q(vv),
                                 precision=HI))
    o = jax.lax.map(queries, (jnp.arange(rows.shape[2]) * block,
                              jnp.moveaxis(rows, 2, 0)))
    o = jnp.moveaxis(o, 0, 2).reshape(batch, hq, -1, dv)[:, :, :seq]
    return _fc(o.transpose(0, 2, 1, 3).reshape(batch * seq, hq * dv),
               p[l + "_o_proj_weight"], q)


def route(u, w_r, bias, k, scale):
    """(chosen (N, k), weights (N, k)) of the sigmoid router."""
    s = jax.nn.sigmoid(jnp.dot(u, w_r.T, precision=HI))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    w = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, w / (w.sum(axis=1, keepdims=True) + 1e-20) * scale


def _experts(p, u, l, cfg, q):
    first = cfg.get("deployment", {}).get("first_expert", 0)
    chosen, w = route(u, p[l + "_router_weight"], p[l + "_router_bias"],
                      cfg["num_experts_per_token"],
                      cfg["routed_scaling_factor"])

    def held_expert(out, args):
        e, gate_e, up_e, down_e = args
        # the token's routing weight for this expert, 0 where not chosen
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=1)
        return out + w_e[:, None] * _swiglu(u, gate_e, up_e, down_e, q), None
    held = p[l + "_experts_up_weight"].shape[0]
    out, _ = jax.lax.scan(held_expert, jnp.zeros_like(u), (
        jnp.arange(held), p[l + "_experts_gate_weight"],
        p[l + "_experts_up_weight"], p[l + "_experts_down_weight"]))
    if l + "_shared_up_weight" in p:
        out = out + _swiglu(u, p[l + "_shared_gate_weight"],
                            p[l + "_shared_up_weight"],
                            p[l + "_shared_down_weight"], q)
    return out


def forward(params, data, cfg, q):
    """Logits (B*T, vocabulary)."""
    p = params
    batch, seq = data.shape
    eps = cfg["rms_norm_eps"]
    x = p["embed_weight"][data.astype(jnp.int32)].reshape(
        batch * seq, cfg["hidden_size"])
    for i, kind in enumerate(parts(cfg)):
        l = "layer%d" % i

        def part(sub, x, l=l, kind=kind):
            u = _rms(x, sub[l + "_norm_gamma"], eps)
            if kind == "K":
                return x + _kda(sub, u, l, batch, seq, cfg, q)
            if kind == "L":
                return x + _mla(sub, u, l, batch, seq, cfg, q)
            if kind == "E":
                return x + _experts(sub, u, l, cfg, q)
            return x + _swiglu(u, sub[l + "_mlp_gate_weight"],
                               sub[l + "_mlp_up_weight"],
                               sub[l + "_mlp_down_weight"], q)
        x = jax.checkpoint(part)(
            {k: v for k, v in p.items() if k.startswith(l + "_")}, x)
    x = _rms(x, p["final_norm_gamma"], eps)
    return _fc(x, p["lm_head_weight"], q)


def mean_loss(params, data, label, cfg, q):
    """Mean next-token cross-entropy over the batch's B x T positions."""
    logp = jax.nn.log_softmax(forward(params, data, cfg, q), axis=-1)
    picked = jnp.take_along_axis(
        logp, label.astype(jnp.int32).reshape(-1, 1), axis=1)
    return -picked.mean()
