"""Hybrid decoder language model built from a layer pattern string (NEW
capability): state-space or linear-attention mixers, routed-expert layers,
dense MLPs and softmax attention (grouped-query or latent) in one stack, as
the hybrid models lay them out.  The residual stream takes ONE part a
letter,

    x <- x + part(RMSNorm(x))

and the pattern names the parts, each on a pre-norm of its own.  A model
whose layers are one part each (a Nemotron-H tower) is one letter a layer;
a model whose layers are a token mixer and then a feed-forward (Kimi Linear)
is two letters a layer, ``KE`` or ``LD``:

  ``M``  a Mamba-2 mixer
  ``K``  a Kimi Delta Attention mixer: q, k, v projections, each through a
         short causal convolution and SiLU; the gated delta rule with a decay
         for every key channel from a low-rank gate (``ops/kda.py``); a
         per-head RMSNorm times a low-rank sigmoid gate; an output projection
  ``*``  causal grouped-query attention without positions
  ``L``  causal latent attention (MLA) without positions and without query
         compression: keys and values come up from one normed latent of
         ``kv_lora_rank``, every key head carries the same
         ``qk_rope_head_dim`` further channels straight from the input (no
         rotation is applied to them), value heads of ``v_head_dim`` beside
         query/key heads of ``qk_nope_head_dim + qk_rope_head_dim``
  ``E``  an expert layer: routed experts beside a shared expert
  ``D``  a dense MLP of ``mlp_hidden``

``mlp_act`` and ``mlp_gated`` say what an MLP is, for the routed experts, the
shared expert and ``D`` alike: ``down(act(up u))``, or gated
``down(act(gate u) * up u)`` (``silu``: SwiGLU).  After the last part a final
RMSNorm and an untied head.

Layout: tokens (B, T) -> embedding (B*T, C) -> parts -> logits (B*T, vocab)
-> SoftmaxOutput.  Each part's nodes run under a ``jax.named_scope``
(``mamba_conv``, ``mamba_ssd``, ``kda_conv``, ``kda_scan``, ``kda_norm``,
``moe_route``, ``moe_experts``, ``moe_shared``, ``mlp_dense``, ``attention``,
``mla_attention``) so that a device trace names its layer.

The expert layer holds ``experts_held`` of ``num_experts`` routed experts from
``first_expert`` on (one chip's share under expert parallelism; all of them
by default) and routes over all: ``ops/moe.py``.
"""
from .. import initializer as init
from .. import symbol as sym
from ..attribute import AttrScope


def _scope(name):
    return AttrScope(__scope__=name)


def _fc(x, num_hidden, name):
    return sym.FullyConnected(x, num_hidden=num_hidden, no_bias=True,
                              name=name)


def _mamba(x, name, seq_len, hp):
    """in_proj -> (gate z | xBC | dt); xBC through the causal convolution
    and SiLU; the scan; the gated group norm, gate first; out_proj."""
    heads, head_dim = hp["ssm_heads"], hp["ssm_head_dim"]
    groups, state = hp["ssm_groups"], hp["ssm_state"]
    inner, bc = heads * head_dim, groups * state
    proj = _fc(x, 2 * inner + 2 * bc + heads, "%s_in_proj" % name)
    proj = sym.Reshape(proj, shape=(-1, seq_len, 2 * inner + 2 * bc + heads))
    z = sym.slice_axis(proj, axis=2, begin=0, end=inner)
    xbc = sym.slice_axis(proj, axis=2, begin=inner, end=2 * inner + 2 * bc)
    dt = sym.slice_axis(proj, axis=2, begin=2 * inner + 2 * bc,
                        end=2 * inner + 2 * bc + heads)
    with _scope("mamba_conv"):
        xbc = sym.causal_conv1d(xbc, kernel=hp["conv_kernel"],
                                act_type="silu", name="%s_conv" % name)
    with _scope("mamba_ssd"):
        y = sym.ssm_scan(
            xbc, dt,
            sym.Variable("%s_A_log" % name, init=init.LogOfUniform(1, 16)),
            sym.Variable("%s_D_gamma" % name),
            sym.Variable("%s_dt_bias" % name,
                         init=init.InverseSoftplusLogUniform(0.001, 0.1)),
            num_heads=heads, head_dim=head_dim, num_groups=groups,
            chunk_size=hp["chunk_size"], name="%s_ssd" % name)
        y = sym.RMSNorm(y, sym.Variable("%s_ssm_norm_gamma" % name), z,
                        gated=True, eps=hp["eps"], num_groups=groups,
                        name="%s_ssm_norm" % name)
    y = sym.Reshape(y, shape=(-1, inner))
    return _fc(y, hp["num_hidden"], "%s_out_proj" % name)


def _kda(x, name, seq_len, hp):
    """q, k and v projections, each through its own 4-tap convolution and
    SiLU; the rule with its gates; the per-head norm times the output gate;
    o_proj."""
    heads, d = hp["kda_heads"], hp["kda_head_dim"]
    inner, rank = heads * d, hp["kda_gate_rank"]

    def rows(t, width):                       # (B*T, width) -> (B, T, width)
        return sym.Reshape(t, shape=(-1, seq_len, width))
    qkv = [rows(_fc(x, inner, "%s_%s_proj" % (name, n)), inner)
           for n in "qkv"]
    with _scope("kda_conv"):
        qkv = [sym.causal_conv1d(t, kernel=hp["conv_kernel"], no_bias=True,
                                 act_type="silu",
                                 name="%s_%s_conv" % (name, n))
               for t, n in zip(qkv, "qkv")]
    decay = _fc(_fc(x, rank, "%s_f_a_proj" % name), inner,
                "%s_f_b_proj" % name)
    beta = _fc(x, heads, "%s_b_proj" % name)
    gate = _fc(_fc(x, rank, "%s_g_a_proj" % name), inner,
               "%s_g_b_proj" % name)
    with _scope("kda_scan"):
        o = sym.kda_scan(
            *qkv, rows(decay, inner), rows(beta, heads),
            sym.Variable("%s_A_log" % name, init=init.LogOfUniform(1, 16)),
            sym.Variable("%s_dt_bias" % name,
                         init=init.InverseSoftplusLogUniform(0.001, 0.1)),
            num_heads=heads, chunk_size=hp["kda_chunk"],
            name="%s_kda" % name)
    with _scope("kda_norm"):
        o = sym.RMSNorm(sym.Reshape(o, shape=(-1, d)), eps=hp["eps"],
                        name="%s_o_norm" % name)
        o = sym.Reshape(o, shape=(-1, inner)) * sym.sigmoid(gate)
    return _fc(o, hp["num_hidden"], "%s_o_proj" % name)


def _mlp(x, hidden, name, hp):
    """``down(act(up x))``, or gated ``down(act(gate x) * up x)``."""
    h = _fc(x, hidden, "%s_up" % name)
    if hp["mlp_gated"]:
        h = sym.Activation(_fc(x, hidden, "%s_gate" % name),
                           act_type=hp["mlp_act"]) * h
    else:
        h = sym.Activation(h, act_type=hp["mlp_act"])
    return _fc(h, hp["num_hidden"], "%s_down" % name)


def _experts(x, name, hp):
    """The router over all experts, the held experts' part of the routed
    result, and the shared expert on every token."""
    with _scope("moe_route"):
        route = sym.moe_router(
            x, sym.Variable("%s_router_weight" % name),
            sym.Variable("%s_router_bias" % name),
            num_experts=hp["num_experts"], top_k=hp["experts_per_token"],
            scale=hp["routed_scale"], name="%s_router" % name)
    with _scope("moe_experts"):
        mats = [sym.Variable("%s_experts_%s_weight" % (name, n))
                for n in ("up", "down") + ("gate",) * hp["mlp_gated"]]
        y = sym.moe_experts(
            x, route[0], route[1], *mats,
            num_experts=hp["num_experts"], experts_held=hp["experts_held"],
            first_expert=hp["first_expert"], num_hidden=hp["expert_hidden"],
            act_type=hp["mlp_act"], gated=hp["mlp_gated"],
            name="%s_experts" % name)
    with _scope("moe_shared"):
        return y + _mlp(x, hp["shared_hidden"], "%s_shared" % name, hp)


def _latent_attention(x, name, seq_len, hp):
    heads, rank = hp["num_heads"], hp["kv_lora_rank"]
    nope, rope, dv = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
                      hp["v_head_dim"])
    with _scope("mla_attention"):
        def heads_first(t, width):       # (B*T, H*width) -> (B, H, T, width)
            t = sym.Reshape(t, shape=(-1, seq_len, heads, width))
            return sym.transpose(t, axes=(0, 2, 1, 3))
        q = heads_first(_fc(x, heads * (nope + rope), "%s_q_proj" % name),
                        nope + rope)
        kv_a = _fc(x, rank + rope, "%s_kv_a_proj" % name)
        latent = sym.RMSNorm(
            sym.slice_axis(kv_a, axis=1, begin=0, end=rank), eps=hp["eps"],
            name="%s_kv_a_norm" % name)
        kv = heads_first(_fc(latent, heads * (nope + dv),
                             "%s_kv_b_proj" % name), nope + dv)
        # the part of a key that bypasses the latent is one for all heads
        k_shared = sym.Reshape(
            sym.slice_axis(kv_a, axis=1, begin=rank, end=rank + rope),
            shape=(-1, 1, seq_len, rope))
        k = sym.Concat(
            sym.slice_axis(kv, axis=3, begin=0, end=nope),
            sym.broadcast_axis(k_shared, axis=1, size=heads), dim=3)
        v = sym.slice_axis(kv, axis=3, begin=nope, end=nope + dv)
        att = sym.dot_product_attention(q, k, v, causal=True,
                                        name="%s_attn" % name)
        att = sym.transpose(att, axes=(0, 2, 1, 3))
        att = sym.Reshape(att, shape=(-1, heads * dv))
        return _fc(att, hp["num_hidden"], "%s_o_proj" % name)


def _attention(x, name, seq_len, hp):
    heads, kv, d = hp["num_heads"], hp["num_kv_heads"], hp["head_dim"]
    with _scope("attention"):
        def split(t, n):                      # (B*T, n*D) -> (B, n, T, D)
            t = sym.Reshape(t, shape=(-1, seq_len, n, d))
            return sym.transpose(t, axes=(0, 2, 1, 3))
        q = split(_fc(x, heads * d, "%s_q" % name), heads)
        k = split(_fc(x, kv * d, "%s_k" % name), kv)
        v = split(_fc(x, kv * d, "%s_v" % name), kv)
        att = sym.dot_product_attention(q, k, v, causal=True,
                                        name="%s_attn" % name)
        att = sym.transpose(att, axes=(0, 2, 1, 3))
        att = sym.Reshape(att, shape=(-1, heads * d))
        return _fc(att, hp["num_hidden"], "%s_o_proj" % name)


def get_symbol(pattern="M*E", vocab_size=1000, seq_len=128, num_hidden=128,
               ssm_heads=4, ssm_head_dim=32, ssm_groups=2, ssm_state=16,
               conv_kernel=4, chunk_size=128,
               num_heads=4, num_kv_heads=2, head_dim=32,
               num_experts=8, experts_held=None, first_expert=0,
               experts_per_token=2, expert_hidden=128, shared_hidden=256,
               routed_scale=1.0, eps=1e-5,
               kda_heads=4, kda_head_dim=32, kda_gate_rank=None, kda_chunk=64,
               kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
               v_head_dim=32, mlp_hidden=512, mlp_act="relu2",
               mlp_gated=False):
    """Causal LM symbol; data (B, T) int tokens, label (B, T).  Leaves:
    ``embed_weight``, ``layer<i>_norm_gamma`` (i counts the pattern's
    letters) and the part's own: ``M`` ``_in_proj_weight``, ``_conv_weight``
    / ``_conv_bias``, ``_A_log``, ``_D_gamma``, ``_dt_bias``,
    ``_ssm_norm_gamma``, ``_out_proj_weight``; ``K`` ``_q_`` / ``_k_`` /
    ``_v_proj_weight``, ``_q_`` / ``_k_`` / ``_v_conv_weight`` (no bias),
    ``_f_a_`` / ``_f_b_proj_weight`` (the decay's low-rank gate, of
    ``kda_gate_rank``, the head size by default), ``_A_log`` (heads,),
    ``_dt_bias`` (heads x head size,), ``_b_proj_weight``, ``_g_a_`` /
    ``_g_b_proj_weight``, ``_o_norm_gamma`` (head size,), ``_o_proj_weight``;
    ``E`` ``_router_weight`` / ``_router_bias``, ``_experts_up_weight``
    (held, F, C), ``_experts_down_weight`` (held, C, F), ``_shared_up_weight``
    / ``_shared_down_weight``, and gated ``_experts_gate_weight`` (held, F,
    C) and ``_shared_gate_weight``; ``D`` ``_mlp_up_weight`` /
    ``_mlp_down_weight`` and gated ``_mlp_gate_weight``; ``*`` ``_q_weight``,
    ``_k_weight``, ``_v_weight``, ``_o_proj_weight``; ``L``
    ``_q_proj_weight``, ``_kv_a_proj_weight`` (rank + shared key channels,
    C), ``_kv_a_norm_gamma``, ``_kv_b_proj_weight``, ``_o_proj_weight``;
    ``final_norm_gamma``, ``lm_head_weight``."""
    hp = dict(num_hidden=num_hidden, ssm_heads=ssm_heads,
              ssm_head_dim=ssm_head_dim, ssm_groups=ssm_groups,
              ssm_state=ssm_state, conv_kernel=conv_kernel,
              chunk_size=chunk_size, num_heads=num_heads,
              num_kv_heads=num_kv_heads, head_dim=head_dim,
              num_experts=num_experts,
              experts_held=num_experts if experts_held is None
              else experts_held,
              first_expert=first_expert, experts_per_token=experts_per_token,
              expert_hidden=expert_hidden, shared_hidden=shared_hidden,
              routed_scale=routed_scale, eps=eps,
              kda_heads=kda_heads, kda_head_dim=kda_head_dim,
              kda_gate_rank=kda_gate_rank or kda_head_dim,
              kda_chunk=kda_chunk, kv_lora_rank=kv_lora_rank,
              qk_nope_head_dim=qk_nope_head_dim,
              qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
              mlp_act=mlp_act, mlp_gated=bool(mlp_gated))
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, input_dim=vocab_size, output_dim=num_hidden,
                      name="embed")
    x = sym.Reshape(x, shape=(-1, num_hidden))
    for i, kind in enumerate(pattern):
        name = "layer%d" % i
        h = sym.RMSNorm(x, eps=eps, name="%s_norm" % name)
        if kind == "M":
            part = _mamba(h, name, seq_len, hp)
        elif kind == "K":
            part = _kda(h, name, seq_len, hp)
        elif kind == "E":
            part = _experts(h, name, hp)
        elif kind == "D":
            with _scope("mlp_dense"):
                part = _mlp(h, mlp_hidden, "%s_mlp" % name, hp)
        elif kind == "*":
            part = _attention(h, name, seq_len, hp)
        elif kind == "L":
            part = _latent_attention(h, name, seq_len, hp)
        else:
            raise ValueError("hybrid_lm: unknown layer kind %r in pattern %r"
                             % (kind, pattern))
        x = x + part
    x = sym.RMSNorm(x, eps=eps, name="final_norm")
    logits = _fc(x, vocab_size, "lm_head")
    label = sym.Reshape(label, shape=(-1,))
    return sym.SoftmaxOutput(logits, label, name="softmax")
