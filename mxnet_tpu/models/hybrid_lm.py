"""Hybrid decoder language model built from a layer pattern string (NEW
capability): state-space mixers, routed-expert layers and grouped-query
attention in one stack, as the hybrid Mamba-2 / expert / attention models
lay them out.  Every layer is ONE part on the residual stream,

    x <- x + part(RMSNorm(x))

and the pattern names the parts: ``M`` a Mamba-2 mixer, ``E`` an expert layer
(routed experts beside a shared expert), ``*`` causal grouped-query attention
without positions (the mixers carry them).  After the last layer a final
RMSNorm and an untied head.

Layout: tokens (B, T) -> embedding (B*T, C) -> layers -> logits (B*T, vocab)
-> SoftmaxOutput.  Each part's nodes run under a ``jax.named_scope``
(``mamba_conv``, ``mamba_ssd``, ``moe_route``, ``moe_experts``, ``moe_shared``,
``attention``) so that a device trace names its layer.

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
        y = sym.moe_experts(
            x, route[0], route[1],
            sym.Variable("%s_experts_up_weight" % name),
            sym.Variable("%s_experts_down_weight" % name),
            num_experts=hp["num_experts"], experts_held=hp["experts_held"],
            first_expert=hp["first_expert"], num_hidden=hp["expert_hidden"],
            act_type="relu2", name="%s_experts" % name)
    with _scope("moe_shared"):
        h = sym.Activation(_fc(x, hp["shared_hidden"], "%s_shared_up" % name),
                           act_type="relu2")
        return y + _fc(h, hp["num_hidden"], "%s_shared_down" % name)


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
               routed_scale=1.0, eps=1e-5):
    """Causal LM symbol; data (B, T) int tokens, label (B, T).  Leaves:
    ``embed_weight``, ``layer<i>_norm_gamma`` and the part's own
    (``_in_proj_weight``, ``_conv_weight`` / ``_conv_bias``, ``_A_log``,
    ``_D_gamma``, ``_dt_bias``, ``_ssm_norm_gamma``, ``_out_proj_weight``;
    ``_router_weight`` / ``_router_bias``, ``_experts_up_weight`` (held, F, C),
    ``_experts_down_weight`` (held, C, F), ``_shared_up_weight`` /
    ``_shared_down_weight``; ``_q_weight``, ``_k_weight``, ``_v_weight``,
    ``_o_proj_weight``), ``final_norm_gamma``, ``lm_head_weight``."""
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
              routed_scale=routed_scale, eps=eps)
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
        elif kind == "E":
            part = _experts(h, name, hp)
        elif kind == "*":
            part = _attention(h, name, seq_len, hp)
        else:
            raise ValueError("hybrid_lm: unknown layer kind %r in pattern %r"
                             % (kind, pattern))
        x = x + part
    x = sym.RMSNorm(x, eps=eps, name="final_norm")
    logits = _fc(x, vocab_size, "lm_head")
    label = sym.Reshape(label, shape=(-1,))
    return sym.SoftmaxOutput(logits, label, name="softmax")
