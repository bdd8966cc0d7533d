"""Required FLOPs of one training step of the ``hybrid_lm`` family: a stack
of single-part layers by a pattern string (``M`` a Mamba-2 mixer, ``E`` an
expert layer, ``*`` grouped-query attention), an embedding and an untied
head.  Counted from the algorithm, whatever implements it; a multiply-add is
two FLOPs; forward and a backward of twice the forward, nothing recomputed.
Norms, activations, gates, the softmax, the routing's sort and the optimizer
are left out.

  M   in_proj and out_proj; the depthwise convolution (kernel taps a channel);
      the scan as its recurrence requires: a multiply-add a state element for
      the update ``S <- a S + (dt x) B^T`` and one for ``y = S C``, 4 H P N a
      token, not what a chunked form spends on its (L, L) blocks
  E   the router's scores; the shared expert on every token; the routed
      experts held here at the EXPECTED assignments, tokens x experts a
      token x held / routed, two products each
  *   q, k, v and output projections; QK^T and PV under a causal mask
"""


def mamba_flops(cfg):
    """Forward FLOPs a token of one ``M`` layer."""
    c = cfg["hidden_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, g = cfg["ssm_state_size"], cfg["n_groups"]
    inner, conv = h * p, h * p + 2 * g * n
    proj = 2 * c * (inner + conv + h) + 2 * inner * c
    return proj + 2 * conv * cfg["conv_kernel"] + 4 * h * p * n


def expert_flops(cfg):
    """Forward FLOPs a token of one ``E`` layer on this chip's share."""
    c = cfg["hidden_size"]
    routed = cfg["published"]["n_routed_experts"]
    held = cfg["n_routed_experts"]
    per_token = cfg["num_experts_per_tok"] * held / routed
    return (2 * c * routed
            + cfg["n_shared_experts"] * 4 * c
            * cfg["moe_shared_expert_intermediate_size"]
            + per_token * 4 * c * cfg["moe_intermediate_size"])


def attention_flops(cfg, seq):
    """Forward FLOPs a token of one ``*`` layer at sequence length ``seq``:
    a causal mask needs half of the two (T, T, D) products."""
    c, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    proj = 2 * c * d * (hq + 2 * hkv) + 2 * hq * d * c
    return proj + 2 * 2 * seq * d * hq // 2


def forward_flops(cfg, batch):
    seq = cfg["max_position_embeddings"]
    per_layer = {"M": mamba_flops(cfg), "E": expert_flops(cfg),
                 "*": attention_flops(cfg, seq)}
    per_token = sum(per_layer[kind] for kind in cfg["hybrid_override_pattern"]) \
        + 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return batch * seq * per_token


def step_flops(cfg, batch):
    return 3 * forward_flops(cfg, batch)


def items_per_step(cfg, batch):
    return batch * cfg["max_position_embeddings"]
