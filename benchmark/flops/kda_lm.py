"""Required FLOPs of one training step of the ``kda_lm`` family: layers of a
token mixer and a feed-forward by the configuration's published keys
(``reference/kda_lm.py`` ``parts``: ``K`` a Kimi Delta Attention mixer, ``L``
latent attention, ``D`` a dense gated MLP, ``E`` an expert layer of gated
experts), an embedding and an untied head.  Counted from the algorithm,
whatever implements it; a multiply-add is two FLOPs; forward and a backward
of twice the forward, nothing recomputed.  Norms, activations, gates'
sigmoids and softplus, the softmax, the routing's sort and the optimizer are
left out.

  K   the q, k, v and output projections, the two low-rank gates and beta;
      the three depthwise convolutions (taps a channel); the rule as its
      recurrence requires, 7 FLOPs a state element: the decay (1), ``k^T S``
      (2), the rank-one update (2), ``S^T q`` (2); not what a chunked form
      spends on its (L, L) blocks and its solve
  L   q, the latent and shared key, the keys and values up from the latent,
      the output projection; ``QK^T`` at the query/key width and ``PV`` at
      the value width under a causal mask
  D   three products of C x ``intermediate_size``
  E   the router's scores; the shared expert on every token; the routed
      experts held here at the EXPECTED assignments, tokens x experts a
      token x held / routed, three products each
"""
from benchmark.reference.kda_lm import parts


def kda_flops(cfg):
    """Forward FLOPs a token of one ``K`` part."""
    c, la = cfg["hidden_size"], cfg["linear_attn_config"]
    h, d = la["num_heads"], la["head_dim"]
    inner, rank = h * d, d
    proj = 2 * c * inner * 4 + 2 * 2 * (c * rank + rank * inner) + 2 * c * h
    conv = 2 * la["short_conv_kernel_size"] * 3 * inner
    return proj + conv + 7 * h * d * d


def mla_flops(cfg, seq):
    """Forward FLOPs a token of one ``L`` part at sequence length ``seq``: a
    causal mask needs half of the (T, T) products."""
    c, hq, rank = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["kv_lora_rank"])
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    proj = 2 * c * hq * (nope + rope) + 2 * c * (rank + rope) \
        + 2 * rank * hq * (nope + dv) + 2 * hq * dv * c
    return proj + 2 * seq * hq * (nope + rope + dv) // 2


def dense_flops(cfg):
    return 6 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_flops(cfg):
    """Forward FLOPs a token of one ``E`` part on this chip's share."""
    c, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    routed = cfg["published"]["num_experts"]
    per_token = cfg["num_experts_per_token"] * cfg["num_experts"] / routed
    return 2 * c * routed + cfg["num_shared_experts"] * 6 * c * f \
        + per_token * 6 * c * f


def forward_flops(cfg, batch):
    seq = cfg["max_position_embeddings"]
    per_part = {"K": kda_flops(cfg), "L": mla_flops(cfg, seq),
                "D": dense_flops(cfg), "E": expert_flops(cfg)}
    per_token = sum(per_part[kind] for kind in parts(cfg)) \
        + 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return batch * seq * per_token


def step_flops(cfg, batch):
    return 3 * forward_flops(cfg, batch)


def items_per_step(cfg, batch):
    return batch * cfg["max_position_embeddings"]
