"""Required FLOPs of one training step of the ``transformer`` family: the
matrix products of every block and of the output head, and causal attention,
forward and backward, nothing recomputed.  Embedding look-ups, LayerNorm,
softmax and the optimizer are left out."""


def block_matmul_flops(tokens, hidden, ffn):
    """Forward FLOPs of one block's four projections."""
    return 2 * tokens * hidden * (3 * hidden + hidden + 2 * ffn)


def attention_flops(batch, heads, seq, head_dim, causal=True):
    """Forward FLOPs of QK^T and PV; a causal mask needs half."""
    full = 2 * (2 * batch * heads * seq * seq * head_dim)
    return full // 2 if causal else full


def forward_flops(cfg, batch):
    seq = cfg["max_position_embeddings"]
    tokens = batch * seq
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    per_block = block_matmul_flops(tokens, hidden, cfg["ffn_dim"]) \
        + attention_flops(batch, heads, seq, hidden // heads)
    return cfg["num_hidden_layers"] * per_block \
        + 2 * tokens * hidden * cfg["vocab_size"]


def step_flops(cfg, batch):
    """The backward of a product is two products (input and weight
    gradient; for attention dQ, dK, dV and dP against the forward's two)."""
    return 3 * forward_flops(cfg, batch)


def items_per_step(cfg, batch):
    return batch * cfg["max_position_embeddings"]
