"""Required FLOPs and bytes of the convolutions-and-rule of ONE Kimi Delta
Attention mixer, forward and backward, from the algorithm's shapes, whatever
implements it.

FLOPs a token, forward: the three depthwise causal convolutions, 2 x taps a
channel of q, k and v; the gated delta rule as its recurrence requires, 7 H
d_k d_v (``flops/kda_lm.py``).  The backward is twice the forward.  Bytes:
forward reads q, k and v (before their convolutions), the decay's gate and
beta's logits and writes o; the backward reads them and ``do`` again and
writes the five gradients; each once, in ``itemsize`` bytes.  A (T, state)
or (T / L, L, L) intermediate that an implementation keeps in memory is its
own cost, not the algorithm's."""


def widths(cfg):
    la = cfg["linear_attn_config"]
    h, d = la["num_heads"], la["head_dim"]
    return {"inner": h * d, "heads": h, "state": h * d * d,
            "taps": la["short_conv_kernel_size"]}


def kda_flops(cfg, tokens):
    w = widths(cfg)
    forward = 2 * w["taps"] * 3 * w["inner"] + 7 * w["state"]
    return 3 * tokens * forward


def kda_bytes(cfg, tokens, itemsize=2):
    w = widths(cfg)
    ins = 4 * w["inner"] + w["heads"]               # q, k, v, gate; beta
    forward = ins + w["inner"]                      # + o written
    backward = ins + w["inner"] + ins               # inputs, do; grads written
    return tokens * itemsize * (forward + backward)


def least_seconds(cfg, tokens, peak_flops, peak_bytes, itemsize=2):
    """(seconds, bound) of one mixer's convolutions and rule, forward and
    backward: the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s."""
    by_flops = kda_flops(cfg, tokens) / peak_flops
    by_bytes = kda_bytes(cfg, tokens, itemsize) / peak_bytes
    return max(by_flops, by_bytes), \
        "flops" if by_flops >= by_bytes else "bytes"
