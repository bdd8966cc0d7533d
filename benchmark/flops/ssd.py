"""Required FLOPs and bytes of the convolution-and-scan of ONE Mamba-2 layer,
forward and backward, from the algorithm's shapes, whatever implements it.

FLOPs a token, forward: the depthwise causal convolution, 2 x taps a channel
of ``xBC``; the recurrence, 4 H P N (``flops/hybrid_lm.py``).  The backward is
twice the forward.  Bytes: forward reads ``xBC`` (x, B, C before the
convolution), ``dt`` and the gate ``z`` and writes ``y``; the backward reads
them and ``dy`` again and writes the three gradients; each once, in
``itemsize`` bytes.  A (T, state) intermediate that an implementation keeps
in memory is its own cost, not the algorithm's."""


def widths(cfg):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, g = cfg["ssm_state_size"], cfg["n_groups"]
    return {"inner": h * p, "xbc": h * p + 2 * g * n, "heads": h,
            "state": h * p * n}


def ssd_flops(cfg, tokens):
    w = widths(cfg)
    forward = 2 * w["xbc"] * cfg["conv_kernel"] + 4 * w["state"]
    return 3 * tokens * forward


def ssd_bytes(cfg, tokens, itemsize=2):
    w = widths(cfg)
    ins = w["xbc"] + w["heads"] + w["inner"]        # xBC, dt, z
    forward = ins + w["inner"]                      # + y written
    backward = ins + w["inner"] + ins               # inputs, dy; grads written
    return tokens * itemsize * (forward + backward)


def least_seconds(cfg, tokens, peak_flops, peak_bytes, itemsize=2):
    """(seconds, bound) of one layer's convolution and scan, forward and
    backward: the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s."""
    by_flops = ssd_flops(cfg, tokens) / peak_flops
    by_bytes = ssd_bytes(cfg, tokens, itemsize) / peak_bytes
    return max(by_flops, by_bytes), \
        "flops" if by_flops >= by_bytes else "bytes"
