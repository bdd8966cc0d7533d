"""Required FLOPs and bytes of the routed GATED experts of ONE expert layer
on this chip's share, forward and backward, at a COUNTED number of
assignments (the program's counter: token-expert pairs that landed on an
expert held here).

An assignment is three products, gate and up (C -> F) and down (F -> C): 6 C
F FLOPs forward, twice that backward.  Bytes: the held experts' three
matrices read once forward and once backward, their gradients written once,
in ``itemsize`` bytes; each assignment's input and output row read and
written once a pass."""


def routed_flops(cfg, assignments):
    return 3 * assignments * 6 * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"]


def routed_bytes(cfg, assignments, itemsize=2):
    c, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["num_experts"] * 3 * c * f
    rows = assignments * 2 * c
    return itemsize * (3 * weights + 3 * rows)


def least_seconds(cfg, assignments, peak_flops, peak_bytes, itemsize=2):
    by_flops = routed_flops(cfg, assignments) / peak_flops
    by_bytes = routed_bytes(cfg, assignments, itemsize) / peak_bytes
    return max(by_flops, by_bytes), \
        "flops" if by_flops >= by_bytes else "bytes"
