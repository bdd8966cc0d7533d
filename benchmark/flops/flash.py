"""Required FLOPs and bytes of the three attention kernels for (B*H, T, D,
causal), counted from the algorithm, whatever implements it: blocked
attention that never stores the (T, T) scores, so each backward kernel forms
the scores it needs again from Q, K and the saved log-sum-exp.

  fwd   S = QK^T, O = PV                              2 products
  dq    S = QK^T, dP = dO V^T, dQ = dS K              3 products
  dkv   S = QK^T, dP = dO V^T, dV = P^T dO, dK = dS^T Q   4 products

A product is 2*T*T*D FLOPs a head, half of that under a causal mask.  Bytes
are the arrays each kernel must read and write once, in ``itemsize`` bytes,
and the two f32 row vectors (log-sum-exp, delta)."""

PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}
ARRAYS = {"fwd": 4, "dq": 5, "dkv": 6}       # (T, D) arrays read + written
ROWS = {"fwd": 1, "dq": 2, "dkv": 2}         # (T, 1) f32 vectors


def flash_flops(kernel, bh, t, d, causal):
    full = PRODUCTS[kernel] * 2 * bh * t * t * d
    return full // 2 if causal else full


def flash_bytes(kernel, bh, t, d, itemsize=2):
    return ARRAYS[kernel] * bh * t * d * itemsize + ROWS[kernel] * bh * t * 4


def least_seconds(kernel, bh, t, d, causal, peak_flops, peak_bytes,
                  itemsize=2):
    """The roofline: the larger of FLOPs over peak FLOP/s and bytes over
    peak bytes/s, and which of the two bounds it."""
    by_flops = flash_flops(kernel, bh, t, d, causal) / peak_flops
    by_bytes = flash_bytes(kernel, bh, t, d, itemsize) / peak_bytes
    return max(by_flops, by_bytes), \
        "flops" if by_flops >= by_bytes else "bytes"
