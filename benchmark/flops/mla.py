"""Required FLOPs and bytes of the three attention kernels of ONE latent
attention layer, whose value heads (``v_head_dim``) are narrower than its
query/key heads (``qk_nope_head_dim + qk_rope_head_dim``): ``flops/flash.py``'s
products and arrays, each at the width it really has, whatever implements
it (a kernel that pads the value to the key's width does no more required
work, so padding reads as a lower share).

  fwd   S = QK^T (Dk), O = PV (Dv)
  dq    S = QK^T (Dk), dP = dO V^T (Dv), dQ = dS K (Dk)
  dkv   S = QK^T (Dk), dP = dO V^T (Dv), dV = P^T dO (Dv), dK = dS^T Q (Dk)

A product is 2 T T D FLOPs a head, half of that under the causal mask.
Bytes: the (T, D) arrays each kernel reads and writes once (fwd q, k | v, o;
dq q, k, dq | v, do; dkv q, k, dk | v, do, dv) and the f32 row vectors."""
from benchmark.flops import flash

# (T, T, D) products and (T, D) arrays of each kernel at (Dk, Dv)
PRODUCTS = {"fwd": (1, 1), "dq": (2, 1), "dkv": (2, 2)}
ARRAYS = {"fwd": (2, 2), "dq": (3, 2), "dkv": (3, 3)}


def widths(cfg):
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def kernel_flops(kernel, bh, t, dk, dv):
    """Under the causal mask."""
    nk, nv = PRODUCTS[kernel]
    return 2 * bh * t * t * (nk * dk + nv * dv) // 2


def kernel_bytes(kernel, bh, t, dk, dv, itemsize=2):
    nk, nv = ARRAYS[kernel]
    return bh * t * (nk * dk + nv * dv) * itemsize \
        + flash.ROWS[kernel] * bh * t * 4


def least_seconds(cfg, batch, peak_flops, peak_bytes, itemsize=2):
    """Seconds of one layer's three kernels together, each at the larger of
    its FLOPs over peak FLOP/s and its bytes over peak bytes/s."""
    bh = batch * cfg["num_attention_heads"]
    t = cfg["max_position_embeddings"]
    dk, dv = widths(cfg)
    return sum(max(kernel_flops(k, bh, t, dk, dv) / peak_flops,
                   kernel_bytes(k, bh, t, dk, dv, itemsize) / peak_bytes)
               for k in PRODUCTS)
