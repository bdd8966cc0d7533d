"""Layer: latent attention (``models/hybrid_lm.py`` ``L`` parts through
``dot_product_attention``), how near the flash kernels run to their roofline
at its two head widths.  A kernel's device operation is named after its
``pallas_call``'s ``name`` (``mxtpu_flash_fwd`` / ``_dq`` / ``_dkv``), so an
operation counts where its name holds ``mxtpu_flash_``.  A program whose
latent attention does not reach the kernels (or has none) reads as
nothing."""
from benchmark.flops import mla as mla_flops
from benchmark.reference.kda_lm import parts

PREFIX = "mxtpu_flash_"


def mla_flash_roofline(ctx):
    """Least seconds of the three kernels of the step's latent-attention
    layers (``flops/mla.py``: QK at the key's width, PV at the value's,
    causal) over the device seconds a step of the operations named
    ``mxtpu_flash_*``, on the slowest device, inside the traced window."""
    t0, t1 = ctx.plain["window"]
    seconds = sum(min(end, t1) - max(start, t0)
                  for name, start, end, _, _ in
                  ctx.plain["devices"][ctx.reduced["slowest"]]
                  if PREFIX in name and min(end, t1) > max(start, t0))
    if not seconds:
        return None
    cfg = ctx.cell.config
    least = mla_flops.least_seconds(
        cfg, int(ctx.cell.traffic["batch"]), ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * parts(cfg).count("L") \
        / (seconds / ctx.reduced["steps"])
