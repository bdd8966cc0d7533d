"""Layer: the expert layer with gated experts (``ops/moe.py`` ``moe_experts``
with a gate's matrix): its routed products against their roofline, at the
assignments the program COUNTED (``readers/moe.py``) and the device seconds
under the scope ``moe_experts`` (``readers/scopes.py``).  A program without
the counters or the scope reads as nothing."""
from benchmark.flops import moe_glu as glu_flops
from benchmark.readers import moe as moe_counters
from benchmark.readers import scopes as by_scope


def moe_glu_experts_roofline(ctx, scopes):
    """Least seconds of the routed gated experts' products (``flops/
    moe_glu.py``, each expert layer at its own counted assignments a step,
    the held experts' three matrices read once a pass) over the device
    seconds under ``moe_experts``."""
    seconds = by_scope._seconds_a_step(ctx, scopes)
    counted, steps = moe_counters.counted(ctx)
    if not seconds or counted is None:
        return None
    least = sum(glu_flops.least_seconds(
        ctx.cell.config, float(landed) / steps,
        ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"])[0]
        for landed in counted[:, 0])
    return 100.0 * least / seconds
