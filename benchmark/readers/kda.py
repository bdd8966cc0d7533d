"""Layer: the linear-attention mixer (``ops/kda.py`` ``kda_scan`` and the
three ``causal_conv1d`` before it), from the device time under the named
scopes ``kda_conv`` + ``kda_scan`` that ``models/hybrid_lm.py`` opens
(``readers/scopes.py`` reads a scope's seconds).  A program without those
scopes (one from before the mixer) reads as nothing."""
from benchmark.flops import kda as kda_flops
from benchmark.reference.kda_lm import parts
from benchmark.readers import scopes as by_scope


def kda_scan_roofline(ctx, scopes):
    """Least seconds of the convolutions-and-rule of the step's KDA mixers
    (``flops/kda.py``, forward and backward) over their device seconds."""
    seconds = by_scope._seconds_a_step(ctx, scopes)
    if not seconds:
        return None
    cfg = ctx.cell.config
    tokens = int(ctx.cell.traffic["batch"]) * cfg["max_position_embeddings"]
    least, _bound = kda_flops.least_seconds(
        cfg, tokens, ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * parts(cfg).count("K") / seconds
