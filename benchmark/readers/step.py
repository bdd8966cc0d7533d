"""Layer: the fused step program (``train.py`` ``TrainStep``)."""


def device_ms(ctx):
    return 1e3 * ctx.reduced["step_device_s"]


def mfu(ctx):
    """Required FLOPs of the window's steps (the benchmark's own functions)
    over the window's seconds and the chips' peak."""
    flops = ctx.cell.flops().step_flops(ctx.cell.config,
                                        int(ctx.cell.traffic["batch"]))
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops * ctx.reduced["steps"] \
        / ctx.reduced["window_s"] / peak
