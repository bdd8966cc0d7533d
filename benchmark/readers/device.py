"""Layer: the device."""


def idle_share(ctx):
    return 100.0 * (1.0 - ctx.reduced["busy_s"] / ctx.reduced["window_s"])
