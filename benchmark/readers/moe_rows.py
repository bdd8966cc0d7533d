"""Layer: the expert layer (``ops/moe.py``), how far its products follow the
load: the program's device counter ``moe_rows`` (for every expert layer, the
rows its grouped products were asked to run over: the blocks visited, each
whole or as the part of it the kernels work) against the first number of ``moe`` (assignments that landed on held
experts), both summed by the step program over the window's steps and
fetched once after it (``readers/moe.py``).  A program without the counter
(one from before it) reads as nothing."""


def rows_computed_over_landed(ctx):
    """Rows the routed products ran over for every assignment that landed
    here, all expert layers, over the counted steps: 1 is no row computed
    in vain; a room of rows worked whatever landed reads the room over the
    load.  Nothing where nothing landed."""
    from mxnet_tpu import telemetry
    fetch = getattr(telemetry, "device_counters", None)
    if fetch is None:
        return None
    values, steps = fetch(ctx.reduced["steps"])
    if not values or not steps or "moe_rows" not in values \
            or "moe" not in values:
        return None
    landed = float(values["moe"][:, 0].sum())
    return float(values["moe_rows"].sum()) / landed if landed > 0 else None
