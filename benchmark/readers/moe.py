"""Layer: the expert layer (``ops/moe.py``), from the program's device
counters: ``mxnet_tpu.telemetry.device_counters(steps)`` hands out, in one
fetch after the window, what the step program summed on the device over the
window's steps (the newest chunks, which are the steps the trace covers):
for every expert layer [assignments that landed on held experts, the
fullest held expert's tokens, assignments to absent experts, landed
assignments that no block computed].  A program without such counters (one
from before them) reads as nothing."""


def counted(ctx):
    """(array (expert layers, 4) summed over ``steps`` steps, steps) or
    (None, 0)."""
    from mxnet_tpu import telemetry
    fetch = getattr(telemetry, "device_counters", None)
    if fetch is None:
        return None, 0
    values, steps = fetch(ctx.reduced["steps"])
    if not values or "moe" not in values or not steps:
        return None, 0
    return values["moe"], steps


def load_max_over_mean(ctx):
    """The fullest held expert's tokens over the mean load of the held
    experts, a step, averaged over the expert layers: 1 is perfect balance,
    ``experts held`` is every landed token on one expert."""
    moe, _ = counted(ctx)
    if moe is None:
        return None
    held = ctx.cell.config["n_routed_experts"]
    ratios = [full / (landed / held) for landed, full, _, _ in moe
              if landed > 0]
    return float(sum(ratios) / len(ratios)) if ratios else None


def dropped_tokens(ctx):
    """Assignments that landed here and that no expert's block computed,
    all layers, over the counted steps; must read 0."""
    moe, _ = counted(ctx)
    return None if moe is None else float(moe[:, 3].sum())
