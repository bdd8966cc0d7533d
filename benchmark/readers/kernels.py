"""Layer: kernels (``ops/pallas_kernels.py``: flash attention forward, dQ,
dK/dV).  The kernels carry no name of their own in the trace (they are
custom calls named after the autodiff scope), so they are told apart by
what they return: forward (bf16[BH,T,D], f32[BH,T,1]); dK/dV two
bf16[BH,T,D]; dQ one."""
import re

from benchmark.flops import flash

SHAPE = re.compile(r"(bf16|f32|f16)\[([0-9,]+)\]")


def _classify(hlo, bh, t, d):
    """'fwd' | 'dq' | 'dkv' | None for one device operation's HLO text,
    ``result custom-call(operands...``."""
    result, call, _ = hlo.partition("custom-call(")
    if not call:
        return None
    outs = [(m.group(1), tuple(int(x) for x in m.group(2).split(",")))
            for m in SHAPE.finditer(result)]
    full = [o for o in outs if o[1] == (bh, t, d)]
    rows = [o for o in outs if o[1] == (bh, t, 1) and o[0] == "f32"]
    if len(full) == 1 and len(rows) == 1 and len(outs) == 2:
        return "fwd"
    if len(full) == 2 and len(outs) == 2:
        return "dkv"
    if len(full) == 1 and len(outs) == 1:
        return "dq"
    return None


def _geometry(ctx):
    cfg = ctx.cell.config
    heads = cfg["num_attention_heads"]
    return (int(ctx.cell.traffic["batch"]) * heads,
            cfg["max_position_embeddings"], cfg["hidden_size"] // heads)


def _times(ctx):
    """kernel -> (seconds, calls) in the window on the slowest device."""
    bh, t, d = _geometry(ctx)
    t0, t1 = ctx.plain["window"]
    out = {}
    for _, a, b, _, hlo in ctx.plain["devices"][ctx.reduced["slowest"]]:
        kind = _classify(hlo, bh, t, d)
        if kind and a >= t0 and b <= t1:
            sec, calls = out.get(kind, (0.0, 0))
            out[kind] = (sec + b - a, calls + 1)
    return out


def flash_time_share(ctx):
    times = _times(ctx)
    if not times:
        return None
    busy = ctx.reduced["per_device"][ctx.reduced["slowest"]]["busy"]
    return 100.0 * sum(s for s, _ in times.values()) / busy


def flash_roofline(ctx, kernel):
    times = _times(ctx)
    if kernel not in times:
        return None
    seconds, calls = times[kernel]
    bh, t, d = _geometry(ctx)
    least, _bound = flash.least_seconds(
        kernel, bh, t, d, True, ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * calls / seconds
