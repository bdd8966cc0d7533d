#!/usr/bin/env python3
"""One run of one cell of the benchmark:

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (import, graph, weights on the device from --seed, programs loaded or
compiled, the first steps that the reference follows, warm-up) is timed as
``setup_s``; then the window measures for --seconds.  The rate is all items of
the window's steps over all its seconds, from its first dispatch to the device
sync that closes it.  After the window the peak memory is read, the program's
state is freed, and the plain reference follows the first steps: ``correct``
is that comparison.  The last line of standard output is the result.

It refuses to run off a TPU, or on fewer chips than the cell asks for."""
import time
T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import cells, check, trace  # noqa: E402


class Context:
    """What a per-layer reader is handed: the cell, the plain trace and its
    reduction, the window, the peaks, and the profiler's own ``profile`` for
    a reader that needs an event the plain form does not keep."""
    Inconsistent = trace.Inconsistent

    def __init__(self, **kw):
        self.__dict__.update(kw)


def watch_compiles():
    """Compile requests and persistent-cache hits so far; their difference
    is what was really compiled."""
    from jax import monitoring
    counts = {"requests": 0, "hits": 0}

    def listener(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            counts["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
    monitoring.register_event_listener(listener)
    return counts


def device_stamp(devices):
    peak, fullest = 0, {}
    for d in devices:
        stats = d.memory_stats() or {}
        if int(stats.get("peak_bytes_in_use", 0)) >= peak:
            peak, fullest = int(stats.get("peak_bytes_in_use", 0)), stats
    # the runtime keeps a program's scratch (activations) as "reserved",
    # apart from the buffers "in use": the chip's peak is both together
    peak += int(fullest.get("peak_bytes_reserved", 0))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak,
            "memory_stats": {k: int(v) for k, v in fullest.items()
                             if isinstance(v, (int, float))}}


def per_layer_metrics(cell, ctx):
    out = {}
    for m in cell.per_layer():
        fn, args = cell.reader(m["name"])
        value = fn(ctx, **args)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell, seed, seconds, traced, t_process=None):
    """The whole of a run after the look for a chip: what ``main`` does on
    the chip, and what the tests drive on the CPU at a tiny size.  Returns
    the result line's object and every number read."""
    import jax
    t_process = T_PROCESS if t_process is None else t_process
    devices = jax.devices()[:cell.chips]
    compiles = watch_compiles()
    tracer = None
    if traced:
        tracer = trace.Tracer(os.path.join(cell.root, ".bench_trace",
                                           cell.name))
        seconds = float(cell.traffic["trace_seconds"])
    entry = cell.entry().Entry(cell, seed, seconds, tracer)
    entry.build()
    before = None

    def mark():
        nonlocal before
        before = dict(compiles)
    entry.on_window_start = mark
    win = entry.run(t_process)
    after = dict(compiles)
    compiled_in_window = (after["requests"] - before["requests"]) \
        - (after["hits"] - before["hits"])
    device = device_stamp(devices)
    batches = entry.reference_batches()
    observed = entry.observed
    entry.release()

    # the plain reference follows the same first steps, from the same seed
    from benchmark import gen
    from benchmark.reference import train as ref
    cfg = cell.config
    shapes = ref.family(cfg).param_shapes(cfg)
    t_ref = time.perf_counter()
    reference = ref.follow(
        cfg, lambda: gen.make_weights(shapes, cfg["init"], seed), batches)
    t_ref = time.perf_counter() - t_ref
    nums = check.numbers(observed, reference,
                         [k for k, v in shapes.items() if len(v) > 1])
    correct, table = check.decide(nums, cell.limits)

    steps = win["steps"]
    result = {"correct": bool(correct), "attempted": steps, "failed": 0}
    metrics = {}
    if not traced:
        values = {
            "train_items_per_s": win["items"] / win["seconds"],
            "peak_hbm_gib": device["memory_peak_bytes"] / 2.0 ** 30,
            "setup_s": win["setup_s"]}
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        try:
            profile = tracer.profile()
            plain = trace.from_profile(profile)
            reduced = trace.reduce(plain, steps)
            ctx = Context(cell=cell, profile=profile, plain=plain,
                          reduced=reduced, window=win, chips=cell.chips,
                          peaks=cell.peaks(device["kind"]),
                          compiles_in_window=compiled_in_window)
            metrics = per_layer_metrics(cell, ctx)
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            slow = reduced["slowest"]
            result["breakdown"] = {
                "device_ops": trace.top_ops(plain, slow),
                "idle_gaps": trace.idle_by_span(
                    plain, slow, reduced["per_device"][slow]["gaps"])}
            tracer.discard()
        except trace.Inconsistent as exc:
            # a trace that does not reconcile is a failed run, not a share
            print("trace does not reconcile: %s" % exc, file=sys.stderr)
            result["correct"] = False
            result["failed"] = steps
    result["metrics"] = metrics
    result["device"] = device
    result["window"] = {"seconds": win["seconds"], "steps": steps,
                        "compiled_in_window": compiled_in_window,
                        "reference_s": t_ref}
    result["intervals"] = intervals(win, cell)
    # every number read, compared or not; then those compared, each beside
    # its limit, last
    result["numbers"] = {k: v[0] for k, v in nums.items()}
    result["checks"] = table
    return result, nums


def intervals(win, cell):
    """Wall seconds of every ``interval_batches`` batches (or every chunk)
    of the window, for finding a slow run's slow part."""
    import numpy as np
    stamps = np.asarray(win["stamps"])
    every = int(cell.traffic.get("interval_batches", 1))
    marks = stamps[::every]
    if marks[-1] != stamps[-1]:
        marks = np.append(marks, stamps[-1])
    return [round(float(x), 4) for x in np.diff(marks)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.Cell(args.workload)
    # the traffic's environment holds the program's switches for its path
    # and must be set before import
    for k, v in cell.traffic.get("env", {}).items():
        os.environ[k] = str(v)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("benchmark: %s needs %d TPU chip(s); jax.devices() gives %d "
              "of platform %r.  Nothing was measured." % (
                  cell.name, cell.chips, len(devices), devices[0].platform),
              file=sys.stderr)
        return 1
    from mxnet_tpu.base import enable_compile_cache
    enable_compile_cache()
    result, nums = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    lines = ["%s = %.6g (limit %.6g)%s" % (
        k, v[0], v[1], "" if nums[k][1] is None else " at " + nums[k][1])
        for k, v in result["checks"].items()]
    print(json.dumps(result), flush=True)
    print("correct=%s\n%s" % (result["correct"], "\n".join(lines)),
          file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
