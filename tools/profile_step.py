#!/usr/bin/env python
"""Per-HLO profile of one fused ResNet-50 train step (the bench.py program).

Captures a jax.profiler device trace around a few single fused steps, then
aggregates the TPU device-track events by HLO fusion kind — the methodology
behind docs/perf.md's cost-bucket tables.

Usage:  python tools/profile_step.py [--batch 32] [--steps 3] [--out DIR]

Prints a JSON summary (bucket -> total ms across the captured steps) plus a
top-N op table to stderr.  Needs the real chip quiet (serialize with other
bench runs — see docs/perf.md).
"""
from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import sys


def build_step(batch, image=224, model="resnet50"):
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.train import TrainStep

    if model == "resnet50":
        from mxnet_tpu.models import resnet
        net = resnet.get_symbol(num_classes=1000, num_layers=50,
                                image_shape="3,%d,%d" % (image, image))
    elif model == "alexnet":
        from mxnet_tpu.models import alexnet
        net = alexnet.get_symbol(num_classes=1000)
    else:
        raise SystemExit("unknown model %s" % model)
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                           rescale_grad=1.0 / batch, wd=1e-4)
    ts = TrainStep(net, opt, dtype="bfloat16")
    params, state, aux = ts.init(
        {"data": (batch, 3, image, image)}, {"softmax_label": (batch,)})
    rng = np.random.RandomState(0)
    data = rng.uniform(-1, 1, (batch, 3, image, image)).astype(np.float32)
    label = rng.randint(0, 1000, (batch,)).astype(np.float32)
    batch_dev = ts.shard_batch({"data": data, "softmax_label": label})
    return ts, params, state, aux, batch_dev


def capture(ts, params, state, aux, batch_dev, steps, out_dir):
    import jax
    import numpy as np
    # warm the compile + one executed step outside the trace
    params, state, aux, outs = ts(params, state, aux, batch_dev)
    np.asarray(outs[0])
    jax.profiler.start_trace(out_dir)
    for _ in range(steps):
        params, state, aux, outs = ts(params, state, aux, batch_dev)
    np.asarray(outs[0])
    jax.profiler.stop_trace()


def load_trace_events(out_dir):
    """Load the trace-viewer JSON jax.profiler writes next to the xplane
    (this image's tensorboard_plugin_profile cannot parse xplane itself)."""
    paths = sorted(glob.glob(os.path.join(
        out_dir, "plugins/profile/*/*.trace.json.gz")))
    if not paths:
        raise SystemExit("no .trace.json.gz under %s" % out_dir)
    with gzip.open(paths[-1], "rt") as f:
        return json.load(f)


DEVICE_HINTS = ("TPU", "/device:", "Chip", "XLA Op")


def aggregate(trace, min_ms=0.0):
    """Sum durations of device-track complete events by event name."""
    events = trace.get("traceEvents", [])
    # map pid -> process name to find device tracks
    pid_name = {}
    tid_name = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pid_name[ev["pid"]] = ev["args"].get("name", "")
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            tid_name[(ev["pid"], ev["tid"])] = ev["args"].get("name", "")
    device_pids = {p for p, n in pid_name.items()
                   if any(h in n for h in DEVICE_HINTS)}
    per_op = collections.Counter()
    for ev in events:
        if ev.get("ph") != "X" or ev.get("pid") not in device_pids:
            continue
        tname = tid_name.get((ev["pid"], ev["tid"]), "")
        # only the per-instruction lanes: "Steps" and "XLA Modules" carry
        # whole-program events that would double-count every op
        if tname not in ("XLA Ops", "Async XLA Ops"):
            continue
        per_op[ev.get("name", "?")] += ev.get("dur", 0) / 1000.0
    return {k: v for k, v in per_op.items() if v >= min_ms}, pid_name, tid_name


BUCKETS = [
    ("convert_reduce", lambda n: "convert_reduce" in n),
    ("add_add", lambda n: n.startswith(("add_add", "fusion_add")) or
        (n.startswith("add") and "fusion" in n)),
    ("copy", lambda n: "copy" in n),
    ("conv_reduce", lambda n: "convolution_reduce" in n),
    ("select_scatter", lambda n: "select-and-scatter" in n or
        "select_and_scatter" in n),
    ("conv+loop_fusion", lambda n: "fusion" in n or "convolution" in n),
]


def bucketize(per_op):
    buckets = collections.Counter()
    for name, ms in per_op.items():
        for bname, pred in BUCKETS:
            if pred(name):
                buckets[bname] += ms
                break
        else:
            buckets["other"] += ms
    return buckets


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--out", default="/tmp/profile_step")
    ap.add_argument("--parse-only", action="store_true",
                    help="skip capture; re-parse an existing --out dir")
    ap.add_argument("--top", type=int, default=40)
    args = ap.parse_args()

    if not args.parse_only:
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
        import bench
        from mxnet_tpu.base import enable_compile_cache
        enable_compile_cache()
        print("device:", bench.device_stamp(), file=sys.stderr)
        ts, params, state, aux, batch_dev = build_step(
            args.batch, model=args.model)
        capture(ts, params, state, aux, batch_dev, args.steps, args.out)
    trace = load_trace_events(args.out)
    per_op, pid_name, _ = aggregate(trace)
    buckets = bucketize(per_op)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:args.top]
    print("device tracks:", sorted(
        n for n in pid_name.values()
        if any(h in n for h in DEVICE_HINTS)), file=sys.stderr)
    for name, ms in top:
        print("%9.3f ms  %s" % (ms, name), file=sys.stderr)
    print(json.dumps({
        "model": args.model, "batch": args.batch, "steps": args.steps,
        "buckets_ms_total": dict(buckets),
        "total_ms": sum(per_op.values()),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
