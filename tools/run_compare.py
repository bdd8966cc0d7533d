#!/usr/bin/env python
"""Compare training/benchmark runs: curve deltas and a regression verdict.

The scalar layer (``telemetry.scalar``) records per-step training curves —
``train_<metric>``, ``val_<metric>``, ``lr``, ``throughput``,
``grad_norm[param=...]``, ... — into the per-rank telemetry JSON-lines
stream, and ``bench.py`` emits one ``BENCH_*.json`` throughput record per
run.  This tool loads two or more runs (either kind, mixed freely), aligns
their curves by step, and answers "did run B get worse than run A":

* **curves** — per series present in both runs: final value, best value,
  and step-averaged area-under-curve over the overlapping step window,
  each as a relative delta vs the baseline (the FIRST run listed);
* **throughput** — BENCH records compare their headline metric (img/s);
  a BENCH file whose ``meta.telemetry_scalars`` names a scalar stream
  (bench.py stamps it) pulls that run's curves in too;
* **verdict** — metrics with a known better-direction (loss-like: down,
  accuracy/throughput-like: up; override with ``--better name=up|down``)
  whose final value moved against that direction by more than
  ``--threshold`` (default 5%) are flagged ``REGRESSION``; a finite
  baseline turning NaN/Inf is always a regression.  Directionless series
  (``lr``, ``grad_norm``, ``monitor``) are reported as context, never
  flagged.

Usage:
    python tools/run_compare.py good.jsonl bad.jsonl
    python tools/run_compare.py bench_parent.json bench_change.json --check
    python tools/run_compare.py a.jsonl b.jsonl --json --threshold 0.02
    python tools/run_compare.py a.jsonl b.jsonl --metric train_accuracy

``--check`` exits non-zero (2) when any comparison ends REGRESSION, so a
CI step or bench ladder can gate on it; without it the tool always exits
0 and just reports.  Pure stdlib, like the other telemetry tools —
usable away from the training image.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

# better-direction heuristics, matched against the series base name
# (lowercased, tags stripped).  Directionless names are context only.
_UP_HINTS = ("acc", "f1", "per_sec", "throughput", "reward", "top",
             "qps", "speedup",
             # model-FLOP utilization: the efficiency denominator the
             # cost-attribution arc added — it regresses by going DOWN
             # (docs/observability.md "Cost attribution & MFU")
             "mfu")
_DOWN_HINTS = ("loss", "entropy", "err", "perplexity", "mae", "mse",
               "rmse", "time", "wait", "p50", "p90", "p99", "latency",
               # pipeline-parallel ladder metrics: the fill/drain bubble
               # share and the per-stage memory footprint both regress by
               # going UP (docs/distributed.md "Pipeline parallelism")
               "bubble", "stage_param", "stage_mem", "live_bytes",
               # ZeRO ladder metrics: per-device param/grad/opt-state
               # residency regresses by going up (docs/distributed.md
               # "ZeRO levels")
               "param_bytes", "grad_bytes", "opt_bytes",
               # collective wire-bytes accounting: payload moved per step
               # regresses by going up — a sharding change that silently
               # widens a collective shows here (docs/observability.md
               # "wire-bytes accounting")
               "wire_bytes",
               # per-program HBM attribution: compiled-program resident
               # bytes regress by going up — a donation break or temp
               # blow-up shows here before the device OOMs
               # (docs/observability.md "HBM attribution")
               "hbm_bytes",
               # compile-time observability: cumulative XLA compile
               # seconds regress by going up — a cache-miss storm (or a
               # lost persistent-cache win) shows here
               "compile_sec",
               # numerics-monitor overhead: the sampled stats step's cost
               # over the plain step regresses by going up
               # (docs/observability.md "Numerics monitor")
               "overhead")

_EVENT_TYPES = ("scalar", "span", "counter", "gauge", "hist", "summary")


def series_key(name, tags=None):
    """Stdlib copy of telemetry.series_key (held together by a test):
    the bare name, or ``name[k=v,...]`` with sorted tags."""
    if not tags:
        return name
    return "%s[%s]" % (name, ",".join("%s=%s" % (k, tags[k])
                                      for k in sorted(tags)))


def direction_of(key, overrides=None):
    """'up' | 'down' | None for a series key; ``overrides`` maps base
    names (tags stripped) to forced directions."""
    base = key.split("[", 1)[0].lower()
    if overrides and base in overrides:
        return overrides[base]
    for hint in _UP_HINTS:
        if hint in base:
            return "up"
    for hint in _DOWN_HINTS:
        if hint in base:
            return "down"
    return None


class Run(object):
    """One loaded run: curves + headline bench metrics."""

    def __init__(self, path):
        self.path = path
        self.label = os.path.basename(path)
        self.series = {}   # key -> [(step, value)] sorted, last-wins per step
        self.bench = {}    # metric name -> value (BENCH headline numbers)
        self.meta = None   # BENCH meta block, when present
        # identity blocks per record group (e.g. the pipeline block's
        # config: pp/dp/microbatches/schedule/interleave) and which bench
        # metrics each group contributed — two runs whose identities
        # differ are different experiments, not a regression pair
        self.identity = {}
        self.groups = {}

    def add_point(self, key, step, value):
        self.series.setdefault(key, []).append((int(step), float(value)))

    def finalize(self):
        for key, pts in self.series.items():
            # sort by step; a step recorded twice keeps the LAST value
            # (e.g. the fit's sampled `lr` point and the scheduler's
            # decay-pinned one land on nearby steps, occasionally equal)
            dedup = {}
            for step, val in pts:
                dedup[step] = val
            self.series[key] = sorted(dedup.items())


def _ingest_events(run, events):
    for ev in events:
        if ev.get("type") == "scalar" and "step" in ev:
            run.add_point(series_key(ev["name"], ev.get("tags")),
                          ev["step"], ev["value"])


def _load_jsonl(run, path):
    with open(path) as f:
        events = []
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except ValueError:
                continue   # partial trailing line from a live run
    _ingest_events(run, events)
    return run


def _load_bench(run, doc, path):
    """A BENCH_*.json document: either the bare bench.py record or the
    bench-driver wrapper that carries it under ``parsed``."""
    rec = doc.get("parsed") if isinstance(doc.get("parsed"), dict) else doc
    if isinstance(rec, dict) and "metric" in rec and "value" in rec:
        run.bench[str(rec["metric"])] = float(rec["value"])
        run.meta = rec.get("meta")
    # serving record (bench.py bench_serving): every numeric field is a
    # gated headline metric (serve_qps up, serve_p50_ms/serve_p99_ms
    # down via the direction hints); nested config blocks are identity,
    # not metrics, and stay out of the comparison
    serving = rec.get("serving") if isinstance(rec, dict) else None
    if isinstance(serving, dict):
        for k, v in serving.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                run.bench[str(k)] = float(v)
    # pipeline record (dryrun_multichip's pp ladder / a pipelined bench):
    # numeric fields are gated headline metrics — pp_bubble_fraction and
    # the per-stage memory/live-bytes fields regress by going up
    # (direction hints); the nested config block is IDENTITY
    # (pp/dp/microbatches/schedule/interleave) — never compared as a
    # metric, and when it differs between two runs their pipeline metrics
    # are reported as context only (a gpipe record vs a 1f1b record is a
    # schedule change, not a regression pair)
    pipeline = rec.get("pipeline") if isinstance(rec, dict) else None
    if isinstance(pipeline, dict):
        names = set()
        for k, v in pipeline.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                run.bench[str(k)] = float(v)
                names.add(str(k))
        # a pp_* HEADLINE metric (the pp ladder records stamp their gated
        # bubble there too) belongs to the same identity group
        for name in run.bench:
            if name.startswith("pp_"):
                names.add(name)
        run.groups["pipeline"] = names
        if isinstance(pipeline.get("config"), dict):
            run.identity["pipeline"] = dict(pipeline["config"])
    # zero record (dryrun_multichip's ZeRO ladder): numeric fields are
    # gated headline metrics — per-device zero_param_bytes/zero_grad_
    # bytes/zero_opt_bytes regress by going up (direction hints); the
    # nested config block (zero level / dp / pp) is IDENTITY — two runs
    # stamped at different levels are different experiments, not a
    # regression pair
    zero = rec.get("zero") if isinstance(rec, dict) else None
    if isinstance(zero, dict):
        names = set()
        for k, v in zero.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                run.bench[str(k)] = float(v)
                names.add(str(k))
        # a zero* HEADLINE metric (the ladder records stamp their gated
        # zero3_* residency there too) belongs to the same identity group
        for name in run.bench:
            if name.startswith("zero"):
                names.add(name)
        run.groups["zero"] = names
        if isinstance(zero.get("config"), dict):
            run.identity["zero"] = dict(zero["config"])
    # wire-bytes record (dryrun_multichip's per-kind collective payload
    # accounting): numeric fields are gated headline metrics — bytes on
    # the wire per step regress by going UP (direction hints); the nested
    # config block (device count / batch shape) is IDENTITY — records
    # stamped on different meshes are different experiments
    wire = rec.get("wire_bytes") if isinstance(rec, dict) else None
    if isinstance(wire, dict):
        names = set()
        for k, v in wire.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                run.bench[str(k)] = float(v)
                names.add(str(k))
        for name in run.bench:
            if "wire_bytes" in name:
                names.add(name)
        run.groups["wire_bytes"] = names
        if isinstance(wire.get("config"), dict):
            run.identity["wire_bytes"] = dict(wire["config"])
    # hbm record (dryrun_multichip's per-program HBM attribution,
    # MULTICHIP_HBM_*): numeric fields are gated headline metrics —
    # compiled-program resident bytes regress by going UP (the hbm_bytes
    # direction hint); the nested config block (device count / batch
    # shape) is IDENTITY, and the per-program breakdown rides under
    # "programs" as context (rendered by tools/hbm_report.py, not gated
    # per-row — program names churn with jit cache keys)
    hbm = rec.get("hbm") if isinstance(rec, dict) else None
    if isinstance(hbm, dict):
        names = set()
        for k, v in hbm.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                run.bench[str(k)] = float(v)
                names.add(str(k))
        for name in run.bench:
            if "hbm_bytes" in name:
                names.add(name)
        run.groups["hbm"] = names
        if isinstance(hbm.get("config"), dict):
            run.identity["hbm"] = dict(hbm["config"])
    # cost record (dryrun_multichip's per-program cost attribution,
    # MULTICHIP_COST_*, or a bench record's efficiency block): numeric
    # fields are gated headline metrics — mfu regresses by going DOWN
    # (up-hint), compile_sec by going UP (down-hint), the FLOP counts
    # are deterministic cross-checks; the nested config block (device
    # count / batch shape) is IDENTITY, and the per-program breakdown
    # rides under "programs" as context (rendered by
    # tools/cost_report.py, not gated per-row)
    cost = rec.get("cost") if isinstance(rec, dict) else None
    if isinstance(cost, dict):
        names = set()
        for k, v in cost.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                run.bench[str(k)] = float(v)
                names.add(str(k))
        for name in run.bench:
            if name.startswith("cost_") or name in ("mfu", "compile_sec"):
                names.add(name)
        run.groups["cost"] = names
        if isinstance(cost.get("config"), dict):
            run.identity["cost"] = dict(cost["config"])
    # num record (dryrun_multichip's numerics-monitor rung,
    # MULTICHIP_NUM_*): numeric fields are gated headline metrics —
    # num_grad_norm_rel_err (replicated-vs-ZeRO global gradient norm
    # agreement) regresses by going UP (the "err" hint), and
    # num_monitor_overhead (sampled stats step cost over the plain step)
    # regresses by going UP (the "overhead" hint); the nested config
    # block (device count / zero level / every_n) is IDENTITY — records
    # stamped on different meshes or sampling cadences are different
    # experiments, not a regression pair
    num = rec.get("num") if isinstance(rec, dict) else None
    if isinstance(num, dict):
        names = set()
        for k, v in num.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                run.bench[str(k)] = float(v)
                names.add(str(k))
        for name in run.bench:
            if name.startswith("num_"):
                names.add(name)
        run.groups["num"] = names
        if isinstance(num.get("config"), dict):
            run.identity["num"] = dict(num["config"])
    chained = (run.meta or {}).get("telemetry_scalars")
    if chained:
        for candidate in (chained,
                          os.path.join(os.path.dirname(os.path.abspath(path)),
                                       os.path.basename(chained))):
            if os.path.exists(candidate):
                _load_jsonl(run, candidate)
                break
        else:
            sys.stderr.write("run_compare: %s names scalar stream %s "
                             "(not found; curves skipped)\n"
                             % (run.label, chained))
    return run


def load_run(path):
    """Load one run file: a telemetry JSON-lines stream, or a BENCH-style
    single JSON document (optionally chaining to its scalar stream)."""
    run = Run(path)
    with open(path) as f:
        content = f.read()
    try:
        doc = json.loads(content)
    except ValueError:
        doc = None
    if isinstance(doc, dict) and doc.get("type") not in _EVENT_TYPES:
        _load_bench(run, doc, path)
    elif isinstance(doc, dict):
        _ingest_events(run, [doc])   # a one-event jsonl file
    else:
        _load_jsonl(run, path)
    run.finalize()
    return run


# ------------------------------------------------------------- curve algebra
def _interp(pts, step):
    """Linear interpolation of a sorted curve at ``step`` (clamped)."""
    if step <= pts[0][0]:
        return pts[0][1]
    if step >= pts[-1][0]:
        return pts[-1][1]
    for (s0, v0), (s1, v1) in zip(pts, pts[1:]):
        if s0 <= step <= s1:
            if s1 == s0:
                return v1
            frac = (step - s0) / float(s1 - s0)
            return v0 + (v1 - v0) * frac
    return pts[-1][1]


def auc_mean(pts, lo, hi):
    """Step-averaged area under the curve over ``[lo, hi]`` (trapezoid;
    the mean level, so runs of different length stay comparable).  None
    when the window is empty or the curve has a single point."""
    if hi <= lo or len(pts) < 2:
        return None
    window = [(lo, _interp(pts, lo))]
    window += [(s, v) for s, v in pts if lo < s < hi]
    window.append((hi, _interp(pts, hi)))
    area = 0.0
    for (s0, v0), (s1, v1) in zip(window, window[1:]):
        if not (math.isfinite(v0) and math.isfinite(v1)):
            return float("nan")
        area += (v1 + v0) / 2.0 * (s1 - s0)
    return area / (hi - lo)


def rel_delta(base, cand):
    """(cand - base) / |base|; None when undefined (base 0 / non-finite)."""
    if base is None or cand is None:
        return None
    if not (math.isfinite(base) and math.isfinite(cand)):
        return None
    if base == 0:
        return 0.0 if cand == 0 else None
    return (cand - base) / abs(base)


def best_of(values, direction):
    finite = [v for v in values if math.isfinite(v)]
    if not finite:
        return values[-1]
    if direction == "down":
        return min(finite)
    return max(finite)   # 'up' and directionless both read as peak


def compare_series(key, base_pts, cand_pts, direction, threshold):
    """One series' comparison record: final/best/auc deltas + verdict."""
    base_final, cand_final = base_pts[-1][1], cand_pts[-1][1]
    lo = max(base_pts[0][0], cand_pts[0][0])
    hi = min(base_pts[-1][0], cand_pts[-1][0])
    rec = {
        "metric": key,
        "direction": direction,
        "base_final": base_final,
        "final": cand_final,
        "final_delta": rel_delta(base_final, cand_final),
        "best_delta": rel_delta(best_of([v for _, v in base_pts], direction),
                                best_of([v for _, v in cand_pts], direction)),
        "auc_delta": rel_delta(auc_mean(base_pts, lo, hi),
                               auc_mean(cand_pts, lo, hi)),
        "points": (len(base_pts), len(cand_pts)),
    }
    rec["verdict"] = _verdict(rec, threshold)
    return rec


def _verdict(rec, threshold):
    """'REGRESSION' | 'ok' | 'info' for one comparison record.  Flagging
    needs a direction; a finite baseline going non-finite is always a
    regression (the NaN run 'improved' no metric)."""
    direction = rec["direction"]
    if direction is None:
        return "info"
    if math.isfinite(rec["base_final"]) and not math.isfinite(rec["final"]):
        return "REGRESSION"
    d = rec["final_delta"]
    if d is None:
        return "ok"
    if direction == "up" and d < -threshold:
        return "REGRESSION"
    if direction == "down" and d > threshold:
        return "REGRESSION"
    return "ok"


def compare_runs(base, cand, threshold, overrides=None, metrics=None):
    """All comparison records for candidate vs baseline: common scalar
    series first, then common BENCH headline metrics (direction up)."""
    records = []
    for key in sorted(set(base.series) & set(cand.series)):
        if metrics and key.split("[", 1)[0] not in metrics and \
                key not in metrics:
            continue
        records.append(compare_series(key, base.series[key],
                                      cand.series[key],
                                      direction_of(key, overrides),
                                      threshold))
    # bench metrics whose record-group identity differs between the runs
    # (e.g. the pipeline config's schedule/interleave/pp/dp/microbatches)
    # are different experiments: report as context, never gate
    mismatched = set()
    for group in set(base.groups) & set(cand.groups):
        bid, cid = base.identity.get(group), cand.identity.get(group)
        if bid is not None and cid is not None and bid != cid:
            mismatched |= base.groups[group] & cand.groups[group]
    for name in sorted(set(base.bench) & set(cand.bench)):
        if metrics and name not in metrics:
            continue
        identity_ok = name not in mismatched
        rec = {
            "metric": name,
            "direction": (direction_of(name, overrides) or "up")
            if identity_ok else None,
            "base_final": base.bench[name],
            "final": cand.bench[name],
            "final_delta": rel_delta(base.bench[name], cand.bench[name]),
            "best_delta": None,
            "auc_delta": None,
            "points": (1, 1),
        }
        if not identity_ok:
            rec["note"] = "identity differs (config block) — not a " \
                          "regression pair"
        rec["verdict"] = _verdict(rec, threshold)
        records.append(rec)
    # flagged metrics first, then by name — the headline reads top-down
    records.sort(key=lambda r: (r["verdict"] != "REGRESSION", r["metric"]))
    return records


# ----------------------------------------------------------------- rendering
def _pct(delta):
    if delta is None:
        return "-"
    if not math.isfinite(delta):
        return "nan"
    return "%+.1f%%" % (100.0 * delta)


def _val(v):
    if v is None:
        return "-"
    if not math.isfinite(v):
        return str(v)
    return "%.6g" % v


def render(base, comparisons, out=None):
    # call-time stdout: a def-time default freezes the stream installed
    # at first import (pytest capture, redirection) — see telemetry_agg
    out = sys.stdout if out is None else out
    out.write("Run comparison — baseline: %s\n" % base.label)
    if not comparisons:
        out.write("no candidate runs\n")
        return
    for cand, records in comparisons:
        out.write("\nvs %s:\n" % cand.label)
        if not records:
            out.write("  no common metrics (different scalar names / no "
                      "overlap)\n")
            continue
        out.write("  %-34s %10s %10s %9s %9s %9s  %s\n"
                  % ("metric", "base", "final", "dfinal", "dbest",
                     "dauc", "verdict"))
        for r in records:
            out.write("  %-34s %10s %10s %9s %9s %9s  %s\n"
                      % (r["metric"], _val(r["base_final"]),
                         _val(r["final"]), _pct(r["final_delta"]),
                         _pct(r["best_delta"]), _pct(r["auc_delta"]),
                         r["verdict"]))
        bad = [r["metric"] for r in records if r["verdict"] == "REGRESSION"]
        if bad:
            out.write("  verdict: REGRESSION (%s)\n" % ", ".join(bad))
        else:
            out.write("  verdict: OK\n")


def _json_safe(obj):
    """Replace non-finite floats with their string forms ('nan', 'inf',
    '-inf') so ``--json`` output stays RFC-8259 parseable — the
    finite-baseline-went-NaN case is exactly the verdict a machine
    consumer must be able to read."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return str(obj)
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    return obj


def to_json(base, comparisons, threshold):
    return {
        "baseline": base.path,
        "threshold": threshold,
        "runs": [{
            "path": cand.path,
            "metrics": records,
            "regressions": [r["metric"] for r in records
                            if r["verdict"] == "REGRESSION"],
            "verdict": "REGRESSION" if any(r["verdict"] == "REGRESSION"
                                           for r in records) else "OK",
        } for cand, records in comparisons],
    }


def _parse_better(values):
    overrides = {}
    for item in values or []:
        name, sep, d = item.partition("=")
        if not sep or d not in ("up", "down"):
            raise ValueError("--better takes name=up|down, got %r" % item)
        overrides[name.lower()] = d
    return overrides


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("runs", nargs="+",
                    help="two or more run files: telemetry JSON-lines "
                         "scalar streams and/or BENCH_*.json records; the "
                         "first is the baseline")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="relative final-value move (against the metric's "
                         "better-direction) that flags REGRESSION "
                         "(default 0.05 = 5%%)")
    ap.add_argument("--check", action="store_true",
                    help="exit 2 when any comparison ends REGRESSION "
                         "(CI / bench-ladder gate)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output")
    ap.add_argument("--metric", action="append", default=None,
                    help="restrict to this metric/series (repeatable; "
                         "matches the base name or the full tagged key)")
    ap.add_argument("--better", action="append", default=None,
                    metavar="NAME=up|down",
                    help="force a metric's better-direction (e.g. "
                         "--better grad_norm=down)")
    args = ap.parse_args(argv)
    if len(args.runs) < 2:
        ap.error("need a baseline and at least one candidate run")
    try:
        overrides = _parse_better(args.better)
    except ValueError as e:
        ap.error(str(e))
    try:
        runs = [load_run(p) for p in args.runs]
    except (OSError, UnicodeDecodeError) as e:
        sys.stderr.write("run_compare: cannot read run: %s\n"
                         % (getattr(e, "strerror", None) and
                            "%s: %s" % (e.filename, e.strerror) or e))
        return 1
    base = runs[0]
    if not base.series and not base.bench:
        sys.stderr.write("run_compare: baseline %s has no scalar events "
                         "and no BENCH metric (was the run recorded with "
                         "MXNET_TELEMETRY?)\n" % base.label)
        return 1
    comparisons = [(cand, compare_runs(base, cand, args.threshold,
                                       overrides, args.metric))
                   for cand in runs[1:]]
    if args.as_json:
        json.dump(_json_safe(to_json(base, comparisons, args.threshold)),
                  sys.stdout, indent=1)
        sys.stdout.write("\n")
    else:
        render(base, comparisons)
    regressed = any(r["verdict"] == "REGRESSION"
                    for _, records in comparisons for r in records)
    return 2 if (args.check and regressed) else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:   # e.g. `... | head`
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
