#!/usr/bin/env python
"""Render a step-time breakdown from an mxnet_tpu telemetry JSON-lines file.

Usage:
    python tools/telemetry_report.py /tmp/telemetry.jsonl [--steps] [--epoch N]

The fit loop (mxnet_tpu.module.base_module.fit) emits, per batch, one
``step`` span (whole-batch wall time) plus component spans tagged with the
same (epoch, nbatch): ``data_wait``, then either ``forward``/``backward``/
``update``/``metric`` (general path) or ``fused_step``/``metric`` (fused
path), then ``callback`` (the batch-end callbacks).  The device
prefetcher's ``input.stage`` runs on its producer thread, beside the step.
This tool groups those spans per step and prints:

* a per-component summary (total / mean / share of step wall time),
* coverage — how much of the measured step wall time the components
  explain (instrumentation gaps show up as the remainder),
* final counter totals from the run's summary event (jit cache hits,
  kvstore traffic, io batches, ...),
* with ``--health``: the training-health signals recorded by the
  diagnostics layer (non-finite counters, XLA compile cost per jit kind,
  jit-cache size, device-memory gauges — docs/observability.md),
* with ``--curves``: every scalar time-series in the file
  (``train_<metric>``, ``lr``, ``throughput``, ``grad_norm[param=...]``,
  ...) as a terminal sparkline with first/last/min/max — the quick look
  before reaching for ``tools/run_compare.py``.

Files it cannot summarise produce a clear one-line message, never a
traceback: an unreadable path exits 1; a file whose steps never completed
(no ``step`` spans) or that lacks a summary event (the run never called
``telemetry.stop()``) says so and renders what it can.

With ``--ranks`` the path is treated as the base of a multi-process run
(``MXNET_TELEMETRY`` under tools/launch.py writes ``<path>.rank<N>`` per
worker): the per-rank files are globbed and the fleet view — counters
summed, latency histograms bucket-merged, per-rank skew columns and the
straggler verdict — is rendered via the aggregation library
(tools/telemetry_agg.py) instead of the single-file breakdown.

With ``--json`` the step-time breakdown (or, combined with ``--ranks``,
the merged fleet view) is emitted as one machine-readable JSON document
carrying the same fields as the rendered tables — the stable interface
for dashboards and CI scripts.

Pure stdlib; safe to point at a file from a live run (partial last line is
ignored).
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from collections import defaultdict

# component display order; anything else observed lands after these
# (forward_backward appears when a module subclass overrides that hook)
_KNOWN = ["data_wait", "forward", "backward", "forward_backward", "update",
          "fused_step", "metric", "callback"]
# the device prefetcher's staging runs on its producer thread, beside the
# step: shown as a row of its own below the breakdown, outside coverage
_BESIDE = "input.stage"


def load_events(path):
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except ValueError:
                continue   # partial trailing line from a live run
    return events


def collect_steps(events, epoch=None):
    """{(epoch, nbatch): {"step": us, "n": count, components: {name: us}}}"""
    steps = defaultdict(lambda: {"step": None, "n": 0, "components": {}})
    for ev in events:
        if ev.get("type") != "span" or ev.get("cat") != "step":
            continue
        tags = ev.get("tags") or {}
        if "nbatch" not in tags:
            continue
        if epoch is not None and tags.get("epoch") != epoch:
            continue
        key = (tags.get("epoch", 0), tags["nbatch"])
        if ev["name"] == "step":
            # accumulate (not overwrite): a session spanning several fit()
            # calls revisits (epoch, nbatch) keys, and coverage must compare
            # like against like; "n" keeps the true step count for means
            steps[key]["step"] = (steps[key]["step"] or 0.0) + ev["dur"]
            steps[key]["n"] += 1
        else:
            comp = steps[key]["components"]
            comp[ev["name"]] = comp.get(ev["name"], 0.0) + ev["dur"]
    return dict(steps)


def beside_step(events):
    """(total us, count) of the ``input.stage`` spans: the prefetcher's
    staging, on its own thread, so a row beside the breakdown and no part
    of its coverage."""
    durs = [ev["dur"] for ev in events
            if ev.get("type") == "span" and ev.get("name") == _BESIDE]
    return sum(durs), len(durs)


def summary_state(events):
    """(counters, gauges, has_summary) from the run's summary event, or
    folded from the raw stream when the run never wrote one (still alive,
    killed, or crashed before telemetry.stop())."""
    for ev in reversed(events):
        if ev.get("type") == "summary":
            return ev.get("counters", {}), ev.get("gauges", {}), True
    counters, gauges = {}, {}
    for ev in events:
        if ev.get("type") == "counter":
            counters[ev["name"]] = ev.get("total", 0)
        elif ev.get("type") == "gauge":
            gauges[ev["name"]] = ev.get("value")
    return counters, gauges, False


def component_order(steps):
    seen = set()
    for rec in steps.values():
        seen.update(rec["components"])
    return [c for c in _KNOWN if c in seen] + \
        sorted(c for c in seen if c not in _KNOWN)


def render(steps, counters, per_step=False, out=sys.stdout, beside=(0, 0)):
    if not steps:
        out.write("no step spans found (was the fit loop run with "
                  "MXNET_TELEMETRY set?)\n")
        if counters:
            render_counters(counters, out)
        return
    order = component_order(steps)
    keys = sorted(steps)
    measured = [k for k in keys if steps[k]["step"] is not None]
    if not measured:
        out.write("%d step component span(s) but no completed 'step' "
                  "spans — live or truncated run, nothing to summarise\n"
                  % sum(len(steps[k]["components"]) for k in keys))
        if counters:
            render_counters(counters, out)
        return

    if per_step:
        hdr = ["epoch", "batch", "step_ms"] + ["%s_ms" % c for c in order]
        out.write("  ".join("%10s" % h for h in hdr) + "\n")
        for k in keys:
            rec = steps[k]
            row = ["%10d" % k[0], "%10d" % k[1],
                   "%10.2f" % ((rec["step"] or 0.0) / 1e3)]
            row += ["%10.2f" % (rec["components"].get(c, 0.0) / 1e3)
                    for c in order]
            out.write("  ".join(row) + "\n")
        out.write("\n")

    # shares/coverage compare component time against step wall time, so
    # both sums run over the SAME steps: those whose 'step' span landed in
    # the file (a live or killed run can have trailing partial steps)
    total_step = sum(steps[k]["step"] for k in measured)
    # true step count, not key count — one session can span several fit()
    # calls that revisit the same (epoch, nbatch) keys
    nsteps = sum(steps[k]["n"] for k in measured) or len(measured)
    out.write("Step-time breakdown (%d steps, %.1f ms total)\n"
              % (nsteps, total_step / 1e3))
    if len(measured) != len(keys):
        out.write("(%d partial step(s) without a 'step' span excluded — "
                  "live or interrupted run)\n" % (len(keys) - len(measured)))
    out.write("%-12s %12s %10s %8s\n"
              % ("component", "total_ms", "mean_ms", "share"))
    comp_sum = 0.0
    for c in order:
        tot = sum(steps[k]["components"].get(c, 0.0) for k in measured)
        comp_sum += tot
        share = tot / total_step if total_step else 0.0
        out.write("%-12s %12.2f %10.3f %7.1f%%\n"
                  % (c, tot / 1e3,
                     tot / nsteps / 1e3 if nsteps else 0.0,
                     100.0 * share))
    if total_step:
        out.write("%-12s %12.2f %10s %7.1f%%  (span sum vs step wall)\n"
                  % ("coverage", comp_sum / 1e3, "",
                     100.0 * comp_sum / total_step))
    if beside[1]:
        out.write("%-12s %12.2f %10.3f %8s  (producer thread, beside the "
                  "step)\n" % (_BESIDE, beside[0] / 1e3,
                               beside[0] / beside[1] / 1e3, ""))
    render_counters(counters, out)


def render_counters(counters, out):
    if not counters:
        return
    out.write("\nCounters\n")
    for name in sorted(counters):
        out.write("  %-24s %s\n" % (name, counters[name]))


def breakdown_json(steps, counters, gauges, has_summary, beside=(0, 0)):
    """The --json view: the step-time breakdown as one document with the
    SAME fields the rendered table shows (totals/means/shares in ms,
    coverage, counter and gauge totals) — for dashboards and CI scripts
    that would otherwise scrape the table."""
    order = component_order(steps)
    keys = sorted(steps)
    measured = [k for k in keys if steps[k]["step"] is not None]
    total_step = sum(steps[k]["step"] for k in measured)
    nsteps = sum(steps[k]["n"] for k in measured) or len(measured)
    components = {}
    comp_sum = 0.0
    for c in order:
        tot = sum(steps[k]["components"].get(c, 0.0) for k in measured)
        comp_sum += tot
        components[c] = {
            "total_ms": tot / 1e3,
            "mean_ms": tot / nsteps / 1e3 if nsteps else 0.0,
            "share": tot / total_step if total_step else 0.0,
        }
    return {
        "steps": nsteps,
        "partial_steps": len(keys) - len(measured),
        "total_step_ms": total_step / 1e3,
        "mean_step_ms": total_step / nsteps / 1e3 if nsteps else 0.0,
        "components": components,
        "coverage": comp_sum / total_step if total_step else 0.0,
        "beside": {_BESIDE: {"total_ms": beside[0] / 1e3,
                             "count": beside[1]}} if beside[1] else {},
        "counters": counters,
        "gauges": gauges,
        "has_summary": has_summary,
    }


# --------------------------------------------------------------- curves view
_SPARK = "▁▂▃▄▅▆▇█"


def collect_scalars(events):
    """{series_key: [(step, value)] sorted} from the scalar events.  Key
    construction comes from tools/run_compare.py (the stdlib copy that is
    lockstep-tested against telemetry.series_key) — one implementation,
    same ``name[k=v,...]`` keys in the curves view and the comparison."""
    series_key = _sibling("run_compare").series_key
    series = {}
    for ev in events:
        if ev.get("type") != "scalar" or "step" not in ev:
            continue
        key = series_key(ev["name"], ev.get("tags"))
        series.setdefault(key, []).append((ev["step"], ev["value"]))
    return {k: sorted(v) for k, v in series.items()}


def sparkline(values, width=48):
    """Block-character sparkline, mean-downsampled to ``width`` columns.
    Non-finite points render as ``!`` — a NaN in a curve must be seen,
    not interpolated away."""
    if len(values) > width:
        cells, per = [], len(values) / float(width)
        for i in range(width):
            chunk = values[int(i * per):max(int((i + 1) * per),
                                            int(i * per) + 1)]
            finite = [v for v in chunk if math.isfinite(v)]
            cells.append(sum(finite) / len(finite) if finite
                         else float("nan"))
    else:
        cells = list(values)
    finite = [v for v in cells if math.isfinite(v)]
    if not finite:
        return "!" * len(cells)
    lo, hi = min(finite), max(finite)
    span = (hi - lo) or 1.0
    return "".join(
        "!" if not math.isfinite(v)
        else _SPARK[min(int((v - lo) / span * (len(_SPARK) - 1) + 0.5),
                        len(_SPARK) - 1)]
        for v in cells)


def render_curves(series, out):
    """Training-curves section: one sparkline + summary row per scalar."""
    out.write("\nScalars (training curves)\n")
    if not series:
        out.write("  no scalar events (fit curves need MXNET_TELEMETRY; "
                  "see telemetry.scalar / MXNET_SCALARS_EVERY)\n")
        return
    out.write("  %-34s %5s %10s %10s %10s %10s\n"
              % ("series", "n", "first", "last", "min", "max"))
    for key in sorted(series):
        pts = series[key]
        vals = [v for _, v in pts]
        finite = [v for v in vals if math.isfinite(v)]
        out.write("  %-34s %5d %10.5g %10.5g %10.5g %10.5g\n"
                  % (key, len(vals), vals[0], vals[-1],
                     min(finite) if finite else float("nan"),
                     max(finite) if finite else float("nan")))
        out.write("    %s\n" % sparkline(vals))


# --------------------------------------------------------------- health view
_NONFINITE = ["nonfinite_loss", "nonfinite_grad", "nonfinite_monitor"]
_INCIDENTS = ["fit_crashes", "watchdog_stalls"]


def collect_compile_spans(events):
    """Compile spans (``xla_compile``, ``compile.seconds``): a program's
    trace + lowering + compile, written by sanitize's set-up feed."""
    return [ev for ev in events if ev.get("type") == "span"
            and ev.get("cat") == "compile"]


def render_health(counters, gauges, compile_spans, out):
    """Training-health section: non-finite/incident counters, compile cost
    per jit kind, cache size, device-memory gauges — rendered only for the
    signals actually present."""
    out.write("\nHealth\n")
    wrote = False
    for name in _NONFINITE + _INCIDENTS:
        if name in counters:
            out.write("  %-28s %s\n" % (name, counters[name]))
            wrote = True
    if not any(n in counters for n in _NONFINITE) and \
            any(n in counters for n in ("fit_batches", "jit_cache_hit")):
        # absence of counters cannot distinguish "sentinel on, zero hits"
        # from "sentinel never enabled" — say exactly that
        out.write("  no nonfinite_* counters (sentinel hits would appear "
                  "here; enable MXNET_CHECK_NUMERICS to check)\n")
        wrote = True
    if compile_spans:
        by_kind = defaultdict(lambda: [0, 0.0])
        for ev in compile_spans:
            kind = (ev.get("tags") or {}).get("kind", "?")
            by_kind[kind][0] += 1
            by_kind[kind][1] += ev.get("dur", 0.0)
        total = sum(v[1] for v in by_kind.values())
        out.write("  xla_compile: %d compile(s), %.1f ms total\n"
                  % (sum(v[0] for v in by_kind.values()), total / 1e3))
        for kind in sorted(by_kind):
            n, dur = by_kind[kind]
            out.write("    %-26s %3d  %10.1f ms\n" % (kind, n, dur / 1e3))
        wrote = True
    for name in ("jit_cache_size", "grad_global_norm"):
        if name in gauges:
            out.write("  %-28s %s\n" % (name, gauges[name]))
            wrote = True
    mem = sorted(n for n in gauges
                 if n.startswith(("device_live_", "device_bytes_in_use")))
    for name in mem:
        out.write("  %-28s %s\n" % (name, gauges[name]))
        wrote = True
    if not wrote:
        out.write("  no health signals recorded (run the fit with "
                  "MXNET_TELEMETRY plus the diagnostics env vars)\n")


def _sibling(name):
    """Load a sibling tool as a library (tools/ is not a package) — how
    this CLI shares one implementation with telemetry_agg (fleet merge)
    and run_compare (series keys)."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "%s.py" % name)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _agg_lib():
    return _sibling("telemetry_agg")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="telemetry JSON-lines file (with --ranks: "
                                 "the base path of a multi-process run)")
    ap.add_argument("--steps", action="store_true",
                    help="also print the per-step table")
    ap.add_argument("--epoch", type=int, default=None,
                    help="restrict to one epoch")
    ap.add_argument("--health", action="store_true",
                    help="also print the training-health section "
                         "(non-finite / compile / memory signals)")
    ap.add_argument("--curves", action="store_true",
                    help="also print every scalar time-series as a "
                         "terminal sparkline (training curves)")
    ap.add_argument("--ranks", action="store_true",
                    help="merge <path>.rank* into the fleet view (summed "
                         "counters, bucket-merged histograms, per-rank "
                         "skew + straggler report); the bare <path> is "
                         "used only when no rank files exist")
    ap.add_argument("--json", action="store_true",
                    help="emit the step-time breakdown (or, with --ranks, "
                         "the merged fleet view) as one JSON document "
                         "instead of the rendered tables")
    args = ap.parse_args(argv)
    if args.ranks and (args.health or args.steps or args.curves or
                       args.epoch is not None):
        ap.error("--ranks renders the fleet view only; --health/--steps/"
                 "--curves/--epoch apply to a single-rank report (run "
                 "them against one <path>.rankN file)")
    if args.json and (args.health or args.steps or args.curves):
        ap.error("--json emits the breakdown document; --health/--steps/"
                 "--curves shape the rendered tables only")
    if args.ranks:
        agg = _agg_lib()
        files = agg.rank_files(args.path)
        if not files:
            sys.stderr.write("telemetry_report: no files match %s[.rank*]\n"
                             % args.path)
            return 1
        merged = agg.aggregate(files)
        if args.json:
            json.dump(agg._strip_per_rank(merged), sys.stdout, indent=1,
                      default=str)
            sys.stdout.write("\n")
        else:
            agg.render(merged)
        return 0
    try:
        events = load_events(args.path)
    except (OSError, UnicodeDecodeError) as e:
        sys.stderr.write("telemetry_report: cannot read %s: %s\n"
                         % (args.path, getattr(e, "strerror", None) or e))
        return 1
    counters, gauges, has_summary = summary_state(events)
    if args.json:
        doc = breakdown_json(collect_steps(events, epoch=args.epoch),
                             counters, gauges, has_summary,
                             beside=beside_step(events))
        json.dump(doc, sys.stdout, indent=1, default=str)
        sys.stdout.write("\n")
        return 0
    if events and not has_summary:
        sys.stdout.write("note: no summary event — run still live or died "
                         "before telemetry.stop(); totals folded from the "
                         "raw stream\n")
    render(collect_steps(events, epoch=args.epoch), counters,
           per_step=args.steps, beside=beside_step(events))
    if args.health:
        render_health(counters, gauges, collect_compile_spans(events),
                      sys.stdout)
    if args.curves:
        render_curves(collect_scalars(events), sys.stdout)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:   # e.g. `... | head`
        import os
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
