#!/usr/bin/env python
"""Cross-rank telemetry aggregation + straggler detection.

A multi-process run under the MXTPU_* launch contract (tools/launch.py)
writes one telemetry JSON-lines file per rank (``<path>.rank<N>`` — see
``MXNET_TELEMETRY`` in docs/env_var.md).  This tool merges them into one
fleet view:

* **counters** are summed across ranks (``fit_samples`` becomes the global
  sample count),
* **histograms** are bucket-merged (bounds are fixed and identical across
  ranks, so the merge is an associative per-bound count sum) and reported
  as p50/p90/p99,
* **gauges** stay per-rank (a last-value-wins metric has no meaningful
  cross-rank sum),

and computes per-rank skew over the latency-critical spans (``step``,
``dist.allreduce`` by default): per-rank count/mean/p50/p99 from the raw
span durations, the slowest rank, and the skew ratio (slowest mean over
the median mean of the other ranks).  A ratio above ``--straggler-ratio``
(default 1.25) flags the straggler — the rank every collective waits for.

The **step-anatomy table** decomposes each rank's mean step into the
phases the fit loop's span families already record — ``data_wait``
(input pipeline), compute (``fused_step`` or the general-path
``forward``/``backward``/``update``/``forward_backward`` plus
``metric``, exclusive of the comm/stall nested inside), comm
(``dist.allreduce``, ``zero.gather``), stall (``pp.bubble``) and the
unattributed remainder — and its straggler verdict names the rank AND
the phase that makes it slow ("rank 1 is 3.1x the fleet, dominated by
data_wait"), turning "who is slow" into "what to fix".

``--timeline OUT.json`` additionally writes the offset-corrected fleet
timeline (one chrome-trace track per rank, via tools/trace_merge.py —
load it at https://ui.perfetto.dev).

Usage:
    python tools/telemetry_agg.py /tmp/t.jsonl          # base: globs .rank*
    python tools/telemetry_agg.py /tmp/t.jsonl.rank0 /tmp/t.jsonl.rank1
    python tools/telemetry_agg.py /tmp/t.jsonl --json   # machine-readable
    python tools/telemetry_agg.py /tmp/t.jsonl --timeline fleet.trace.json

Pure stdlib (usable offline, away from the training image); also imported
as a library by ``tools/telemetry_report.py --ranks``.  Histogram quantile
estimation and MERGING need no bucket-scheme knowledge — the exported
format is self-describing (sparse ``{upper_bound: count}`` plus the bucket
ratio).  Rebuilding a summary-less rank's histograms from its raw stream
(a killed or still-live rank never ran ``telemetry.stop()``) does need the
scheme, so this module carries a stdlib copy of it alongside
``quantile_from_hist``; a unit test holds the two implementations together.
"""
from __future__ import annotations

import argparse
import glob as _glob
import json
import math
import os
import re
import sys
from collections import defaultdict

SKEW_SPANS = ("step", "dist.allreduce")
STRAGGLER_RATIO = 1.25

# step-anatomy phase families (mxnet_tpu span names).  Compute lists the
# fit loop's mutually-exclusive alternatives (the fused span OR the
# general-path trio OR the grad-array variant) — whichever path ran is
# the only one populated, so summing the family never double-counts.
# comm and stall spans nest INSIDE the compute spans (the kvstore
# allreduce runs inside ``update``, the pipeline bubble inside
# ``fused_step``), so compute is reported exclusive of them.
# ``callback`` is the batch-end callbacks' time on the loop's thread;
# ``input.stage`` is the device prefetcher's staging on its producer
# thread, beside the step and not part of it (OFF_THREAD: a column of
# its own, left out of the residual ``other``).
ANATOMY_PHASES = (
    ("data_wait", ("data_wait",)),
    ("compute", ("fused_step", "forward_backward", "forward", "backward",
                 "update", "metric")),
    ("comm", ("dist.allreduce", "zero.gather")),
    ("stall", ("pp.bubble",)),
    ("callback", ("callback",)),
    ("input.stage", ("input.stage",)),
)
OFF_THREAD = ("input.stage",)

# span-fed histograms and span durations are microseconds (telemetry.py)
_US_PER_MS = 1e3


# ------------------------------------------------------------------- loading
def load_events(path):
    """Parse one JSON-lines file; a partial trailing line (live run) is
    ignored."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except ValueError:
                continue
    return events


def since_us_of(value):
    """Normalise a --since timestamp to event-stream µs.  Values below
    1e12 are treated as seconds-since-epoch (``date +%s``, bundle
    ``time`` fields); larger values are already µs (event ``ts`` fields)
    — the two regimes are ~6 orders of magnitude apart, so the split
    point is unambiguous for any date this side of the year 33000."""
    value = float(value)
    return value * 1e6 if value < 1e12 else value


def window_events(events, since_us=None, last_steps=None):
    """Slice one rank's event stream to a time window: events at/after
    ``since_us`` (µs), and/or only the last ``last_steps`` training steps
    (anchored at the n-th-from-last ``step`` span's start).  Any active
    window DROPS the run's summary event — its totals cover the whole
    run, so keeping it would let whole-run histograms shadow the
    windowed rebuild (fold_rank prefers summaries by design).  Returns
    the (possibly) filtered list."""
    if since_us is None and last_steps is None:
        return events
    evs = [ev for ev in events if ev.get("type") != "summary"]
    if since_us is not None:
        evs = [ev for ev in evs if float(ev.get("ts", 0)) >= since_us]
    if last_steps is not None:
        steps = [ev for ev in evs
                 if ev.get("type") == "span" and ev.get("name") == "step"]
        if len(steps) > last_steps:
            cut = float(steps[-last_steps].get("ts", 0))
            evs = [ev for ev in evs if float(ev.get("ts", 0)) >= cut]
    return evs


def rank_of(path):
    """Rank from the launch-contract filename suffix, else None."""
    m = re.search(r"\.rank(\d+)$", path)
    return int(m.group(1)) if m else None


def rank_files(base):
    """Per-rank files of one run: ``base.rank*``, rank-sorted.  The bare
    ``base`` (a single-process run writes no suffix) is used only when NO
    rank files exist — a leftover single-process file must not join a
    multi-process merge, where it would shift every real rank's label and
    fold a stale run's data into the fleet totals."""
    files = sorted((p for p in _glob.glob(_glob.escape(base) + ".rank*")
                    if rank_of(p) is not None),
                   key=rank_of)
    if not files and os.path.exists(base):
        return [base]
    return files


def fold_rank(events):
    """One rank's {counters, gauges, histograms, span_durs}.  Prefers the
    run's summary event; a file without one (run still live, or killed)
    folds counters/gauges from the raw stream and REBUILDS its histograms
    from the span durations and explicit ``hist`` events, so a dead rank —
    in a straggler investigation, exactly the rank whose latency matters —
    still contributes to the merged fleet view.  ``span_durs`` (raw span
    durations per name, µs) always comes from the stream — it is the
    exact-percentile source for the skew tables."""
    counters, gauges, hists, has_summary = {}, {}, {}, False
    for ev in reversed(events):
        if ev.get("type") == "summary":
            counters = dict(ev.get("counters", {}))
            gauges = dict(ev.get("gauges", {}))
            hists = dict(ev.get("histograms", {}))
            has_summary = True
            break
    span_durs = defaultdict(list)
    stage_durs = defaultdict(list)
    hist_vals = defaultdict(list)
    for ev in events:
        t = ev.get("type")
        if t == "span":
            span_durs[ev["name"]].append(ev.get("dur", 0.0))
            # pipeline stage spans additionally fold by their stage tag —
            # the per-STAGE skew view (the pp analogue of per-rank skew).
            # The schedule tag folds into the key (stage@schedule) so a
            # run that switched MXNET_PP_SCHEDULE mid-stream keeps its
            # gpipe and 1f1b observations separate, and a SLOW STAGE
            # verdict names the schedule it was observed under.
            if ev["name"] == "pp.stage" and \
                    (ev.get("tags") or {}).get("stage") is not None:
                tags = ev["tags"]
                key = str(tags["stage"])
                if tags.get("schedule"):
                    key = "%s@%s" % (key, tags["schedule"])
                stage_durs[key].append(ev.get("dur", 0.0))
        elif not has_summary:
            if t == "counter":
                counters[ev["name"]] = ev.get("total", 0)
            elif t == "gauge":
                gauges[ev["name"]] = ev.get("value")
            elif t == "hist":
                hist_vals[ev["name"]].append(ev.get("value", 0.0))
    if not has_summary:
        # span closes feed their histogram without a separate hist event
        # (telemetry.record_span), so the rebuild sources are span durs
        # plus the explicit histogram() observations
        for name, durs in span_durs.items():
            hist_vals[name] = list(durs) + hist_vals.get(name, [])
        hists = {name: h for name, h in
                 ((n, rebuild_hist(vs)) for n, vs in hist_vals.items())
                 if h is not None}
    return {"counters": counters, "gauges": gauges, "histograms": hists,
            "span_durs": dict(span_durs), "stage_durs": dict(stage_durs),
            "has_summary": has_summary}


# ------------------------------------------------------- histogram rebuild
# Stdlib copy of mxnet_tpu.telemetry's fixed bucket scheme (20 buckets per
# decade, finite upper bounds 10**-1 .. 10**10, overflow bucket beyond) —
# held in lockstep by test_fleet_observability.  Needed only to rebuild a
# summary-less rank's histograms; merging and quantiles stay scheme-free.
_HIST_PER_DECADE = 20
_HIST_MIN_EXP = -1
_HIST_MAX_EXP = 10
_HIST_NFINITE = (_HIST_MAX_EXP - _HIST_MIN_EXP) * _HIST_PER_DECADE
_HIST_RATIO = 10.0 ** (1.0 / _HIST_PER_DECADE)


def _hist_bound(index):
    if index > _HIST_NFINITE:
        return float("inf")
    return 10.0 ** (_HIST_MIN_EXP + index / _HIST_PER_DECADE)


def _hist_index(value):
    if value <= 10.0 ** _HIST_MIN_EXP:
        return 0
    if value > 10.0 ** _HIST_MAX_EXP:
        return _HIST_NFINITE + 1
    idx = int(math.ceil((math.log10(value) - _HIST_MIN_EXP)
                        * _HIST_PER_DECADE))
    return min(max(idx, 1), _HIST_NFINITE)


def rebuild_hist(values):
    """Exported-format histogram from raw observations — what
    ``telemetry.stop()`` would have written had the rank lived to run it.
    Bucket keys use the same ``%.6g`` bound formatting as the exporter so
    the result merges cleanly with real summary histograms.  Returns None
    when no finite observation exists."""
    finite = [float(v) for v in values if math.isfinite(float(v))]
    if not finite:
        return None
    buckets = {}
    for v in finite:
        b = _hist_bound(_hist_index(v))
        key = "inf" if math.isinf(b) else "%.6g" % b
        buckets[key] = buckets.get(key, 0) + 1
    return {"count": len(finite), "sum": sum(finite), "min": min(finite),
            "max": max(finite), "ratio": _HIST_RATIO, "buckets": buckets}


# ------------------------------------------------------------------- merging
def merge_histograms(a, b):
    """Bucket-merge two exported histograms (same fixed bounds across all
    processes ⇒ a per-bound count sum — associative and commutative)."""
    if a is None:
        return dict(b)
    buckets = dict(a.get("buckets", {}))
    for k, n in b.get("buckets", {}).items():
        buckets[k] = buckets.get(k, 0) + n
    return {
        "count": a.get("count", 0) + b.get("count", 0),
        "sum": a.get("sum", 0.0) + b.get("sum", 0.0),
        "min": min(a.get("min"), b.get("min")),
        "max": max(a.get("max"), b.get("max")),
        "ratio": a.get("ratio") or b.get("ratio"),
        "buckets": buckets,
    }


def quantile_from_hist(h, q):
    """Stdlib copy of mxnet_tpu.telemetry.quantile_from_hist (kept in
    lockstep by test_fleet_observability)."""
    count = h.get("count", 0)
    if not count:
        return None
    q = min(max(float(q), 0.0), 1.0)
    lo_all = h.get("min")
    hi_all = h.get("max")
    ratio = h.get("ratio") or 10.0 ** 0.05
    entries = sorted(((float("inf") if k == "inf" else float(k), n)
                      for k, n in h.get("buckets", {}).items()),
                     key=lambda kv: kv[0])
    target = q * count
    cum = 0
    for i, (bound, n) in enumerate(entries):
        if cum + n < target and i < len(entries) - 1:
            cum += n
            continue
        if math.isinf(bound):
            lo = entries[i - 1][0] if i else lo_all
            hi = hi_all
        else:
            lo = lo_all if (i == 0 and lo_all is not None) else bound / ratio
            hi = bound
        if hi_all is not None:
            hi = min(hi, hi_all)
        if lo_all is not None:
            lo = min(max(lo, lo_all), hi)
        frac = (target - cum) / n if n else 1.0
        frac = min(max(frac, 0.0), 1.0)
        if lo <= 0 or hi <= 0:
            return lo + (hi - lo) * frac
        return lo * (hi / lo) ** frac
    return hi_all


def percentile(values, q):
    """Exact linear-interpolation percentile (numpy 'linear' method) of a
    list of raw values."""
    if not values:
        return None
    vals = sorted(values)
    pos = (len(vals) - 1) * min(max(float(q), 0.0), 1.0)
    lo = int(math.floor(pos))
    hi = int(math.ceil(pos))
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def merge_ranks(per_rank):
    """{rank: fold_rank dict} → fleet view: summed counters, bucket-merged
    histograms, per-rank gauges."""
    counters = defaultdict(int)
    hists = {}
    gauges = {}
    for rank in sorted(per_rank):
        st = per_rank[rank]
        for name, v in st["counters"].items():
            counters[name] += v
        for name, h in st["histograms"].items():
            hists[name] = merge_histograms(hists.get(name), h)
        gauges[rank] = st["gauges"]
    return {"counters": dict(counters), "histograms": hists,
            "gauges_by_rank": gauges}


# ----------------------------------------------------------- straggler skew
def skew_table(per_rank, name):
    """Per-rank latency stats for span ``name`` from raw durations (µs):
    {rank: {count, mean, p50, p99}}; ranks without the span are absent."""
    table = {}
    for rank, st in per_rank.items():
        durs = st["span_durs"].get(name)
        if not durs:
            continue
        table[rank] = {"count": len(durs),
                       "mean": sum(durs) / len(durs),
                       "p50": percentile(durs, 0.50),
                       "p99": percentile(durs, 0.99)}
    return table


def straggler_report(per_rank, names=SKEW_SPANS, ratio=STRAGGLER_RATIO):
    """Skew analysis over the latency-critical spans: for each span
    present on ≥1 rank, the per-rank table, the slowest rank by mean, and
    the skew ratio (slowest mean / median mean of the other ranks).
    ``straggler`` is set when ≥2 ranks disagree by more than ``ratio``."""
    report = {}
    for name in names:
        table = skew_table(per_rank, name)
        if not table:
            continue
        means = sorted((rec["mean"], rank) for rank, rec in table.items())
        slowest_mean, slowest_rank = means[-1]
        # skew against the median of the OTHER ranks — "the straggler is
        # Nx the typical rank", which stays meaningful at world size 2
        rest = [m for m, _ in means[:-1]] or [slowest_mean]
        median_mean = percentile(rest, 0.5)
        skew = slowest_mean / median_mean if median_mean else float("inf")
        report[name] = {
            "ranks": table,
            "slowest_rank": slowest_rank,
            "skew_ratio": skew,
            "straggler": slowest_rank if (len(table) >= 2 and skew >= ratio)
            else None,
        }
    return report


def stage_skew_report(per_rank, ratio=STRAGGLER_RATIO):
    """Pipeline per-STAGE skew from the ``pp.stage`` spans (stage-tagged
    per-step busy time, mxnet_tpu/train.py PipelineTrainStep): durations
    merged across ranks per stage, the slowest stage by mean, and the skew
    ratio vs the median of the other stages — naming the stage the
    schedule's bubbles wait for, the way the per-rank view names straggler
    ranks.  Empty dict when no pipeline spans exist."""
    merged = defaultdict(list)
    for st in per_rank.values():
        for stage, durs in st.get("stage_durs", {}).items():
            merged[stage].extend(durs)
    if not merged:
        return {}
    def _split(key):
        # fold_rank keys pipeline spans "stage" or "stage@schedule"
        stage, _, sched = key.partition("@")
        return stage, (sched or None)

    table = {}
    for stage in sorted(merged, key=lambda s: (len(s), s)):
        durs = merged[stage]
        table[stage] = {"count": len(durs),
                        "mean": sum(durs) / len(durs),
                        "p50": percentile(durs, 0.50),
                        "p99": percentile(durs, 0.99),
                        "schedule": _split(stage)[1]}
    # skew is judged WITHIN one schedule group: a mid-run
    # MXNET_PP_SCHEDULE toggle splits stages into stage@sched keys, and
    # comparing a warmup-skewed small-sample group against the other
    # schedule's steady state would fabricate a SLOW STAGE verdict; the
    # reported verdict is the worst group's
    means = sorted((rec["mean"], stage) for stage, rec in table.items())
    groups = {}
    for m, stage in means:
        groups.setdefault(_split(stage)[1], []).append((m, stage))
    worst = None   # (skew, slowest_mean, slowest_stage, group size)
    for g in groups.values():
        g_mean, g_stage = g[-1]
        rest = [m for m, _ in g[:-1]] or [g_mean]
        median_mean = percentile(rest, 0.5)
        sk = g_mean / median_mean if median_mean else float("inf")
        if worst is None or (sk, g_mean) > worst[:2]:
            worst = (sk, g_mean, g_stage, len(g))
    skew, _, slowest_stage, group_n = worst
    return {
        "stages": table,
        "slowest_stage": slowest_stage,
        "slowest_schedule": _split(slowest_stage)[1],
        "skew_ratio": skew,
        "slow_stage": slowest_stage if (group_n >= 2 and skew >= ratio)
        else None,
    }


def step_anatomy(per_rank, ratio=STRAGGLER_RATIO):
    """Per-rank, per-phase decomposition of the mean step (ms) from the
    fit loop's span families (see ANATOMY_PHASES), plus a verdict that
    names the straggler rank AND the phase responsible: the phase whose
    per-step mean exceeds the median of the other ranks' by the largest
    margin.  Empty dict when no rank recorded ``step`` spans."""
    table = {}
    for rank, st in per_rank.items():
        durs = st["span_durs"]
        steps = durs.get("step")
        if not steps:
            continue
        n = len(steps)
        row = {"steps": n, "step_ms": sum(steps) / n / _US_PER_MS}
        totals = {}
        for phase, names in ANATOMY_PHASES:
            totals[phase] = sum(sum(durs.get(nm, ())) for nm in names)
        # compute exclusive of the comm/stall spans nested inside it
        totals["compute"] = max(
            0.0, totals["compute"] - totals["comm"] - totals["stall"])
        for phase in totals:
            row[phase + "_ms"] = totals[phase] / n / _US_PER_MS
        on_thread = sum(v for k, v in totals.items() if k not in OFF_THREAD)
        row["other_ms"] = max(
            0.0, row["step_ms"] - on_thread / n / _US_PER_MS)
        # the rank's last MFU gauge (fit loop, MXNET_PEAK_FLOPS): the
        # efficiency column next to the time decomposition — absent
        # when peaks were unset during the run
        mfu = st.get("gauges", {}).get("mfu")
        if isinstance(mfu, (int, float)):
            row["mfu"] = float(mfu)
        # the rank's last sampled global gradient norm (MXNET_MONITOR,
        # mxnet_tpu/numerics.py): the training-dynamics column next to
        # the efficiency one — absent when the monitor was off
        gn = st.get("gauges", {}).get("grad_global_norm")
        if isinstance(gn, (int, float)):
            row["grad_norm"] = float(gn)
        table[rank] = row
    if not table:
        return {}
    phases = [p for p, _ in ANATOMY_PHASES] + ["other"]
    means = sorted((rec["step_ms"], rank) for rank, rec in table.items())
    slowest_mean, slowest_rank = means[-1]
    rest = [m for m, _ in means[:-1]] or [slowest_mean]
    median_mean = percentile(rest, 0.5)
    skew = slowest_mean / median_mean if median_mean else float("inf")
    # blame the phase with the largest per-step excess over the other
    # ranks' median — the phase a fix would actually buy time in
    blame, blame_excess = None, 0.0
    for phase in phases:
        col = phase + "_ms"
        others = [table[r][col] for r in table if r != slowest_rank] \
            or [table[slowest_rank][col]]
        excess = table[slowest_rank][col] - percentile(others, 0.5)
        if blame is None or excess > blame_excess:
            blame, blame_excess = phase, excess
    return {
        "ranks": table,
        "phases": phases,
        "slowest_rank": slowest_rank,
        "skew_ratio": skew,
        "slow_phase": blame,
        "slow_phase_excess_ms": blame_excess,
        "straggler": slowest_rank if (len(table) >= 2 and skew >= ratio)
        else None,
    }


# ----------------------------------------------------------------- top level
def aggregate(paths, skew_spans=SKEW_SPANS, ratio=STRAGGLER_RATIO,
              since_us=None, last_steps=None):
    """Load + merge a set of per-rank files.  Files without a rank suffix
    get sequential pseudo-ranks so single-file input still renders.
    ``since_us``/``last_steps`` window each rank's stream before folding
    (see :func:`window_events`) — every downstream table, the step
    anatomy included, then describes only the window."""
    per_rank = {}
    for path in paths:
        rank = rank_of(path)
        if rank is None or rank in per_rank:
            rank = 0
            while rank in per_rank:
                rank += 1
        events = window_events(load_events(path), since_us=since_us,
                               last_steps=last_steps)
        per_rank[rank] = fold_rank(events)
        per_rank[rank]["path"] = path
    merged = merge_ranks(per_rank)
    merged["ranks"] = sorted(per_rank)
    merged["skew"] = straggler_report(per_rank, names=skew_spans,
                                      ratio=ratio)
    merged["stage_skew"] = stage_skew_report(per_rank, ratio=ratio)
    merged["anatomy"] = step_anatomy(per_rank, ratio=ratio)
    merged["per_rank"] = per_rank
    return merged


def render(agg, out=None):
    # resolve sys.stdout at CALL time: a def-time default would freeze
    # whatever stream was installed at first import (pytest capture,
    # redirected stdout) and break every later caller once it closes
    out = sys.stdout if out is None else out
    ranks = agg["ranks"]
    out.write("Fleet telemetry: %d rank file(s) (%s)\n"
              % (len(ranks), ", ".join("rank%s" % r for r in ranks)))
    win = agg.get("window")
    if win:
        parts = []
        if win.get("since") is not None:
            parts.append("since %s" % win["since"])
        if win.get("last") is not None:
            parts.append("last %d step(s)" % win["last"])
        out.write("window: %s — summaries dropped, all tables rebuilt "
                  "from the windowed stream\n" % ", ".join(parts))
    live = [r for r in ranks if not agg["per_rank"][r]["has_summary"]]
    if live:
        out.write("note: no summary event for rank(s) %s — run still live "
                  "or killed; totals and histograms rebuilt from the raw "
                  "stream\n"
                  % ", ".join(str(r) for r in live))

    hists = agg["histograms"]
    if hists:
        out.write("\nLatency histograms (bucket-merged; recorded in µs, "
                  "shown in ms)\n")
        out.write("%-20s %8s %10s %10s %10s %10s\n"
                  % ("name", "count", "p50_ms", "p90_ms", "p99_ms",
                     "max_ms"))
        for name in sorted(hists):
            h = hists[name]
            qs = [quantile_from_hist(h, q) for q in (0.50, 0.90, 0.99)]
            out.write("%-20s %8d %10.3f %10.3f %10.3f %10.3f\n"
                      % ((name, h["count"])
                         + tuple((v or 0.0) / _US_PER_MS for v in qs)
                         + (h["max"] / _US_PER_MS,)))

    for name, rep in agg["skew"].items():
        out.write("\nPer-rank skew — span '%s'\n" % name)
        out.write("%6s %8s %10s %10s %10s\n"
                  % ("rank", "n", "mean_ms", "p50_ms", "p99_ms"))
        for rank in sorted(rep["ranks"]):
            rec = rep["ranks"][rank]
            out.write("%6s %8d %10.3f %10.3f %10.3f\n"
                      % (rank, rec["count"], rec["mean"] / _US_PER_MS,
                         rec["p50"] / _US_PER_MS, rec["p99"] / _US_PER_MS))
        verdict = "STRAGGLER" if rep["straggler"] is not None else "ok"
        out.write("  slowest rank: %s (%.2fx the median of the other "
                  "ranks) — %s\n"
                  % (rep["slowest_rank"], rep["skew_ratio"], verdict))

    stage = agg.get("stage_skew")
    if stage:
        out.write("\nPer-stage skew — pipeline 'pp.stage' busy time\n")
        out.write("%6s %8s %10s %10s %10s\n"
                  % ("stage", "n", "mean_ms", "p50_ms", "p99_ms"))
        for sname in sorted(stage["stages"], key=lambda s: (len(s), s)):
            rec = stage["stages"][sname]
            out.write("%6s %8d %10.3f %10.3f %10.3f\n"
                      % (sname, rec["count"], rec["mean"] / _US_PER_MS,
                         rec["p50"] / _US_PER_MS, rec["p99"] / _US_PER_MS))
        verdict = "SLOW STAGE" if stage["slow_stage"] is not None else "ok"
        sched = stage.get("slowest_schedule")
        out.write("  slowest stage: %s%s (%.2fx the median of the other "
                  "stages) — %s\n"
                  % (stage["slowest_stage"].partition("@")[0],
                     " [schedule %s]" % sched if sched else "",
                     stage["skew_ratio"], verdict))

    anatomy = agg.get("anatomy")
    if anatomy:
        cols = anatomy["phases"]
        has_mfu = any("mfu" in rec for rec in anatomy["ranks"].values())
        has_gn = any("grad_norm" in rec
                     for rec in anatomy["ranks"].values())
        out.write("\nStep anatomy (per-rank mean, ms/step)\n")
        out.write("%6s %8s %10s" % ("rank", "steps", "step_ms"))
        for p in cols:
            out.write(" %10s" % p)
        if has_mfu:
            out.write(" %10s" % "mfu")
        if has_gn:
            out.write(" %10s" % "grad_norm")
        out.write("\n")
        for rank in sorted(anatomy["ranks"]):
            rec = anatomy["ranks"][rank]
            out.write("%6s %8d %10.3f" % (rank, rec["steps"],
                                          rec["step_ms"]))
            for p in cols:
                out.write(" %10.3f" % rec[p + "_ms"])
            if has_mfu:
                out.write(" %10s" % ("%.4f" % rec["mfu"]
                                     if "mfu" in rec else "-"))
            if has_gn:
                out.write(" %10s" % ("%.4g" % rec["grad_norm"]
                                     if "grad_norm" in rec else "-"))
            out.write("\n")
        verdict = "STRAGGLER" if anatomy["straggler"] is not None else "ok"
        out.write("  slowest rank: %s (%.2fx the median of the other "
                  "ranks), dominated by %s (+%.3f ms/step vs the fleet) "
                  "— %s\n"
                  % (anatomy["slowest_rank"], anatomy["skew_ratio"],
                     anatomy["slow_phase"],
                     anatomy["slow_phase_excess_ms"], verdict))

    counters = agg["counters"]
    if counters:
        out.write("\nCounters (summed across ranks)\n")
        for name in sorted(counters):
            out.write("  %-24s %s\n" % (name, counters[name]))

    gauges = agg["gauges_by_rank"]
    shown = sorted({n for g in gauges.values() for n in g})
    if shown:
        out.write("\nGauges (per rank)\n")
        for name in shown:
            vals = ", ".join("rank%s=%s" % (r, gauges[r][name])
                             for r in sorted(gauges) if name in gauges[r])
            out.write("  %-24s %s\n" % (name, vals))


def _sibling(name):
    """Load a sibling tool as a library (tools/ is not a package) — the
    telemetry_report idiom; --timeline shares trace_merge's one merge
    implementation instead of growing a second."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "%s.py" % name)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _strip_per_rank(agg):
    """The --json view: drop the bulky raw-duration lists, keep the stats."""
    out = {k: v for k, v in agg.items() if k != "per_rank"}
    out["files"] = {r: agg["per_rank"][r]["path"] for r in agg["ranks"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="+",
                    help="per-rank telemetry files, or ONE base path "
                         "(expands to <base>.rank* per the launch contract)")
    ap.add_argument("--span", action="append", default=None,
                    help="additional span name(s) for the skew analysis "
                         "(default: %s)" % ", ".join(SKEW_SPANS))
    ap.add_argument("--straggler-ratio", type=float, default=STRAGGLER_RATIO,
                    help="flag a straggler when slowest/median rank mean "
                         "exceeds this (default %(default)s)")
    ap.add_argument("--since", metavar="TS", type=float, default=None,
                    help="window: only events at/after TS — seconds since "
                         "epoch (date +%%s, bundle 'time' fields) or raw "
                         "event-stream µs; drops run summaries so every "
                         "table is rebuilt from the windowed stream")
    ap.add_argument("--last", metavar="N", type=int, default=None,
                    help="window: only the last N training steps per rank "
                         "(anchored at each rank's N-th-from-last 'step' "
                         "span); composes with --since")
    ap.add_argument("--json", action="store_true",
                    help="emit the merged view as one JSON document")
    ap.add_argument("--timeline", metavar="OUT",
                    help="also write the offset-corrected fleet timeline "
                         "(chrome-trace JSON, one track per rank) via "
                         "tools/trace_merge.py")
    args = ap.parse_args(argv)
    paths = list(args.paths)
    if len(paths) == 1 and rank_of(paths[0]) is None:
        paths = rank_files(paths[0])
        if not paths:
            sys.stderr.write("telemetry_agg: no files match %s[.rank*]\n"
                             % args.paths[0])
            return 1
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        sys.stderr.write("telemetry_agg: cannot read %s\n"
                         % ", ".join(missing))
        return 1
    if args.last is not None and args.last <= 0:
        sys.stderr.write("telemetry_agg: --last must be positive\n")
        return 1
    spans = tuple(SKEW_SPANS) + tuple(args.span or ())
    agg = aggregate(paths, skew_spans=spans, ratio=args.straggler_ratio,
                    since_us=(since_us_of(args.since)
                              if args.since is not None else None),
                    last_steps=args.last)
    if args.since is not None or args.last is not None:
        agg["window"] = {"since": args.since, "last": args.last}
    if args.timeline:
        tm = _sibling("trace_merge")
        doc, _notes = tm.merge_paths(paths)
        with open(args.timeline, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        sys.stderr.write("telemetry_agg: wrote fleet timeline (%d trace "
                         "event(s)) to %s\n"
                         % (len(doc["traceEvents"]), args.timeline))
    if args.json:
        json.dump(_strip_per_rank(agg), sys.stdout, indent=1, default=str)
        sys.stdout.write("\n")
    else:
        render(agg)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
