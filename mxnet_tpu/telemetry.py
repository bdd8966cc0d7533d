"""Unified runtime telemetry — counters, gauges, and timed spans.

The reference lineage ships three disconnected observability affordances
(the engine profiler's chrome trace, the per-tensor ``Monitor``, and the
``Speedometer`` callback).  This module is the shared substrate underneath
all of them: a process-wide, thread-safe registry of

* **counters**   — monotonically accumulated values (``jit_cache_hit``,
  ``kvstore_push_bytes``, ``fit_samples``, ...),
* **gauges**     — last-value-wins measurements (``epoch_time``), and
* **spans**      — timed regions with arbitrary tags (``data_wait``,
  ``forward``, ``backward``, ``update`` per fit batch), and
* **histograms** — fixed log-spaced bucket distributions with p50/p90/p99
  estimation (``histogram(name, value)``); every span close also feeds a
  latency histogram of the same name automatically, so tail latency for
  ``step``, ``forward``, ``dist.allreduce``, ``predict.forward``, ... is
  always available while recording, and
* **scalars**    — per-step time-series points (``scalar(name, step,
  value)``): ``train_loss``, ``lr``, ``grad_norm``, ``throughput``, ...
  — the training-curve leg of the stack.  ``MXNET_SCALARS_EVERY=N``
  samples the per-step producers (fit metrics, optimizer introspection)
  down to every N-th step via ``scalar_due(step)`` so the device syncs
  those values cost stay bounded; ``tools/run_compare.py`` aligns the
  recorded curves across runs,

exported as JSON-lines events; ``tools/telemetry_report.py`` renders a
step-time breakdown table from a JSON-lines file.

One span, two sinks: ``span(name, **tags)`` always enters a
``jax.profiler.TraceAnnotation("mx:" + name, **tags)`` — an atomic check
and nothing else while no profiler session is live — so the program's
spans land on the host plane of the same xplane as the device's
operations, on its clock, whenever anyone traces (``mx.profiler``,
``jax.profiler.start_trace``, the benchmark's ``--trace 1``).  Only while
the registry is enabled does it also record the JSON-lines event under
the unprefixed name.  Recording never changes what runs: no path is
switched and no span waits for the device, so a span around a dispatch
is the host's launch time and the device's time is the device trace's.

Off-by-default contract: when telemetry is disabled (the normal state)
``counter``/``gauge``/``scalar`` return at a single module-global bool
check, ``span()`` builds the annotation alone (no clock read, no event),
importing this module does not import jax (the first span does), and no
hot path gains a device sync.  Call sites in hot loops guard their
counters with ``if telemetry._enabled:`` so they do not even build the
kwargs dict.

Enable programmatically with ``start(path)`` / ``stop()``, or for a whole
process with ``MXNET_TELEMETRY=<path.jsonl>`` (autostart at import, flush
at exit — the env-var analogue of ``MXNET_PROFILER_AUTOSTART``).

Flight recorder: ``MXNET_FLIGHT_RECORDER=N`` arms a bounded in-memory
ring of the last N closed events (spans / counter deltas / scalars —
shape/time metadata only) WITHOUT a file sink, threads, or device syncs.
The hot-path call sites light up (``_enabled`` goes True) but
``enabled()`` stays False so nothing that keys a cost on "full
telemetry" (``scalar_due`` device syncs, file export) reacts.  The ring's only consumer is the
diagnostics bundle: a crash, fatal signal, sanitizer ``:raise``
violation, or watchdog stall dump carries the last ~N events of
timeline without anyone having pre-armed full telemetry
(docs/observability.md).
"""
from __future__ import annotations

import atexit
import json
import math
import threading
import time
from collections import deque

from .base import get_env

__all__ = ["start", "stop", "enabled", "span", "record_span", "counter",
           "gauge", "histogram", "scalar", "scalar_due", "value",
           "counters", "gauges", "histograms", "scalars", "quantile",
           "quantile_from_hist", "hist_bound", "events", "recent_events",
           "flush", "reset", "sink_path", "flight_recorder",
           "flight_recorder_armed", "device_counter",
           "collect_device_counters", "publish_device_counters",
           "device_counters"]

_lock = threading.RLock()
_enabled = False
_path = None
_buffer = deque()     # pending event dicts (drained to _path on flush)
_counters = {}
_gauges = {}
_histograms = {}      # name -> [count, sum, min, max, {bucket_index: n}]
_scalars = {}         # series key -> [n, last_step, last_value]
_scalars_every = 1    # MXNET_SCALARS_EVERY, re-read at every start()
_atexit_armed = False
_FLUSH_EVERY = 1024   # buffered events before an automatic file flush
_BUFFER_CAP = 262144  # in-memory mode: drop oldest beyond this
_RECENT_CAP = 512     # event-stream tail kept past flushes (diagnostics)
_recent = deque(maxlen=_RECENT_CAP)
_dropped = 0
_gc_open = None       # (annotation, wall, perf_counter) of the running gc pass
_gc_done = deque()    # (wall, seconds, generation) of passes not yet emitted
# Flight recorder (MXNET_FLIGHT_RECORDER=N): a bounded ring of the last N
# events, fed by _emit_locked whenever armed.  In *fr-only* mode (_enabled
# True purely because the recorder armed it) events go ONLY to the ring —
# no buffer growth, no file sink, no _recent churn — and enabled() stays
# False so costs keyed on "full telemetry" (scalar_due syncs) stay off.
_fr_ring = None       # deque(maxlen=_fr_cap) while armed, else None
_fr_cap = 0
_fr_only = False


def enabled():
    """True while the registry is recording a FULL session (``start()`` /
    ``MXNET_TELEMETRY``).  Deliberately False in flight-recorder-only mode:
    call sites that key a cost — not just emission — on telemetry (the
    sampled scalar syncs) must not react to a crash ring that promises
    zero overhead."""
    return _enabled and not _fr_only


def start(path=None):
    """Begin a recording session.  ``path`` (optional) is a JSON-lines
    sink; without it events stay in memory (``events()``), capped at
    ``_BUFFER_CAP``.  Any state left by a previous session (buffered
    events, counter totals) is cleared — one session per file."""
    global _enabled, _path, _atexit_armed, _dropped, _scalars_every, _fr_only
    with _lock:
        if path:
            open(path, "w").close()   # truncate: one run per file
        _buffer.clear()
        _recent.clear()
        _gc_done.clear()
        _counters.clear()
        _gauges.clear()
        _histograms.clear()
        _scalars.clear()
        if _fr_ring is not None:
            _fr_ring.clear()
        _dropped = 0
        _fr_only = False   # the recorder keeps riding along under a session
        _path = path
        try:
            _scalars_every = max(1, int(get_env("MXNET_SCALARS_EVERY", 1)))
        except (TypeError, ValueError):
            import warnings
            warnings.warn("MXNET_SCALARS_EVERY=%r is not an integer; "
                          "recording every step"
                          % get_env("MXNET_SCALARS_EVERY"))
            _scalars_every = 1
        if path and not _atexit_armed:
            atexit.register(stop)
            _atexit_armed = True
        _enabled = True


def stop():
    """Stop recording: emit a summary event (final counter/gauge values),
    flush any file sink, and disable.  Idempotent.  While the flight
    recorder is armed the registry drops back to fr-only mode instead of
    fully disabling — the crash ring keeps recording."""
    global _enabled, _path, _fr_only
    with _lock:
        if not _enabled or _fr_only:
            return
        _drain_gc_locked()
        summary = {"type": "summary", "ts": time.time() * 1e6,
                   "counters": dict(_counters), "gauges": dict(_gauges)}
        if _histograms:
            summary["histograms"] = {name: _hist_export(h)
                                     for name, h in _histograms.items()}
        if _scalars:
            summary["scalars"] = {k: {"n": s[0], "step": s[1],
                                      "value": s[2]}
                                  for k, s in _scalars.items()}
        if _dropped:
            # in-memory cap evicted the run's oldest events — say so
            summary["dropped_events"] = _dropped
        _buffer.append(summary)
        if _fr_ring is not None:
            _flush_locked()
            _path = None
            _fr_only = True
        else:
            _enabled = False
            _flush_locked()


def reset():
    """Clear all recorded state (test helper)."""
    global _dropped
    with _lock:
        _buffer.clear()
        _recent.clear()
        _gc_done.clear()
        _counters.clear()
        _gauges.clear()
        _histograms.clear()
        _scalars.clear()
        if _fr_ring is not None:
            _fr_ring.clear()
        _dropped = 0


def sink_path():
    """Path of the JSON-lines sink of the current session (None while
    disabled or recording in memory) — lets a run stamp WHERE its event/
    scalar stream went into artifacts it emits (bench.py writes it into
    BENCH_*.json so ``tools/run_compare.py`` can chain from the benchmark
    record to its training curves)."""
    with _lock:
        return _path if _enabled else None


def _emit_locked(ev):
    _drain_gc_locked()
    _append_locked(ev)


def _append_locked(ev):
    global _dropped
    if _fr_ring is not None:
        _fr_ring.append(ev)      # bounded: deque(maxlen) evicts the oldest
        if _fr_only:
            return               # fr-only: the ring is the ONLY sink
    _buffer.append(ev)
    _recent.append(ev)
    if _path is not None:
        if len(_buffer) >= _FLUSH_EVERY:
            _flush_locked()
    elif len(_buffer) > _BUFFER_CAP:
        _buffer.popleft()
        _dropped += 1


def _emit(ev):
    with _lock:
        if not _enabled:
            return
        _emit_locked(ev)


def _flush_locked():
    global _path
    if _path is None or not _buffer:
        return
    try:
        with open(_path, "a") as f:
            for ev in _buffer:
                f.write(json.dumps(ev) + "\n")
    except OSError as e:
        # an observability feature must not abort training: a sink that
        # turns unwritable mid-run (dir removed, disk full) degrades to
        # in-memory recording with a warning
        import warnings
        warnings.warn("telemetry sink %s became unwritable (%s); file "
                      "export disabled, events stay in memory" % (_path, e))
        _path = None
        return
    _buffer.clear()


def flush():
    """Drain buffered events to the file sink (no-op without a path)."""
    with _lock:
        _flush_locked()


# ---------------------------------------------------------- device counters
# Counters that live on the device: an op hands a traced value to
# ``device_counter`` while a step function traces, the step function returns
# what was collected beside its outputs, and the caller publishes the device
# arrays here, where the newest few runs' are kept.  Nothing is fetched until
# somebody asks (``device_counters``: one ``device_get``), so a counter costs
# the step its own arithmetic and the hot path no sync.
_dev_collecting = []   # stack of {name: [traced value, ...]} while tracing
# ({name: device array (calls, ...)}, steps summed) of the newest runs of a
# step program, the newest last
_dev_recent = deque(maxlen=64)


def device_counter(name, value):
    """Called by an op under trace: one more value of counter ``name`` (an
    array; every call of a name has the same shape), in call order, which
    is the graph's layer order.  Without a collecting step function the
    value is dropped."""
    if _dev_collecting:
        _dev_collecting[-1].setdefault(name, []).append(value)


class collect_device_counters(object):
    """``with collect_device_counters() as bag:`` around the trace of a
    graph; ``bag.stacked()`` is {name: array (calls, ...)} of what its ops
    handed in, still traced."""

    def __enter__(self):
        self._bag = {}
        _dev_collecting.append(self._bag)
        return self

    def __exit__(self, *exc):
        _dev_collecting.pop()

    def stacked(self):
        import jax.numpy as jnp
        return {k: jnp.stack(v) for k, v in self._bag.items()}


def publish_device_counters(values, steps):
    """The counters of one run of a step program, as device arrays summed
    over the ``steps`` steps it ran."""
    _dev_recent.append((values, int(steps)))


def device_counters(steps=None):
    """({name: numpy array (calls, ...)}, steps they are summed over),
    fetched now: of the newest run of a step program or, with ``steps``, of
    the newest runs that together cover so many steps (a window's chunks);
    (None, 0) where no step program with counters has run."""
    if not _dev_recent:
        return None, 0
    import jax
    take, covered = [], 0
    for values, n in reversed(_dev_recent):
        if take and {k: v.shape for k, v in values.items()} != {
                k: v.shape for k, v in take[0].items()}:
            break                                    # another program's
        take.append(values)
        covered += n
        if steps is None or covered >= steps:
            break
    take = jax.device_get(take)
    return {k: sum(t[k] for t in take) for k in take[0]}, covered


# ------------------------------------------------------------------ counters
def counter(name, value=1, **tags):
    """Accumulate ``value`` into counter ``name`` and emit one event.  The
    total update and the event emission share ONE lock acquisition, so
    concurrent threads can't write out-of-order ``total`` values."""
    if not _enabled:
        return
    ev = {"type": "counter", "name": name, "ts": time.time() * 1e6,
          "value": value}
    if tags:
        ev["tags"] = tags
    with _lock:
        if not _enabled:
            return
        total = _counters.get(name, 0) + value
        _counters[name] = total
        ev["total"] = total
        _emit_locked(ev)


def gauge(name, value, **tags):
    """Record the current value of gauge ``name`` and emit one event."""
    if not _enabled:
        return
    ev = {"type": "gauge", "name": name, "ts": time.time() * 1e6,
          "value": value}
    if tags:
        ev["tags"] = tags
    with _lock:
        if not _enabled:
            return
        _gauges[name] = value
        _emit_locked(ev)


# ---------------------------------------------------------------- histograms
# Fixed log-spaced buckets shared by every histogram: 20 buckets per decade
# (~5.9% relative resolution) with finite upper bounds 10**-1 .. 10**10,
# plus an implicit overflow bucket.  Fixed process-independent bounds are
# what make cross-rank merging associative — tools/telemetry_agg.py sums
# bucket counts by upper bound, no re-binning.  Values are unit-agnostic;
# the span-fed latency histograms record MICROSECONDS (matching span
# ``dur``).
_HIST_PER_DECADE = 20
_HIST_MIN_EXP = -1
_HIST_MAX_EXP = 10
_HIST_NFINITE = (_HIST_MAX_EXP - _HIST_MIN_EXP) * _HIST_PER_DECADE
_HIST_RATIO = 10.0 ** (1.0 / _HIST_PER_DECADE)


def hist_bound(index):
    """Upper bound of bucket ``index`` (0.._HIST_NFINITE; beyond is +inf).
    Bucket i holds values in (hist_bound(i-1), hist_bound(i)]; bucket 0
    additionally absorbs everything at or below its bound."""
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


def _hist_update_locked(name, value):
    if not math.isfinite(value):
        # an observability layer must never crash (or poison sums/quantiles
        # in) the run it observes; NaN/Inf *detection* is the diagnostics
        # sentinel's job (MXNET_CHECK_NUMERICS), not the histogram's
        return
    h = _histograms.get(name)
    if h is None:
        h = _histograms[name] = [0, 0.0, value, value, {}]
    h[0] += 1
    h[1] += value
    if value < h[2]:
        h[2] = value
    if value > h[3]:
        h[3] = value
    idx = _hist_index(value)
    h[4][idx] = h[4].get(idx, 0) + 1


def _hist_export(h):
    """Self-describing export: sparse ``{upper_bound: count}`` buckets (the
    overflow bucket keys as ``"inf"``) plus the bucket ratio, so consumers
    (summary event, metrics endpoint, tools/telemetry_agg.py) need no
    knowledge of the bucket scheme — merging sums counts by bound key and
    quantile estimation derives each bucket's lower edge as bound/ratio."""
    buckets = {}
    for idx, n in sorted(h[4].items()):
        b = hist_bound(idx)
        buckets["inf" if math.isinf(b) else "%.6g" % b] = n
    return {"count": h[0], "sum": h[1], "min": h[2], "max": h[3],
            "ratio": _HIST_RATIO, "buckets": buckets}


def histogram(name, value, **tags):
    """Record one observation into histogram ``name``.  Observations
    aggregate in-registry (no per-observation memory growth); one ``hist``
    event is emitted per explicit call so the JSON-lines stream keeps the
    raw value.  Span closes feed their histogram WITHOUT a ``hist`` event —
    the span event already carries the raw duration.  Non-finite values
    are dropped (NaN/Inf detection belongs to the diagnostics sentinel)."""
    if not _enabled:
        return
    value = float(value)
    if not math.isfinite(value):
        return
    ev = {"type": "hist", "name": name, "ts": time.time() * 1e6,
          "value": value}
    if tags:
        ev["tags"] = tags
    with _lock:
        if not _enabled:
            return
        _hist_update_locked(name, value)
        _emit_locked(ev)


def histograms():
    """Snapshot of all histograms in export form (see ``_hist_export``)."""
    with _lock:
        return {name: _hist_export(h) for name, h in _histograms.items()}


def quantile(name, q):
    """Estimated q-quantile (q in [0, 1]) of histogram ``name``, or None
    when it doesn't exist.  Log-linear interpolation inside the winning
    bucket, clamped to the observed [min, max]."""
    with _lock:
        h = _histograms.get(name)
        exp = _hist_export(h) if h is not None else None
    return quantile_from_hist(exp, q) if exp else None


def quantile_from_hist(h, q):
    """Quantile estimate from an exported histogram dict (pure function;
    tools/telemetry_agg.py carries a stdlib copy for offline use — the
    two are held together by a test)."""
    count = h.get("count", 0)
    if not count:
        return None
    q = min(max(float(q), 0.0), 1.0)
    lo_all = h.get("min")
    hi_all = h.get("max")
    ratio = h.get("ratio") or _HIST_RATIO
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
            # the first occupied bucket contains the observed min, so its
            # effective lower edge is exactly that (also covers the
            # underflow bucket, whose nominal lower edge is meaningless)
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


# ------------------------------------------------------------------ scalars
def series_key(name, tags=None):
    """Display/series key of a scalar: the bare name, or ``name[k=v,...]``
    when tags distinguish several series under one name (``grad_norm``
    per parameter group, ``monitor`` per tensor).  ``tools/run_compare.py``
    carries a stdlib copy so offline curve alignment builds the SAME keys."""
    if not tags:
        return name
    return "%s[%s]" % (name, ",".join("%s=%s" % (k, tags[k])
                                      for k in sorted(tags)))


def scalar_due(step):
    """True when per-step scalar producers should record ``step`` — the
    sampling gate behind ``MXNET_SCALARS_EVERY=N`` (default 1: every
    step).  Producers whose values cost a device sync (fit metric values,
    optimizer introspection) check this BEFORE computing, so the knob
    bounds syncs, not just file volume.  Producers with their own cadence
    (Speedometer ``frequent``, Monitor ``interval``, epoch-end rollups,
    lr decay boundaries) emit directly — decimating those would drop the
    few points that matter most.  Always False in flight-recorder-only
    mode: the crash ring must never buy a device sync."""
    return _enabled and not _fr_only and int(step) % _scalars_every == 0


def scalar(name, step, value, **tags):
    """Record one time-series point: ``value`` of series ``name`` at
    integer ``step``.  Append-only into the same per-rank JSON-lines
    stream as every other event (``type: "scalar"``); the registry keeps
    only the last value per series (no per-point memory growth), exported
    with the summary event.  Non-finite values are RECORDED — unlike
    histogram observations, a NaN in a loss curve is the finding, and
    consumers (``run_compare``, ``--curves``) handle it.  Strict no-op
    while disabled."""
    if not _enabled:
        return
    step = int(step)
    value = float(value)
    ev = {"type": "scalar", "name": name, "ts": time.time() * 1e6,
          "step": step, "value": value}
    if tags:
        ev["tags"] = tags
    key = series_key(name, tags)
    with _lock:
        if not _enabled:
            return
        s = _scalars.get(key)
        if s is None:
            _scalars[key] = [1, step, value]
        else:
            s[0] += 1
            s[1] = step
            s[2] = value
        _emit_locked(ev)


def scalars():
    """Snapshot of every scalar series' last recorded point:
    ``{series_key: {"n": points, "step": last_step, "value": last}}``."""
    with _lock:
        return {k: {"n": s[0], "step": s[1], "value": s[2]}
                for k, s in _scalars.items()}


def value(name, default=None):
    """Current accumulated value of a counter (or gauge), else ``default``."""
    with _lock:
        if name in _counters:
            return _counters[name]
        return _gauges.get(name, default)


def counters():
    """Snapshot of all counter totals."""
    with _lock:
        return dict(_counters)


def gauges():
    """Snapshot of all gauge values."""
    with _lock:
        return dict(_gauges)


def registry_snapshot():
    """All four registries under ONE lock acquisition:
    ``{"counters", "gauges", "histograms", "scalars"}``.  The separate
    ``counters()``/``gauges()``/... accessors each lock independently, so
    a scraper stitching them together can observe a torn step — counters
    from step N, gauges from step N+1.  metrics_server builds its
    ``/metrics.json`` document from this snapshot so one scrape is one
    consistent point in time."""
    with _lock:
        return {
            "counters": dict(_counters),
            "gauges": dict(_gauges),
            "histograms": {name: _hist_export(h)
                           for name, h in _histograms.items()},
            "scalars": {k: {"n": s[0], "step": s[1], "value": s[2]}
                        for k, s in _scalars.items()},
        }


def events():
    """Snapshot of buffered (not yet flushed) events."""
    with _lock:
        return list(_buffer)


def recent_events(n=None):
    """Tail of the event stream (last ``_RECENT_CAP``, surviving file
    flushes) — the "last N events" a diagnostics bundle embeds so a hang
    or crash shows what the run was doing right before it died."""
    with _lock:
        evs = list(_recent)
    if n is None:
        return evs
    n = int(n)
    return evs[-n:] if n > 0 else []


def nbytes_of(arr):
    """Payload size of an array-like (host-side arithmetic, no device
    sync); 0 when the size can't be derived.  Shared by the kvstore and
    dist byte counters so the accounting stays in one place."""
    try:
        import numpy as _np
        return int(arr.size) * _np.dtype(arr.dtype).itemsize
    except Exception:
        return 0


# --------------------------------------------------------------------- spans
def record_span(name, start_wall_s, dur_s, cat="runtime", **tags):
    """Record one already-timed span in the registry (seconds in,
    microseconds stored): the sink ``span()`` feeds while recording, and
    the one for regions whose two ends are not one ``with`` block (a
    request's queue wait, the whole-batch ``step``).

    Every close also feeds the latency histogram of the same name (µs), so
    spans get p50/p90/p99 visibility for free — ``quantile("step", 0.99)``,
    the metrics endpoint, and the cross-rank straggler report all read it.
    """
    if not _enabled:
        return
    with _lock:
        if _enabled:
            _drain_gc_locked()
            _span_locked(name, start_wall_s, dur_s, cat, tags)


def _span_locked(name, start_wall_s, dur_s, cat, tags):
    ev = {"type": "span", "name": name, "cat": cat,
          "ts": start_wall_s * 1e6, "dur": dur_s * 1e6}
    if tags:
        ev["tags"] = tags
    _hist_update_locked(name, ev["dur"])
    _append_locked(ev)


class _Span(object):
    """A span while the registry records: the profiler's annotation plus
    the timed JSON-lines event.  Extra tags may be attached mid-flight via
    ``self.tags[...] = ...`` (read at ``__exit__``, for the registry's
    event alone); ``cancel()`` suppresses the registry's event."""

    __slots__ = ("name", "cat", "tags", "_ann", "_t0", "_wall",
                 "_cancelled")

    def __init__(self, name, cat, tags, ann):
        self.name = name
        self.cat = cat
        self.tags = tags
        self._ann = ann
        self._cancelled = False

    def __enter__(self):
        self._ann.__enter__()
        self._wall = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if not self._cancelled:
            record_span(self.name, self._wall, dur, self.cat, **self.tags)

    def cancel(self):
        self._cancelled = True


_Annotation = None    # made at the first span: jax is not imported before


def _first_span():
    """Import jax, make the annotation class that ``span()`` hands out
    while the registry is off, and hook the collector."""
    global _Annotation
    import gc
    from jax.profiler import TraceAnnotation

    class Annotation(TraceAnnotation):
        """The profiler's annotation with ``_Span``'s surface, so a call
        site reads the same whether or not the registry records."""
        __slots__ = ()
        tags = {}   # class-level scratch dict: writes are cheap and ignored

        def cancel(self):
            pass

    with _lock:
        if _Annotation is None:
            _Annotation = Annotation
            gc.callbacks.append(_gc_hook)
    return _Annotation


def span(name, cat="runtime", **tags):
    """Timed-region context manager with two sinks: always a
    ``jax.profiler.TraceAnnotation`` named ``"mx:" + name`` carrying
    ``tags`` (an atomic check while no profiler session is live), and,
    only while the registry is enabled, the JSON-lines / ring event under
    the unprefixed name.  With the registry off no clock is read and no
    event is built."""
    ann = (_Annotation or _first_span())("mx:" + name, **tags)
    if not _enabled:
        return ann
    return _Span(name, cat, tags, ann)


# A collector pause is host work under no layer's boundary.  The hook
# writes it into the profiler's trace as ``mx:host.gc`` like any span;
# for the registry it only queues (when, seconds, generation) — the
# collector can run inside any allocation, the registry's locked sections
# included — and the next emission drains the queue.
def _gc_hook(phase, info):
    global _gc_open
    if phase == "start":
        ann = _Annotation("mx:host.gc", generation=info["generation"])
        ann.__enter__()
        _gc_open = (ann, time.time(), time.perf_counter()) if _enabled \
            else (ann, 0.0, 0.0)
    elif _gc_open is not None:
        ann, wall, t0 = _gc_open
        _gc_open = None
        ann.__exit__(None, None, None)
        if wall and _enabled:
            _gc_done.append((wall, time.perf_counter() - t0,
                             info["generation"]))


def _drain_gc_locked():
    while _gc_done:
        wall, dur, generation = _gc_done.popleft()
        _span_locked("host.gc", wall, dur, "runtime",
                     {"generation": generation})


# ------------------------------------------------------- flight recorder
def flight_recorder_armed():
    """True while the crash ring (``MXNET_FLIGHT_RECORDER=N``) is armed."""
    return _fr_ring is not None


def flight_recorder():
    """Snapshot of the flight-recorder ring for a diagnostics bundle, or
    None while disarmed: capacity, the ring contents (oldest first), and
    the last completed step derived from them — ``last_step`` is the tag
    dict of the newest closed ``step`` span (epoch/nbatch), and
    ``last_scalar_step`` the newest scalar event's global step, so a crash
    report names where each rank got to without replaying the ring."""
    with _lock:
        if _fr_ring is None:
            return None
        evs = list(_fr_ring)
    last_step = None
    last_scalar_step = None
    for ev in reversed(evs):
        t = ev.get("type")
        if last_step is None and t == "span" and ev.get("name") == "step":
            last_step = dict(ev.get("tags") or {})
        if last_scalar_step is None and t == "scalar":
            last_scalar_step = ev.get("step")
        if last_step is not None and last_scalar_step is not None:
            break
    return {"capacity": _fr_cap, "recorded": len(evs),
            "last_step": last_step, "last_scalar_step": last_scalar_step,
            "events": evs}


def _fr_arm(capacity):
    """Arm the flight recorder with a ring of ``capacity`` events.  Flips
    the registry into fr-only mode unless a full session is already
    recording (then the ring simply rides along)."""
    global _enabled, _fr_ring, _fr_cap, _fr_only
    capacity = int(capacity)
    if capacity <= 0:
        raise ValueError("flight recorder capacity must be > 0 "
                         "(got %d)" % capacity)
    with _lock:
        _fr_cap = capacity
        _fr_ring = deque(_fr_ring or (), maxlen=capacity)
        if not _enabled:
            _fr_only = True
            _enabled = True


def _fr_disarm():
    """Disarm the recorder and drop the ring (test helper)."""
    global _enabled, _fr_ring, _fr_cap, _fr_only
    with _lock:
        _fr_ring = None
        _fr_cap = 0
        if _fr_only:
            _fr_only = False
            _enabled = False


def _fr_autostart():
    """MXNET_FLIGHT_RECORDER=N arms the crash ring at import time.  No
    threads, no file, no atexit — the ring only surfaces through the
    diagnostics bundle.  A malformed or non-positive value degrades to
    disarmed-with-a-warning rather than failing the import."""
    raw = get_env("MXNET_FLIGHT_RECORDER")
    if raw is None or raw == "" or str(raw) == "0":
        return False
    try:
        cap = int(raw)
        if cap <= 0:
            raise ValueError(raw)
        _fr_arm(cap)
    except (TypeError, ValueError):
        import warnings
        warnings.warn("MXNET_FLIGHT_RECORDER=%r is not a positive integer; "
                      "flight recorder disarmed" % (raw,))
        return False
    return True


# ------------------------------------------------- autostart (env contract)
def _autostart():
    """MXNET_TELEMETRY=<path.jsonl> starts recording at import time.  In a
    multi-process run (the MXTPU_* launch contract, tools/launch.py) every
    worker would otherwise truncate and interleave the same file, so the
    worker rank is appended — one file per process.  An unwritable path
    degrades to disabled-with-a-warning rather than failing the import."""
    path = get_env("MXNET_TELEMETRY")
    if not path:
        return False
    rank = get_env("MXTPU_PROCESS_ID")
    if rank is not None:
        path = "%s.rank%s" % (path, rank)
    try:
        start(path)
    except OSError as e:
        import warnings
        warnings.warn("MXNET_TELEMETRY=%s is unwritable (%s); telemetry "
                      "disabled" % (path, e))
        return False
    return True


_autostart()
_fr_autostart()
