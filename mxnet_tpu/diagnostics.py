"""Training health & diagnostics — the layer that answers "why did this
run misbehave?" on top of the telemetry substrate (telemetry.py answers
"where does a healthy step spend time").

Four affordances, each strictly opt-in via an environment variable and a
strict no-op otherwise (the same zero-overhead contract as telemetry):

* **hang watchdog** (``MXNET_WATCHDOG_SEC=<seconds>``) — a daemon thread
  watching a step heartbeat fed by ``Module.fit`` (per batch), the fused
  ``TrainStep`` (per update) and ``parallel.dist`` (per collective).  When
  no heartbeat arrives within the threshold — a hung allreduce, a stuck
  input pipeline, a deadlocked callback — it dumps every Python thread's
  stack plus the telemetry counter/gauge snapshot and the tail of the
  event stream to a per-rank diagnostics bundle, then re-arms on the next
  heartbeat.  Arming also wires :mod:`faulthandler` to a per-rank file so
  hard crashes (segfault, fatal signal) leave C-level stacks behind.

* **non-finite sentinel** (``MXNET_CHECK_NUMERICS={warn,raise}``) — per
  step, loss/outputs and the gradient global norm are checked for
  NaN/Inf; hits increment the ``nonfinite_loss`` / ``nonfinite_grad``
  telemetry counters and either warn or fail fast (``raise`` mode names
  the offending batch, so the poisoned step is the *first* thing in the
  traceback, not epoch-ten fallout).

* **compile & memory visibility** — ``sample_device_memory`` turns JAX
  live-array statistics (and, where the backend provides them, device
  ``memory_stats``) into per-epoch telemetry gauges; the ``xla_compile``
  span (a program's trace + lowering + compile) comes from the set-up
  feed in ``sanitize``.

* **crash snapshot** — any exception escaping ``Module.fit`` writes the
  same bundle (stacks, counters, recent events, the exception itself)
  before re-raising, whenever any diagnostics feature — or
  ``MXNET_DIAG_DIR`` alone — is set.

Bundles are JSON documents under ``MXNET_DIAG_DIR`` (default: current
directory), one file per (reason, pid, rank); render them with
``tools/diagnose.py``.
"""
from __future__ import annotations

import faulthandler
import json
import math
import os
import sys
import threading
import time
import traceback
import warnings

from .base import MXNetError, get_env
from . import telemetry as _tel

__all__ = ["NonFiniteError", "arm", "disarm", "armed", "heartbeat",
           "check_numerics_mode", "check_outputs", "check_grad_norm",
           "check_fit_step", "report_nonfinite", "sample_device_memory",
           "snapshot", "write_snapshot", "crash_snapshot",
           "crash_snapshots_active", "diag_dir", "diag_path",
           "thread_stacks"]

RECENT_EVENTS = 200   # telemetry tail length embedded in a bundle


class NonFiniteError(MXNetError):
    """MXNET_CHECK_NUMERICS=raise found a NaN/Inf loss, output, or
    gradient; the message names the offending step."""


# ----------------------------------------------------------------- watchdog
_lock = threading.RLock()
_armed = False          # hot-path guard: heartbeat() is a no-op while False
_watchdog_sec = None
_poll_sec = None
_thread = None
_fault_file = None
_last_beat = None       # time.monotonic() of the latest heartbeat
_beat_count = 0
_beat_info = {}         # last heartbeat's tags (epoch/nbatch/comm/...)
_stall_handled = False  # one bundle per stall; next heartbeat re-arms


def armed():
    """True while the hang watchdog is running."""
    return _armed


def heartbeat(**info):
    """Mark training progress (fed by fit batches, fused train steps, and
    dist collectives).  Near-zero cost unarmed; call sites in hot loops
    additionally guard with ``if diagnostics._armed:`` so they do not even
    build the kwargs dict."""
    global _last_beat, _beat_count, _stall_handled, _beat_info
    if not _armed:
        return
    _last_beat = time.monotonic()
    _beat_count += 1
    _stall_handled = False
    if info:
        # REPLACE, never merge or mutate: merging would let stale keys
        # (a long-finished dist.allreduce) misreport what was in flight,
        # and the watchdog thread copies this dict lock-free, so it must
        # be immutable once published
        _beat_info = dict(info)


def arm(seconds=None, poll=None):
    """Start the hang watchdog.  ``seconds`` defaults to
    ``MXNET_WATCHDOG_SEC``; returns False (and stays off) when neither is
    set.  Set the threshold ABOVE the first step's XLA compile time — the
    watchdog cannot tell a long compile from a hang.  Also wires
    ``faulthandler`` so hard crashes dump to a per-rank file."""
    global _armed, _watchdog_sec, _poll_sec, _thread, _last_beat
    with _lock:
        if seconds is None:
            seconds = get_env("MXNET_WATCHDOG_SEC", typ=float)
        if not seconds or seconds <= 0:
            return False
        _watchdog_sec = float(seconds)
        _poll_sec = float(poll) if poll else min(1.0, _watchdog_sec / 4.0)
        _last_beat = time.monotonic()   # arming counts as progress
        _wire_faulthandler()
        _armed = True
        if _thread is None or not _thread.is_alive():
            _thread = threading.Thread(target=_watch_loop,
                                       name="mxtpu-watchdog", daemon=True)
            _thread.start()
        return True


def disarm():
    """Stop the watchdog thread and unwind the faulthandler wiring
    (test helper; production watchdogs live for the process)."""
    global _armed, _thread, _beat_count, _last_beat, _stall_handled, \
        _beat_info
    with _lock:
        t, _thread = _thread, None
        _armed = False
    if t is not None and t.is_alive():
        t.join(timeout=5.0)
    with _lock:
        _unwire_faulthandler()
        _beat_count = 0
        _last_beat = None
        _beat_info = {}
        _stall_handled = False


def _watch_loop():
    global _stall_handled
    while _armed:
        time.sleep(_poll_sec)
        if not _armed:
            break
        try:
            last = _last_beat
            if last is None or _stall_handled:
                continue
            age = time.monotonic() - last
            if age < _watchdog_sec:
                continue
            # GIL-atomic bool flip; heartbeat()'s lock-free reset is the
            # hot-path contract (it must never contend with a dump in
            # progress) and at worst costs one extra bundle
            # mxlint: disable=THR001 GIL-atomic publication, see above
            _stall_handled = True
            path = write_snapshot("watchdog_stall",
                                  extra={"stall_sec": age,
                                         "watchdog_sec": _watchdog_sec})
            sys.stderr.write(
                "mxnet_tpu watchdog: no training heartbeat for %.1fs "
                "(threshold %.1fs)%s\n"
                % (age, _watchdog_sec,
                   "; diagnostics written to %s" % path if path else ""))
            sys.stderr.flush()
            if _tel._enabled:
                _tel.counter("watchdog_stalls")
        except Exception as e:   # noqa: BLE001 — a dump error must not
            # kill hang detection for the rest of the run
            try:
                sys.stderr.write("mxnet_tpu watchdog: dump failed (%s)\n"
                                 % e)
            except Exception:
                pass


_fault_prev_enabled = False


def _wire_faulthandler():
    global _fault_file, _fault_prev_enabled
    if _fault_file is not None:
        return
    try:
        _fault_prev_enabled = faulthandler.is_enabled()
        _fault_file = open(diag_path("fault", ext="txt"), "w")
        faulthandler.enable(file=_fault_file)
    except OSError as e:
        warnings.warn("diagnostics: cannot wire faulthandler (%s)" % e)


def _unwire_faulthandler():
    global _fault_file
    if _fault_file is None:
        return
    # restore the pre-arm state BEFORE closing our file, so a crash in
    # the gap never writes to a dead fd; a process that kept faulthandler
    # off gets it back off (arm/disarm is state-restoring)
    faulthandler.disable()
    if _fault_prev_enabled:
        try:
            faulthandler.enable(file=sys.stderr)
        except (OSError, ValueError):
            pass
    try:
        _fault_file.close()
    except OSError:
        pass
    _fault_file = None


# ------------------------------------------------------------------ bundles
def diag_dir():
    return get_env("MXNET_DIAG_DIR") or "."


def diag_path(reason, ext="json"):
    """Per-(reason, pid, rank) bundle path under MXNET_DIAG_DIR — workers
    of a multi-process launch (MXTPU_* contract) never clobber each other."""
    rank = get_env("MXTPU_PROCESS_ID")
    name = "mxtpu_diag.%s.pid%d%s.%s" % (
        reason, os.getpid(),
        ".rank%s" % rank if rank is not None else "", ext)
    return os.path.join(diag_dir(), name)


def thread_stacks():
    """Every live Python thread's current stack, formatted — what the
    reference lineage could only get from gdb on a hung worker."""
    frames = sys._current_frames()
    by_ident = {t.ident: t for t in threading.enumerate()}
    out = []
    for ident, frame in frames.items():
        t = by_ident.get(ident)
        out.append({
            "ident": ident,
            "name": t.name if t is not None else "<unknown>",
            "daemon": bool(t.daemon) if t is not None else None,
            "stack": [ln.rstrip("\n")
                      for ln in traceback.format_stack(frame)],
        })
    out.sort(key=lambda rec: (rec["name"] != "MainThread", rec["name"]))
    return out


def snapshot(reason, exc=None, extra=None):
    """Assemble a diagnostics bundle dict: identity, heartbeat state, all
    thread stacks, the telemetry counter/gauge snapshot and recent-event
    tail, and (for crashes) the exception."""
    bundle = {
        "type": "mxtpu_diagnostics",
        "version": 1,
        "reason": reason,
        "time": time.time(),
        "pid": os.getpid(),
        "rank": get_env("MXTPU_PROCESS_ID"),
        "argv": list(sys.argv),
        "heartbeat": {
            "count": _beat_count,
            "age_sec": (time.monotonic() - _last_beat
                        if _last_beat is not None else None),
            "last": dict(_beat_info),
        },
        "threads": thread_stacks(),
        "telemetry": {
            "enabled": _tel.enabled(),
            "counters": _tel.counters(),
            "gauges": _tel.gauges(),
            "histograms": _tel.histograms(),
            # last training-curve points: a crash/stall bundle then shows
            # where the loss/lr/grad norms stood when the run died
            "scalars": _tel.scalars(),
            "recent_events": _tel.recent_events(RECENT_EVENTS),
        },
    }
    try:
        from . import sanitize as _san
        if _san._collective_on:
            # the collective checker's per-rank ledger tail: a stall or
            # crash bundle then says which collective this rank stopped
            # at (seq, kind, signature) — the post-mortem for a hung
            # fleet (docs/static_analysis.md "collective checker")
            bundle["collective"] = _san.collective_state()
            bundle["collective_ledger"] = _san.ledger_tail()
    except Exception:   # diagnostics must never add a second failure
        pass
    try:
        # flight recorder (MXNET_FLIGHT_RECORDER=N): the ring of the last
        # N events — the "last seconds before the incident" timeline that
        # exists even when full telemetry was never armed
        fr = _tel.flight_recorder()
        if fr is not None:
            bundle["flight_recorder"] = fr
    except Exception:   # diagnostics must never add a second failure
        pass
    try:
        from .parallel import resize as _resize
        rz = _resize.stats()
        if rz["history"]:
            # live-resize trajectory (elasticity v3): which membership
            # transitions this process survived, when, and at what cost —
            # a post-mortem of an elastic fleet needs the world-size
            # history next to the collective ledger it rebased
            bundle["resize"] = rz
    except Exception:   # diagnostics must never add a second failure
        pass
    try:
        from . import sentinel as _sen
        if _sen._on:
            # live-sentinel state: the last step's phase anatomy, the
            # rolling baselines it was judged against, the latest fired
            # anomaly and the cross-rank straggler verdict — a
            # perf_anomaly or oom bundle is then self-contained
            from .parallel import dist as _dist
            bundle["sentinel"] = {
                "anatomy": _sen.anatomy(),
                "last_step": _sen.last_anatomy(),
                "last_anomaly": _sen.last_anomaly(),
                "straggler": _dist.straggler(),
            }
    except Exception:   # diagnostics must never add a second failure
        pass
    try:
        from . import sanitize as _san
        hbm = _san.hbm_ledger()
        if hbm:
            # per-program HBM attribution (sentinel / hbm_report): which
            # compiled program holds how many bytes — the first question
            # an oom bundle must answer
            bundle["hbm"] = hbm
    except Exception:   # diagnostics must never add a second failure
        pass
    try:
        from . import sanitize as _san
        from . import cost as _cost
        ledger = _san.cost_ledger()
        compile_s = _san.compile_seconds()
        if ledger or compile_s:
            # per-program cost attribution (cost_report): each compiled
            # program's FLOPs / bytes / arithmetic intensity, the
            # resolved roofline peaks (so the bundle's verdicts are
            # reproducible offline), and per-cache cumulative compile
            # seconds — the denominator behind every MFU gauge
            peak_flops, peak_bw = _cost.resolve_peaks()
            bundle["cost"] = {
                "programs": ledger,
                "peaks": {"flops_per_sec": peak_flops,
                          "bytes_per_sec": peak_bw},
                "compile_seconds": compile_s,
            }
    except Exception:   # diagnostics must never add a second failure
        pass
    try:
        from . import numerics as _num
        numerics = _num.bundle_section()
        if numerics:
            # the MXNET_MONITOR history ring: recent sampled-step grad
            # norms / update ratios / finite flags — the training-
            # dynamics trail leading up to whatever this bundle records
            bundle["numerics"] = numerics
    except Exception:   # diagnostics must never add a second failure
        pass
    if exc is not None:
        bundle["exception"] = {
            "type": type(exc).__name__,
            "message": str(exc),
            "traceback": [ln.rstrip("\n") for ln in
                          traceback.format_exception(type(exc), exc,
                                                     exc.__traceback__)],
        }
    if extra:
        bundle["extra"] = dict(extra)
    return bundle


def write_snapshot(reason, exc=None, extra=None):
    """Write a bundle to its per-rank path; returns the path, or None when
    the sink is unwritable (diagnostics must never add a second failure).
    A repeat incident in the same process gets a sequence-numbered name —
    the first stall's evidence must survive the second."""
    path = diag_path(reason)
    n = 1
    while os.path.exists(path) and n < 1000:
        path = diag_path("%s.%d" % (reason, n))
        n += 1
    bundle = snapshot(reason, exc=exc, extra=extra)
    try:
        # MXNET_DIAG_DIR is usually pointed at a fresh path mid-incident;
        # a missing directory must not cost the evidence
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(bundle, f, indent=1, default=str)
            f.write("\n")
    except (OSError, TypeError, ValueError) as e:
        warnings.warn("diagnostics: cannot write %s (%s); bundle dropped"
                      % (path, e))
        return None
    return path


def crash_snapshots_active():
    """Crash bundles write when ANY diagnostics feature is opted into —
    the watchdog, the sentinel, the flight recorder, or MXNET_DIAG_DIR
    alone."""
    if _armed or get_env("MXNET_DIAG_DIR") is not None \
            or _tel.flight_recorder_armed():
        return True
    try:
        return check_numerics_mode() is not None
    except MXNetError:
        return True   # malformed value is still an opt-in


def crash_snapshot(exc, **context):
    """Forensic bundle for an exception escaping the fit loop (called by
    Module.fit before re-raising).  No-op unless diagnostics is active;
    must never raise a second failure over the one being reported."""
    try:
        if not crash_snapshots_active():
            return None
        if _tel._enabled:
            _tel.counter("fit_crashes", kind=type(exc).__name__)
        return write_snapshot("crash", exc=exc, extra=context or None)
    except Exception as e:   # noqa: BLE001 — diagnostics must not mask exc
        warnings.warn("diagnostics: crash snapshot failed (%s)" % e)
        return None


# --------------------------------------------------------- non-finite sentinel
def check_numerics_mode():
    """'warn' | 'raise' from MXNET_CHECK_NUMERICS, else None (read once
    per fit / per step — never per tensor)."""
    mode = get_env("MXNET_CHECK_NUMERICS")
    if not mode:
        return None
    mode = mode.lower()
    if mode in ("0", "off", "false", "none"):
        return None
    if mode not in ("warn", "raise"):
        raise MXNetError("MXNET_CHECK_NUMERICS must be 'warn' or 'raise', "
                         "got %r" % mode)
    return mode


def _ctx_str(ctx):
    return " ".join("%s=%s" % (k, v) for k, v in sorted(ctx.items())) \
        or "<no context>"


def report_nonfinite(mode, msg):
    """Fail fast or warn, per sentinel mode (shared by fit, TrainStep and
    Monitor so the escalation policy lives in one place)."""
    if mode == "raise":
        raise NonFiniteError(msg)
    warnings.warn(msg)


def _nonfinite_count(arr):
    """Count NaN/Inf elements.  Device-resident inputs (NDArray / jax
    array) reduce ON DEVICE and sync one scalar — no full-tensor host
    transfer; host data falls back to numpy."""
    v = getattr(arr, "value", arr)   # NDArray -> its jax array
    if hasattr(v, "devices"):
        import jax.numpy as jnp
        if not jnp.issubdtype(v.dtype, jnp.floating):
            return 0   # integer labels/ids cannot be non-finite
        return int(v.size) - int(jnp.isfinite(v).sum())
    import numpy as np
    a = arr.asnumpy() if hasattr(arr, "asnumpy") else np.asarray(arr)
    if not np.issubdtype(a.dtype, np.floating):
        return 0
    return int(a.size - int(np.isfinite(a).sum()))


def check_outputs(outputs, mode, where="loss", **ctx):
    """NaN/Inf check over forward outputs.  Counts bad elements into the
    ``nonfinite_loss`` telemetry counter and warns/raises per ``mode``.
    Returns True when everything is finite.  Costs one device sync per
    output — the sentinel is opt-in precisely because of this."""
    from . import sanitize as _san
    bad = {}
    with _san.allow_sync("check_numerics sentinel"):
        for i, o in enumerate(outputs):
            n = _nonfinite_count(o)
            if n:
                bad[i] = n
    if not bad:
        return True
    total = sum(bad.values())
    if _tel._enabled:
        _tel.counter("nonfinite_loss", total, where=where, **ctx)
    report_nonfinite(mode,
                     "non-finite values in %s output(s) %s (%d bad "
                     "element(s)) at %s"
                     % (where, sorted(bad), total, _ctx_str(ctx)))
    return False


def check_grad_norm(grads, mode, **ctx):
    """Gradient global-norm check: a finite norm is recorded as the
    ``grad_global_norm`` gauge (free trend line for blow-up forensics); a
    NaN/Inf norm increments ``nonfinite_grad`` and warns/raises.

    ``grads`` elements may be per-device lists (executor_group layout).
    The squared sums reduce ON DEVICE (float32) and only scalars cross to
    the host — no full-tensor transfer per batch.  On multi-context
    bindings the gauge is the root-sum-square over the per-device shard
    gradients (cross-device summation would cost the transfers this path
    avoids); it is exact on a single context and exact for NaN/Inf
    detection always."""
    import jax.numpy as jnp
    by_dev = {}   # device -> list of scalar squared-sums (colocated)
    total = 0.0
    seen = False
    for g in grads:
        for dev_g in (g if isinstance(g, (list, tuple)) else (g,)):
            if dev_g is None:
                continue
            seen = True
            v = getattr(dev_g, "value", None)
            if v is None:
                import numpy as np
                a = np.asarray(dev_g)
                total += float(np.square(a.astype(np.float64,
                                                  copy=False)).sum())
                continue
            sq = jnp.sum(jnp.square(v.astype(jnp.float32)))
            dev = next(iter(sq.devices())) if hasattr(sq, "devices") \
                else None
            by_dev.setdefault(dev, []).append(sq)
    if not seen:
        return True
    for sqs in by_dev.values():
        s = sqs[0] if len(sqs) == 1 else jnp.sum(jnp.stack(sqs))
        total += float(s)   # the batch's one (scalar) device sync
    norm = math.sqrt(total) if math.isfinite(total) and total >= 0 \
        else float("nan")
    if math.isfinite(norm):
        if _tel._enabled:
            _tel.gauge("grad_global_norm", norm, **ctx)
        return True
    if _tel._enabled:
        _tel.counter("nonfinite_grad", **ctx)
    report_nonfinite(mode, "non-finite gradient global norm at %s"
                     % _ctx_str(ctx))
    return False


def check_fit_step(module, epoch, nbatch, mode, outputs=None,
                   check_grads=True):
    """Per-batch health check for Module.fit: loss/outputs first (the
    failure users see), then the gradient global norm (the failure that
    *causes* it one step earlier).  On the general path fit calls this
    BETWEEN backward and update, so ``raise`` halts with the weights
    still clean.  ``outputs=None`` reads them from the module;
    ``check_grads=False`` skips gradients (the fused path keeps them
    inside the donated XLA program)."""
    if outputs is None:
        outputs = module.get_outputs()
    ok = check_outputs(outputs, mode, where="loss",
                       epoch=epoch, nbatch=nbatch)
    if check_grads:
        eg = getattr(module, "_exec_group", None)
        grads = getattr(eg, "grad_arrays", None) if eg is not None else None
        if grads:
            ok = check_grad_norm(grads, mode,
                                 epoch=epoch, nbatch=nbatch) and ok
    return ok


# --------------------------------------------------------- memory visibility
def sample_device_memory(**tags):
    """Device-memory gauges from JAX live-array stats (and backend
    ``memory_stats`` where available): ``device_live_bytes`` /
    ``device_live_arrays`` totals plus a per-device breakdown.  Sampled
    per epoch by Module.fit while telemetry records; a no-op otherwise (no
    device sync either way — live_arrays is host-side bookkeeping)."""
    if not _tel._enabled:
        return {}
    import jax
    per_dev = {}
    count = 0
    for a in jax.live_arrays():
        try:
            # per-shard accounting: a replicated array physically holds
            # its FULL nbytes on every device (dividing evenly would
            # undercount exactly the dominant replicated-param footprint)
            shards = [(str(sh.device), int(sh.data.nbytes))
                      for sh in a.addressable_shards]
        except Exception:
            continue   # deleted/donated buffers race the walk
        count += 1
        for d, nb in shards:
            per_dev[d] = per_dev.get(d, 0) + nb
    _tel.gauge("device_live_bytes", sum(per_dev.values()), **tags)
    _tel.gauge("device_live_arrays", count, **tags)
    for d, nb in sorted(per_dev.items()):
        _tel.gauge("device_live_bytes[%s]" % d, nb, **tags)
    for d in jax.local_devices():
        # local_devices, not devices: under a multi-process world the
        # remote devices are non-addressable and memory_stats() raises
        # (INVALID_ARGUMENT) — each rank reports its own devices, the
        # fleet merge composes them
        stats = getattr(d, "memory_stats", None)
        try:
            stats = stats() if callable(stats) else None
        except Exception:
            stats = None   # backend without memory introspection
        if stats and "bytes_in_use" in stats:
            _tel.gauge("device_bytes_in_use[%s]" % d,
                       int(stats["bytes_in_use"]), **tags)
    return per_dev


# ------------------------------------- flight-recorder flush triggers
# The crash snapshot covers exceptions escaping Module.fit, and the mxsan
# watchdog covers collective stalls — but a flight-recorder-armed process
# must also leave its ring behind for (a) exceptions that never pass
# through fit (data pipeline setup, serving loops) and (b) a SIGTERM from
# a launcher/scheduler killing one rank of a fleet.  Both hooks install
# ONLY when the ring is armed at import (zero-overhead contract), chain or
# restore prior behaviour, and never add a second failure.
_fr_prev_excepthook = None
_fr_prev_sigterm = None
_fr_sigterm_wired = False


def _fr_excepthook(exc_type, exc, tb):
    try:
        if not issubclass(exc_type, (KeyboardInterrupt, SystemExit)):
            write_snapshot("unhandled_exception", exc=exc)
    except Exception:   # noqa: BLE001 — must not mask the real crash
        pass
    (_fr_prev_excepthook or sys.__excepthook__)(exc_type, exc, tb)


def _fr_on_sigterm(signum, frame):
    import signal
    try:
        write_snapshot("fatal_signal",
                       extra={"signal": int(signum), "signal_name": "SIGTERM"})
    except Exception:   # noqa: BLE001
        pass
    prev = _fr_prev_sigterm
    if callable(prev):
        # a chained application handler (jax's preemption notifier after
        # distributed init) OWNS the death semantics — graceful
        # preemption relies on the process surviving to the next step
        # boundary, so the hook only buys the bundle write and defers
        try:
            prev(signum, frame)
        except Exception:   # noqa: BLE001 — never add a second failure
            pass
        return
    # no prior handler: restore the default disposition and re-deliver,
    # so the process still dies by SIGTERM (exit status, parent waitpid
    # semantics) — the handler only buys the bundle write
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    os.kill(os.getpid(), signal.SIGTERM)


def _fr_wire():
    """Install the flight-recorder flush triggers (import time, armed
    processes only).  The SIGTERM hook only takes a handler slot that was
    at the default disposition — an application handler wins."""
    global _fr_prev_excepthook, _fr_prev_sigterm, _fr_sigterm_wired
    if not _tel.flight_recorder_armed():
        return False
    if _fr_prev_excepthook is None:
        _fr_prev_excepthook = sys.excepthook
        sys.excepthook = _fr_excepthook
    try:
        import signal
        if threading.current_thread() is threading.main_thread() \
                and signal.getsignal(signal.SIGTERM) == signal.SIG_DFL:
            _fr_prev_sigterm = None
            signal.signal(signal.SIGTERM, _fr_on_sigterm)
            _fr_sigterm_wired = True
    except (ValueError, OSError, RuntimeError):
        pass   # non-main thread / exotic platform: excepthook still covers
    return True


def fr_rewire_sigterm():
    """Re-assert the flight-recorder SIGTERM hook after jax's
    distributed init: the runtime installs its preemption notifier on
    SIGTERM at the C level — invisible to ``signal.getsignal`` — which
    displaces the import-time hook in exactly the fleet case the
    recorder exists for (a launcher/scheduler killing one rank).
    ``dist.init_process_group`` calls this once the runtime is up.  A
    Python-level application handler found in the slot is chained after
    the bundle write and keeps its own death semantics; the C-level
    notifier cannot be observed or chained and is displaced — an armed
    ring means the operator asked for post-mortem bundles on kill.
    No-op unless armed."""
    global _fr_prev_sigterm, _fr_sigterm_wired
    if not _tel.flight_recorder_armed():
        return False
    try:
        import signal
        if threading.current_thread() is not threading.main_thread():
            return False
        cur = signal.getsignal(signal.SIGTERM)
        if callable(cur) and cur is not _fr_on_sigterm:
            _fr_prev_sigterm = cur
        # unconditional re-install: when a C-level handler holds the OS
        # slot, getsignal still names whatever Python set last — trusting
        # it would no-op exactly when the rewire is needed
        signal.signal(signal.SIGTERM, _fr_on_sigterm)
        _fr_sigterm_wired = True
        return True
    except (ValueError, OSError, RuntimeError):
        return False   # exotic platform: the excepthook still covers


# ------------------------------------------------- autostart (env contract)
def _autoarm():
    """MXNET_WATCHDOG_SEC arms the watchdog at import time (the env-var
    analogue of MXNET_TELEMETRY autostart).  A malformed value degrades to
    disabled-with-a-warning rather than failing the import."""
    try:
        return arm()
    except (ValueError, MXNetError) as e:
        warnings.warn("MXNET_WATCHDOG_SEC invalid (%s); watchdog disabled"
                      % e)
        return False


_autoarm()
_fr_wire()
