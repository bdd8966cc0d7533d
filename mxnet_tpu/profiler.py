"""Profiler (parity: reference python/mxnet/profiler.py + src/engine/profiler.*;
SURVEY.md §5.1).

TPU-first: op-level timing comes from the JAX/XLA profiler rather than engine
worker instrumentation.  ``dump_profile`` writes a chrome://tracing JSON like the
reference's DumpProfile; ``set_state('run')`` also starts the JAX trace collector
so XLA-level timelines land in ``<filename>.xplane/`` for TensorBoard; the
program's own spans (``telemetry.span``) are ``mx:<name>`` host events there.
"""
from __future__ import annotations

import json
import threading
import time

from .base import MXNetError, get_env

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "set_config", "set_state", "Scope", "is_running", "record_event"]

_state = {"mode": "symbolic", "filename": "profile.json", "running": False,
          "events": [], "jax_trace_dir": None}
_lock = threading.Lock()


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """(parity: MXSetProfilerConfig)"""
    if mode not in ("symbolic", "imperative", "api", "mem", "all"):
        raise MXNetError("invalid profiler mode %s" % mode)
    _state["mode"] = mode
    _state["filename"] = filename


set_config = profiler_set_config


def profiler_set_state(state="stop"):
    """(parity: MXSetProfilerState) — 'run' | 'stop'."""
    if state == "run":
        _state["running"] = True
        _state["t0"] = time.time()
        try:
            import jax
            _state["jax_trace_dir"] = _state["filename"] + ".xplane"
            jax.profiler.start_trace(_state["jax_trace_dir"])
        except Exception:
            _state["jax_trace_dir"] = None
    elif state == "stop":
        _state["running"] = False
        if _state.get("jax_trace_dir"):
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
    else:
        raise MXNetError("invalid profiler state %s" % state)


set_state = profiler_set_state


def is_running():
    return _state["running"]


def record_event(name, start_us, dur_us, cat="operator"):
    """Append one chrome-trace complete event (engine-level op timing)."""
    if not _state["running"]:
        return
    with _lock:
        _state["events"].append({"name": name, "cat": cat, "ph": "X",
                                 "ts": start_us, "dur": dur_us, "pid": 0,
                                 "tid": 0})


class Scope(object):
    """Context manager timing a region into the profile."""

    def __init__(self, name, cat="operator"):
        self.name = name
        self.cat = cat

    def __enter__(self):
        self._t0 = time.time()
        return self

    def __exit__(self, *exc):
        t1 = time.time()
        record_event(self.name, self._t0 * 1e6, (t1 - self._t0) * 1e6,
                     self.cat)


def dump_profile():
    """Write chrome://tracing JSON (parity: MXDumpProfile / DumpProfile).

    Emits ``process_name``/``thread_name`` metadata events (ph='M') so the
    trace viewer labels rows, and DRAINS the recorded events: back-to-back
    dumps each contain only the events recorded since the previous dump.
    Each dump overwrites ``filename`` with its delta — a caller snapshotting
    mid-run AND at exit should ``set_config`` a fresh filename between
    dumps, or the mid-run snapshot is replaced by the final delta.
    """
    with _lock:
        # build and write under the one lock (record_event also locks, so
        # the event list can't move underneath), and drain only AFTER a
        # successful write — a failing open/write keeps the events for a
        # retry with a corrected filename
        events = _state["events"]
        meta = [{"name": "process_name", "ph": "M", "pid": 0,
                 "args": {"name": "mxnet_tpu"}},
                {"name": "thread_name", "ph": "M", "pid": 0, "tid": 0,
                 "args": {"name": "python-main"}}]
        trace = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        with open(_state["filename"], "w") as f:
            json.dump(trace, f)
        _state["events"] = []


# autostart parity: MXNET_PROFILER_AUTOSTART
if get_env("MXNET_PROFILER_AUTOSTART", "0") == "1":
    profiler_set_config(get_env("MXNET_PROFILER_MODE", "symbolic"),
                        "profile_output.json")
    profiler_set_state("run")
