"""Unified runtime telemetry tests: registry round-trip, env autostart,
jit-cache counters, the Module.fit step-time breakdown, the report tool,
and the zero-overhead-by-default guard."""
import importlib.util
import json
import os
from pathlib import Path

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tel

RS = np.random.RandomState


@pytest.fixture(autouse=True)
def _clean_registry():
    """Telemetry is process-global: every test starts and ends disabled."""
    tel.stop()
    tel.reset()
    yield
    tel.stop()
    tel.reset()


def _small_net():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _load_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _fit_smoke(tmp_path, kvstore="local"):
    """2-epoch synthetic Module.fit on the general (executor) loop, asked
    for by name (a recording registry no longer changes the path), with a
    JSON-lines sink; returns events."""
    fname = str(tmp_path / "telemetry.jsonl")
    x = RS(0).rand(20, 6).astype(np.float32)
    y = RS(1).randint(0, 4, 20).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=10)
    mod = mx.Module(_small_net(), context=mx.cpu(),
                    data_names=("data",), label_names=("softmax_label",))
    tel.start(fname)
    os.environ["MXNET_FUSED_FIT"] = "0"
    try:
        mod.fit(it, num_epoch=2, kvstore=kvstore,
                optimizer_params={"learning_rate": 0.1})
    finally:
        del os.environ["MXNET_FUSED_FIT"]
        tel.stop()
    return fname, _load_jsonl(fname)


# ----------------------------------------------------------------- registry
def test_counter_span_gauge_roundtrip_jsonl(tmp_path):
    fname = str(tmp_path / "t.jsonl")
    tel.start(fname)
    tel.counter("apples", 2, basket="a")
    tel.counter("apples", 3)
    tel.gauge("temp", 21.5)
    with tel.span("work", cat="unit", nbatch=7):
        pass
    assert tel.value("apples") == 5
    assert tel.value("temp") == 21.5
    tel.stop()
    events = _load_jsonl(fname)
    kinds = {}
    for ev in events:
        kinds.setdefault(ev["type"], []).append(ev)
    assert [e["total"] for e in kinds["counter"]
            if e["name"] == "apples"] == [2, 5]
    assert kinds["counter"][0]["tags"] == {"basket": "a"}
    (sp,) = kinds["span"]
    assert sp["name"] == "work" and sp["cat"] == "unit"
    assert sp["dur"] >= 0 and sp["tags"] == {"nbatch": 7}
    (summary,) = kinds["summary"]
    assert summary["counters"]["apples"] == 5
    assert summary["gauges"]["temp"] == 21.5
    # stop() disables: later traffic is dropped, file unchanged
    tel.counter("apples", 100)
    assert tel.value("apples") == 5


def test_span_cancel_suppresses_emission():
    tel.start()
    with tel.span("kept"):
        pass
    with tel.span("dropped") as sp:
        sp.cancel()
    names = [e["name"] for e in tel.events() if e["type"] == "span"]
    assert names == ["kept"]


def test_profiler_plus_telemetry_no_double_count(tmp_path):
    """With both live, a profiler-Scoped executor region lands in the
    chrome trace ONCE (telemetry's spans go to the jax.profiler trace and
    the registry, never into ``dump_profile``'s event list)."""
    fname = str(tmp_path / "both.json")
    mx.profiler.set_config(mode="symbolic", filename=fname)
    mx.profiler.set_state("run")
    tel.start()
    try:
        ex = _small_net().simple_bind(mx.cpu(), data=(2, 6),
                                      softmax_label=(2,))
        ex.forward(is_train=False, data=mx.nd.array(RS(0).rand(2, 6)))
    finally:
        tel.stop()
        mx.profiler.set_state("stop")
    mx.profiler.dump_profile()
    with open(fname) as f:
        trace = json.load(f)
    fwd = [e["name"] for e in trace["traceEvents"]
           if e.get("ph") != "M" and "executor.forward" in e["name"]]
    assert len(fwd) == 1, fwd
    # but telemetry still holds its own span for the same region
    assert any(e["type"] == "span" and e["name"] == "executor.forward"
               for e in tel.events())


def test_autostart_env(monkeypatch, tmp_path):
    fname = str(tmp_path / "auto.jsonl")
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    assert tel._autostart() is False
    assert not tel.enabled()
    monkeypatch.setenv("MXNET_TELEMETRY", fname)
    assert tel._autostart() is True
    assert tel.enabled()
    tel.counter("autostarted")
    tel.stop()
    events = _load_jsonl(fname)
    assert any(e["type"] == "counter" and e["name"] == "autostarted"
               for e in events)
    # multi-process launch contract: each worker gets its own file
    monkeypatch.setenv("MXTPU_PROCESS_ID", "3")
    assert tel._autostart() is True
    tel.stop()
    assert os.path.exists(fname + ".rank3")


def test_flush_failure_degrades_to_memory(tmp_path):
    """A sink that turns unwritable mid-run (dir removed, disk full) must
    not crash the instrumented training loop — file export disables with a
    warning and recording continues in memory."""
    d = tmp_path / "sink"
    d.mkdir()
    fname = str(d / "t.jsonl")
    tel.start(fname)
    tel.counter("before")
    tel.flush()
    os.remove(fname)
    d.rmdir()
    tel.counter("after")
    with pytest.warns(UserWarning, match="unwritable"):
        tel.flush()
    assert tel.enabled()
    assert tel.value("after") == 1
    tel.stop()   # no raise; summary stays in memory


def test_autostart_unwritable_path_degrades(monkeypatch, tmp_path):
    """A bad MXNET_TELEMETRY path must not kill the importing process —
    telemetry warns and stays disabled."""
    monkeypatch.setenv("MXNET_TELEMETRY",
                       str(tmp_path / "no-such-dir" / "t.jsonl"))
    monkeypatch.delenv("MXTPU_PROCESS_ID", raising=False)
    with pytest.warns(UserWarning, match="unwritable"):
        assert tel._autostart() is False
    assert not tel.enabled()


# ------------------------------------------------------------ executor wiring
def test_jit_cache_hit_miss_counters():
    tel.start()
    try:
        ex = _small_net().simple_bind(mx.cpu(), data=(4, 6),
                                      softmax_label=(4,))
        ex.forward(is_train=False, data=mx.nd.array(RS(0).rand(4, 6)))
        after_first = tel.counters()
        ex.forward(is_train=False, data=mx.nd.array(RS(1).rand(4, 6)))
        after_second = tel.counters()
    finally:
        tel.stop()
    assert after_first.get("jit_cache_miss", 0) >= 1
    assert after_first.get("jit_cache_hit", 0) == 0
    assert after_second["jit_cache_miss"] == after_first["jit_cache_miss"]
    assert after_second.get("jit_cache_hit", 0) >= 1
    # the spans carry the trace-vs-cached split
    spans = [e for e in tel.events() if e["type"] == "span"
             and e["name"] == "executor.forward"]
    assert [s["tags"]["jit"] for s in spans] == ["miss", "hit"]


# ------------------------------------------------------------------ fit loop
def test_fit_smoke_step_breakdown(tmp_path):
    fname, events = _fit_smoke(tmp_path)
    spans = [e for e in events if e["type"] == "span"]
    names = {s["name"] for s in spans}
    for required in ("data_wait", "forward", "backward", "update", "step",
                     "epoch"):
        assert required in names, (required, sorted(names))
    (summary,) = [e for e in events if e["type"] == "summary"]
    c = summary["counters"]
    assert c.get("jit_cache_miss", 0) >= 1
    assert c.get("jit_cache_hit", 0) >= 1
    assert c["fit_epochs"] == 2
    assert c["fit_batches"] == 4 and c["fit_samples"] == 40
    assert c["io_batches"] == 4
    # per-step component spans sum to within 20% of the step wall time
    steps = {}
    for s in spans:
        tags = s.get("tags") or {}
        if s["cat"] != "step" or "nbatch" not in tags:
            continue
        key = (tags["epoch"], tags["nbatch"])
        steps.setdefault(key, {})[s["name"]] = \
            steps.setdefault(key, {}).get(s["name"], 0) + s["dur"]
    assert len(steps) == 4
    for key, comp in steps.items():
        wall = comp.pop("step")
        assert sum(comp.values()) >= 0.8 * wall, (key, comp, wall)
        assert sum(comp.values()) <= 1.05 * wall, (key, comp, wall)


def test_fit_with_kvstore_counters(tmp_path):
    _, events = _fit_smoke(tmp_path, kvstore=mx.kvstore.create("local"))
    (summary,) = [e for e in events if e["type"] == "summary"]
    c = summary["counters"]
    assert c.get("kvstore_push", 0) >= 1
    assert c.get("kvstore_pull", 0) >= 1
    assert c.get("kvstore_push_bytes", 0) > 0
    assert c.get("param_updates", 0) >= 1


def test_speedometer_reads_telemetry_counters(caplog):
    import logging
    from mxnet_tpu.model import BatchEndParam
    tel.start()
    try:
        meter = mx.callback.Speedometer(batch_size=10, frequent=2)
        with caplog.at_level(logging.INFO, logger="mxnet_tpu.callback"):
            for n in range(5):
                tel.counter("fit_samples", 10)
                meter(BatchEndParam(epoch=0, nbatch=n, eval_metric=None,
                                    locals={}))
    finally:
        tel.stop()
    shown = [r.getMessage() for r in caplog.records
             if "samples/s" in r.getMessage()]
    assert shown, "Speedometer never reported with telemetry active"


def test_speedometer_stale_counter_falls_back(caplog):
    """A loop that never advances fit_samples (e.g. score()) must not
    report 0.00 samples/s while telemetry records — the meter falls back
    to batch-index arithmetic."""
    import logging
    from mxnet_tpu.model import BatchEndParam
    tel.start()
    try:
        meter = mx.callback.Speedometer(batch_size=10, frequent=2)
        with caplog.at_level(logging.INFO, logger="mxnet_tpu.callback"):
            for n in range(5):   # fit_samples never incremented
                meter(BatchEndParam(epoch=0, nbatch=n, eval_metric=None,
                                    locals={}))
    finally:
        tel.stop()
    rates = [float(r.getMessage().split()[2]) for r in caplog.records
             if "samples/s" in r.getMessage()]
    assert rates and all(r > 0 for r in rates), rates


# -------------------------------------------------------------- report tool
def _report_mod():
    root = Path(__file__).resolve().parents[3]
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", root / "tools" / "telemetry_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_report_renders_breakdown(tmp_path, capsys):
    fname, _ = _fit_smoke(tmp_path)
    report = _report_mod()
    assert report.main([fname, "--steps"]) == 0
    out = capsys.readouterr().out
    assert "Step-time breakdown" in out
    assert "data_wait" in out and "forward" in out and "backward" in out
    assert "coverage" in out
    assert "jit_cache_hit" in out


def test_report_empty_file(tmp_path, capsys):
    fname = str(tmp_path / "empty.jsonl")
    open(fname, "w").close()
    report = _report_mod()
    assert report.main([fname]) == 0
    assert "no step spans" in capsys.readouterr().out


# ---------------------------------------------------- zero-overhead default
def test_zero_overhead_when_disabled(tmp_path):
    """With MXNET_TELEMETRY unset, the registry must be a pure no-op: a
    span is the profiler's annotation alone, counters don't accumulate,
    and a full executor round leaves no events behind (no hot-path work)."""
    assert "MXNET_TELEMETRY" not in os.environ
    assert not tel.enabled()
    sp = tel.span("anything", cat="x", k=1)
    assert not isinstance(sp, tel._Span)
    with sp:
        sp.tags["ignored"] = True
    tel.counter("c", 5)
    tel.gauge("g", 1.0)
    tel.record_span("s", 0.0, 1.0)
    assert tel.counters() == {} and tel.gauges() == {} and tel.events() == []
    ex = _small_net().simple_bind(mx.cpu(), data=(2, 6), softmax_label=(2,))
    ex.forward(is_train=True, data=mx.nd.array(RS(0).rand(2, 6)),
               softmax_label=mx.nd.array([0.0, 1.0]))
    ex.backward()
    assert tel.counters() == {} and tel.events() == []
    assert not (tmp_path / "telemetry.jsonl").exists()


def test_fused_fit_kept_when_telemetry_off(tmp_path, caplog):
    """The fused fit fast path must stay engaged by default."""
    import logging
    x = RS(0).rand(20, 6).astype(np.float32)
    y = RS(1).randint(0, 4, 20).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=10)
    mod = mx.Module(_small_net(), context=mx.cpu(),
                    data_names=("data",), label_names=("softmax_label",))
    with caplog.at_level(logging.INFO):
        mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
    assert not any("general (executor) path" in r.message
                   for r in caplog.records)
