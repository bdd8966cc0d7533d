"""Training health & diagnostics tests: the hang watchdog (synthetic
stalled step -> all-thread-stack dump), the non-finite sentinel
(warn/raise per MXNET_CHECK_NUMERICS), crash snapshots, compile/memory
visibility, the diagnose tool, and the disabled-path zero-overhead
guard."""
import importlib.util
import json
import glob
import os
import threading
import time
import warnings
from pathlib import Path

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import diagnostics as diag
from mxnet_tpu import telemetry as tel

RS = np.random.RandomState


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    """Diagnostics and telemetry are process-global: every test starts and
    ends with the watchdog disarmed, the registry off, and no env vars."""
    for var in ("MXNET_WATCHDOG_SEC", "MXNET_CHECK_NUMERICS",
                "MXNET_DIAG_DIR"):
        monkeypatch.delenv(var, raising=False)
    diag.disarm()
    tel.stop()
    tel.reset()
    yield
    diag.disarm()
    tel.stop()
    tel.reset()


def _small_net():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _data(n=40, nan_at=None):
    x = RS(0).rand(n, 6).astype(np.float32)
    if nan_at is not None:
        x[nan_at] = np.nan
    y = RS(1).randint(0, 4, n).astype(np.float32)
    return mx.io.NDArrayIter(x, y, batch_size=10)


def _module():
    return mx.Module(_small_net(), context=mx.cpu(),
                     data_names=("data",), label_names=("softmax_label",))


def _bundles(tmp_path, reason="*"):
    return sorted(glob.glob(str(tmp_path / ("mxtpu_diag.%s.*.json" % reason))))


class _StallingIter(object):
    """Delegating iterator that sleeps before yielding one batch — a
    synthetic hung step for the watchdog."""

    def __init__(self, inner, stall_at, sec):
        self._inner = inner
        self._stall_at = stall_at
        self._sec = sec

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __iter__(self):
        self._n = 0
        self._it = iter(self._inner)
        return self

    def __next__(self):
        if self._n == self._stall_at:
            time.sleep(self._sec)
        self._n += 1
        return next(self._it)


# ----------------------------------------------------------------- watchdog
def test_watchdog_unit_stall_dump(tmp_path, monkeypatch):
    """Heartbeat silence past the threshold produces ONE bundle with every
    thread's stack and the telemetry snapshot; the next beat re-arms."""
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))
    tel.start()
    tel.counter("fit_batches", 3)
    assert diag.arm(seconds=0.2, poll=0.05)
    assert diag.armed()
    diag.heartbeat(epoch=0, nbatch=1)
    time.sleep(0.7)
    files = _bundles(tmp_path, "watchdog_stall")
    assert len(files) == 1, files   # one bundle per stall, not one per poll
    bundle = json.load(open(files[0]))
    assert bundle["reason"] == "watchdog_stall"
    assert bundle["extra"]["stall_sec"] >= 0.2
    names = {t["name"] for t in bundle["threads"]}
    assert "MainThread" in names and "mxtpu-watchdog" in names
    assert any(t["stack"] for t in bundle["threads"])
    assert bundle["telemetry"]["counters"]["fit_batches"] == 3
    assert bundle["heartbeat"]["last"] == {"epoch": 0, "nbatch": 1}
    assert tel.value("watchdog_stalls") == 1
    # a heartbeat clears the stall; renewed silence dumps again, into a
    # SEQUENCE-NUMBERED bundle — the first incident's evidence survives
    diag.heartbeat(epoch=0, nbatch=2)
    time.sleep(0.5)
    assert len(_bundles(tmp_path, "watchdog_stall")) == 2
    diag.disarm()
    assert not diag.armed()
    assert "mxtpu-watchdog" not in [t.name for t in threading.enumerate()]


def test_watchdog_stalled_fit_step(tmp_path, monkeypatch):
    """End-to-end: a fit whose iterator hangs mid-epoch trips the watchdog
    (the fit loop feeds the heartbeat), and the dump's main-thread stack
    shows the stalled fetch."""
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))
    inner = _data()
    it = _StallingIter(inner, stall_at=2, sec=1.2)
    mod = _module()
    tel.start()
    try:
        # warm the jit first: the watchdog cannot tell a long first-step
        # compile from a hang, and this test wants exactly ONE stall
        mod.fit(inner, num_epoch=1, optimizer_params={"learning_rate": 0.1})
        inner.reset()
        assert diag.arm(seconds=0.3, poll=0.05)
        mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
    finally:
        diag.disarm()
        tel.stop()
    files = _bundles(tmp_path, "watchdog_stall")
    assert len(files) == 1, files
    bundle = json.load(open(files[0]))
    # beats arrived per completed batch before the stall
    assert bundle["heartbeat"]["count"] >= 2
    assert bundle["heartbeat"]["last"].get("nbatch") == 1
    (main,) = [t for t in bundle["threads"] if t["name"] == "MainThread"]
    tail = "\n".join(main["stack"][-3:])
    assert "sleep" in tail or "__next__" in tail, tail
    assert bundle["telemetry"]["counters"].get("fit_batches", 0) >= 2
    assert bundle["telemetry"]["recent_events"], "event tail missing"


def test_watchdog_fed_by_score_loop(tmp_path, monkeypatch):
    """A long validation pass is progress, not a hang — score() feeds the
    heartbeat so healthy eval epochs cannot trip a false stall."""
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))
    mod = _module()
    it = _data()
    mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
    assert diag.arm(seconds=60)
    before = diag._beat_count
    it.reset()
    mod.score(it, "acc")
    assert diag._beat_count > before
    assert "eval_nbatch" in diag._beat_info
    diag.disarm()


def test_watchdog_env_autoarm(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_WATCHDOG_SEC", "30")
    assert diag._autoarm() is True
    assert diag.armed()
    # faulthandler wired to the per-rank file for hard crashes
    assert (tmp_path / ("mxtpu_diag.fault.pid%d.txt" % os.getpid())).exists()
    diag.disarm()
    monkeypatch.setenv("MXNET_WATCHDOG_SEC", "not-a-number")
    with pytest.warns(UserWarning, match="invalid"):
        assert diag._autoarm() is False
    assert not diag.armed()


# --------------------------------------------------------- non-finite sentinel
def test_sentinel_raise_names_offending_batch(tmp_path, monkeypatch):
    """MXNET_CHECK_NUMERICS=raise halts on the NaN batch with the batch
    index in the message, counters recorded, and a crash bundle behind."""
    monkeypatch.setenv("MXNET_FUSED_FIT", "0")   # the general loop's checks
    monkeypatch.setenv("MXNET_CHECK_NUMERICS", "raise")
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))
    it = _data(nan_at=25)   # batch 2 of 4 (batch_size 10)
    mod = _module()
    tel.start()
    try:
        with pytest.raises(diag.NonFiniteError, match="nbatch=2"):
            mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
        assert tel.value("nonfinite_loss", 0) >= 1
        assert tel.value("fit_crashes") == 1
        # the general path checks BETWEEN backward and update: the halt
        # leaves the weights un-poisoned
        arg_params, _ = mod.get_params()
        assert all(np.isfinite(v.asnumpy()).all()
                   for v in arg_params.values())
    finally:
        tel.stop()
    files = _bundles(tmp_path, "crash")
    assert len(files) == 1
    bundle = json.load(open(files[0]))
    assert bundle["exception"]["type"] == "NonFiniteError"
    assert bundle["telemetry"]["counters"]["nonfinite_loss"] >= 1


def test_sentinel_raise_fused_path_names_batch(tmp_path, monkeypatch):
    """By default fit rides the fused TrainStep — the sentinel
    must still halt with the BATCH index (the step-level check defers to
    the fit loop's epoch/nbatch context)."""
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_CHECK_NUMERICS", "raise")
    it = _data(nan_at=25)
    mod = _module()
    with pytest.raises(diag.NonFiniteError, match="nbatch=2"):
        mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})


def test_sentinel_warn_counts_and_continues(monkeypatch):
    """warn mode finishes the epoch, warning per hit and counting both the
    loss and the grad-global-norm non-finites."""
    monkeypatch.setenv("MXNET_FUSED_FIT", "0")   # the general loop's checks
    monkeypatch.setenv("MXNET_CHECK_NUMERICS", "warn")
    it = _data(nan_at=25)
    mod = _module()
    tel.start()
    try:
        with pytest.warns(UserWarning, match="non-finite"):
            mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
        assert tel.value("nonfinite_loss", 0) >= 1
        assert tel.value("nonfinite_grad", 0) >= 1
    finally:
        tel.stop()


def test_sentinel_healthy_fit_records_grad_norm(monkeypatch):
    """On a healthy run the sentinel is silent and leaves the
    grad_global_norm gauge as a free blow-up trend line."""
    monkeypatch.setenv("MXNET_FUSED_FIT", "0")   # the general loop's checks
    monkeypatch.setenv("MXNET_CHECK_NUMERICS", "raise")
    mod = _module()
    tel.start()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error", UserWarning)
            mod.fit(_data(), num_epoch=1,
                    optimizer_params={"learning_rate": 0.1})
        assert tel.value("nonfinite_loss") is None
        norm = tel.gauges().get("grad_global_norm")
        assert norm is not None and np.isfinite(norm) and norm > 0
    finally:
        tel.stop()


def test_sentinel_train_step(monkeypatch):
    """TrainStep's fused path checks its outputs (grads live inside the
    donated XLA program)."""
    monkeypatch.setenv("MXNET_CHECK_NUMERICS", "raise")
    from mxnet_tpu.train import TrainStep
    ts = TrainStep(_small_net(), mx.optimizer.SGD(learning_rate=0.1))
    params, state, aux = ts.init({"data": (10, 6)},
                                 {"softmax_label": (10,)})
    x = RS(0).rand(10, 6).astype(np.float32)
    y = RS(1).randint(0, 4, 10).astype(np.float32)
    params, state, aux, _ = ts(params, state, aux,
                               {"data": x, "softmax_label": y})
    x[0, 0] = np.nan
    with pytest.raises(diag.NonFiniteError, match="num_update=2"):
        ts(params, state, aux, {"data": x, "softmax_label": y})


def test_sentinel_monitor_names_tensor(monkeypatch):
    """Under the sentinel the Monitor names the first TENSOR that went
    non-finite — finer-grained than the fit loop's output check."""
    monkeypatch.setenv("MXNET_CHECK_NUMERICS", "warn")
    mon = mx.Monitor(interval=1, pattern=".*output.*")
    ex = _small_net().simple_bind(mx.cpu(), data=(2, 6), softmax_label=(2,))
    mon.install(ex)
    mon.tic()
    bad = np.full((2, 6), np.nan, np.float32)
    ex.forward(is_train=False, data=mx.nd.array(bad))
    tel.start()
    try:
        with pytest.warns(UserWarning, match="fc1_output"):
            mon.toc()
        assert tel.value("nonfinite_monitor", 0) >= 1
    finally:
        tel.stop()


def test_invalid_sentinel_mode_rejected(monkeypatch):
    monkeypatch.setenv("MXNET_CHECK_NUMERICS", "explode")
    with pytest.raises(mx.MXNetError, match="warn"):
        diag.check_numerics_mode()
    monkeypatch.setenv("MXNET_CHECK_NUMERICS", "off")
    assert diag.check_numerics_mode() is None


# ------------------------------------------------------------ crash snapshot
def test_crash_snapshot_on_callback_error(tmp_path, monkeypatch):
    """Any exception escaping fit leaves a forensic bundle when
    diagnostics is active (here: MXNET_DIAG_DIR alone)."""
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))

    def boom(param):
        raise RuntimeError("callback exploded")

    mod = _module()
    with pytest.raises(RuntimeError, match="callback exploded"):
        mod.fit(_data(), num_epoch=1, batch_end_callback=boom,
                optimizer_params={"learning_rate": 0.1})
    files = _bundles(tmp_path, "crash")
    assert len(files) == 1
    bundle = json.load(open(files[0]))
    assert bundle["exception"]["type"] == "RuntimeError"
    assert any("callback exploded" in ln
               for ln in bundle["exception"]["traceback"])
    assert bundle["extra"]["where"] == "module.fit"
    assert any(t["name"] == "MainThread" for t in bundle["threads"])


def test_crash_snapshot_inactive_without_optin(tmp_path, monkeypatch):
    """With no diagnostics env vars a fit crash writes NOTHING."""
    monkeypatch.chdir(tmp_path)

    def boom(param):
        raise RuntimeError("no bundle expected")

    mod = _module()
    with pytest.raises(RuntimeError):
        mod.fit(_data(), num_epoch=1, batch_end_callback=boom,
                optimizer_params={"learning_rate": 0.1})
    assert not diag.crash_snapshots_active()
    assert _bundles(tmp_path) == []


# -------------------------------------------- compile & memory visibility
def test_xla_compile_span_tagged_with_kind():
    """The set-up feed records one xla_compile span per program the
    executor's jit-cache miss path compiles, tagged with the program
    (trace + lowering + compile, not its execution); cache hits add
    none; the jit_cache_size gauge tracks."""
    import gc
    executor_programs = {"mxtpu_fwd", "mxtpu_grad"}
    tel.start()
    try:
        # the gauge is the LIVE total over sanitize.register_cache (dead
        # owners drop out via weakref) — collect earlier tests' dead
        # executors NOW so the deltas below see a stable registry
        gc.collect()
        ex = _small_net().simple_bind(mx.cpu(), data=(4, 6),
                                      softmax_label=(4,))
        ex.forward(is_train=False, data=mx.nd.array(RS(0).rand(4, 6)))
        ex.forward(is_train=False, data=mx.nd.array(RS(1).rand(4, 6)))
        spans = [e for e in tel.events() if e["type"] == "span"
                 and e["name"] == "xla_compile"
                 and e["tags"]["kind"] in executor_programs]
        assert len(spans) == 1, spans
        assert spans[0]["cat"] == "compile"
        assert spans[0]["tags"]["kind"] == "mxtpu_fwd"
        assert spans[0]["dur"] > 0
        # process-wide across executors (bucketing holds one per bucket),
        # so assert the delta, not an absolute value
        size1 = tel.gauges()["jit_cache_size"]
        assert size1 >= 1
        ex.forward(is_train=True, data=mx.nd.array(RS(0).rand(4, 6)),
                   softmax_label=mx.nd.array(RS(2).randint(0, 4, 4)))
        ex.backward()
        kinds = {e["tags"]["kind"] for e in tel.events()
                 if e["type"] == "span" and e["name"] == "xla_compile"}
        assert kinds >= executor_programs
        assert tel.gauges()["jit_cache_size"] == size1 + 1
        # and the published value IS the registry total (executor kinds +
        # imperative op keys + fused/serving entries all counted)
        from mxnet_tpu import sanitize as san
        assert tel.gauges()["jit_cache_size"] == san.total_cache_entries()
    finally:
        tel.stop()


def test_device_memory_gauges_per_epoch(tmp_path):
    """A telemetry-recorded fit samples the device-memory trajectory once
    per epoch."""
    mod = _module()
    tel.start(str(tmp_path / "t.jsonl"))
    try:
        mod.fit(_data(), num_epoch=2, optimizer_params={"learning_rate": 0.1})
        gauges = tel.gauges()
        assert gauges.get("device_live_bytes", 0) > 0
        assert gauges.get("device_live_arrays", 0) > 0
        mem_events = [e for e in tel.recent_events()
                      if e["type"] == "gauge"
                      and e["name"] == "device_live_bytes"]
        assert [e["tags"]["epoch"] for e in mem_events] == [0, 1]
    finally:
        tel.stop()


def test_sample_device_memory_noop_without_telemetry():
    assert diag.sample_device_memory(epoch=0) == {}
    assert tel.gauges() == {}


# ------------------------------------------------------------ tooling
def _tool(name):
    root = Path(__file__).resolve().parents[3]
    spec = importlib.util.spec_from_file_location(name,
                                                  root / "tools" /
                                                  (name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_diagnose_tool_smoke(tmp_path, monkeypatch, capsys):
    """tools/diagnose.py renders a generated bundle: stacks, counters,
    the exception, and the event tail."""
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))
    tel.start()
    tel.counter("fit_batches", 7)
    tel.gauge("device_live_bytes", 4096)
    with tel.span("step", cat="step", epoch=0, nbatch=3):
        pass
    try:
        raise ValueError("synthetic crash")
    except ValueError as e:
        path = diag.write_snapshot("crash", exc=e, extra={"where": "test"})
    tel.stop()
    assert path is not None
    diagnose = _tool("diagnose")
    assert diagnose.main([path]) == 0
    out = capsys.readouterr().out
    assert "crash" in out and "MainThread" in out
    assert "fit_batches" in out and "device_live_bytes" in out
    assert "ValueError" in out and "synthetic crash" in out
    assert "step" in out   # event tail
    # unreadable bundle: one-line error, exit 1, no traceback
    assert diagnose.main([str(tmp_path / "nope.json")]) == 1
    err = capsys.readouterr().err
    assert "cannot read" in err and "Traceback" not in err


def test_report_health_section(tmp_path, capsys):
    fname = str(tmp_path / "h.jsonl")
    events = [
        {"type": "span", "cat": "compile", "name": "xla_compile", "ts": 0,
         "dur": 2e5, "tags": {"kind": "grad"}},
        {"type": "summary", "ts": 1,
         "counters": {"nonfinite_loss": 8, "nonfinite_grad": 1,
                      "fit_batches": 4, "jit_cache_hit": 3},
         "gauges": {"jit_cache_size": 2, "device_live_bytes": 4096,
                    "grad_global_norm": 2.5}},
    ]
    with open(fname, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")
    report = _tool("telemetry_report")
    assert report.main([fname, "--health"]) == 0
    out = capsys.readouterr().out
    assert "Health" in out
    assert "nonfinite_loss" in out and "nonfinite_grad" in out
    assert "xla_compile" in out and "grad" in out
    assert "jit_cache_size" in out and "device_live_bytes" in out


def test_report_one_line_messages(tmp_path, capsys):
    report = _tool("telemetry_report")
    # unreadable path: one line on stderr, exit 1
    assert report.main([str(tmp_path / "missing.jsonl")]) == 1
    err = capsys.readouterr().err
    assert "cannot read" in err and len(err.strip().splitlines()) == 1
    # component spans but no completed 'step' span; also no summary event
    fname = str(tmp_path / "partial.jsonl")
    with open(fname, "w") as f:
        f.write(json.dumps({"type": "span", "cat": "step", "name": "forward",
                            "ts": 0, "dur": 5.0,
                            "tags": {"epoch": 0, "nbatch": 0}}) + "\n")
    assert report.main([fname]) == 0
    out = capsys.readouterr().out
    assert "no completed 'step' spans" in out
    assert "no summary event" in out


# ---------------------------------------------------- zero-overhead default
def test_disabled_path_guard(tmp_path, monkeypatch):
    """With no diagnostics env vars: no watchdog thread, heartbeats are
    inert, the sentinel is off, crash snapshots are off, telemetry stays
    empty, and a 2-epoch fit leaves no diagnostics output behind."""
    monkeypatch.chdir(tmp_path)
    for var in ("MXNET_WATCHDOG_SEC", "MXNET_CHECK_NUMERICS",
                "MXNET_DIAG_DIR"):
        assert var not in os.environ
    assert not diag.armed()
    assert diag.check_numerics_mode() is None
    assert not diag.crash_snapshots_active()
    before = {t.ident for t in threading.enumerate()}
    beats = diag._beat_count
    diag.heartbeat(epoch=0, nbatch=0)     # inert while disarmed
    assert diag._beat_count == beats
    mod = _module()
    mod.fit(_data(), num_epoch=2, optimizer_params={"learning_rate": 0.1})
    after = {t.ident for t in threading.enumerate()}
    assert "mxtpu-watchdog" not in [t.name for t in threading.enumerate()]
    assert after - before == set(), "fit spawned unexpected threads"
    assert list(tmp_path.glob("mxtpu_diag.*")) == []
    assert tel.counters() == {} and tel.events() == []
