"""Training curves & run comparison tests: the telemetry scalar layer
(emit / sampling / strict no-op), the fit-loop and optimizer wiring
(curve scalars, MXNET_OPT_STATS introspection vs a numpy reference),
multi-rank file naming, and the offline tools (tools/run_compare.py
regression verdicts + BENCH ingestion, telemetry_report --curves)."""
import importlib.util
import json
import math
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


def _small_net(hidden=8):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _load_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _scalar_events(events):
    return [e for e in events if e["type"] == "scalar"]


def _tool(name):
    root = Path(__file__).resolve().parents[3]
    spec = importlib.util.spec_from_file_location(
        name, root / "tools" / ("%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fit(path=None, lr=0.1, num_epoch=2, eval_metric="acc", eval_data=False,
         monitor=None, batch_size=8, n=32):
    """Synthetic learnable-labels fit with telemetry recording to path."""
    x = RS(0).rand(n, 6).astype(np.float32)
    w = RS(2).rand(6, 4)
    y = (x @ w).argmax(axis=1).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=batch_size, shuffle=False)
    val = mx.io.NDArrayIter(x, y, batch_size=batch_size) if eval_data \
        else None
    mod = mx.Module(_small_net(), context=mx.cpu())
    tel.start(path)
    try:
        mod.fit(it, eval_data=val, num_epoch=num_epoch,
                eval_metric=eval_metric, monitor=monitor,
                optimizer_params={"learning_rate": lr})
    finally:
        tel.stop()


# ------------------------------------------------------------- scalar layer
def test_scalar_roundtrip_and_summary(tmp_path):
    fname = str(tmp_path / "s.jsonl")
    tel.start(fname)
    tel.scalar("train_loss", 0, 2.5)
    tel.scalar("train_loss", 1, 1.5)
    tel.scalar("grad_norm", 1, 0.25, param="fc1_weight")
    tel.stop()
    events = _load_jsonl(fname)
    sc = _scalar_events(events)
    assert [(e["step"], e["value"]) for e in sc
            if e["name"] == "train_loss"] == [(0, 2.5), (1, 1.5)]
    (gn,) = [e for e in sc if e["name"] == "grad_norm"]
    assert gn["tags"] == {"param": "fc1_weight"}
    (summary,) = [e for e in events if e["type"] == "summary"]
    assert summary["scalars"]["train_loss"] == \
        {"n": 2, "step": 1, "value": 1.5}
    assert "grad_norm[param=fc1_weight]" in summary["scalars"]


def test_scalar_strict_noop_when_disabled(tmp_path):
    assert not tel.enabled()
    tel.scalar("train_loss", 0, 1.0)
    assert tel.scalars() == {} and tel.events() == []
    assert tel.scalar_due(0) is False   # gate is closed while disabled
    assert tel.sink_path() is None


def test_scalar_sampling_knob(monkeypatch):
    monkeypatch.setenv("MXNET_SCALARS_EVERY", "3")
    tel.start()
    assert [s for s in range(10) if tel.scalar_due(s)] == [0, 3, 6, 9]
    tel.stop()
    monkeypatch.setenv("MXNET_SCALARS_EVERY", "not-a-number")
    with pytest.warns(UserWarning, match="MXNET_SCALARS_EVERY"):
        tel.start()
    assert tel.scalar_due(1)   # degraded to every-step, not to broken
    tel.stop()


def test_non_finite_scalar_is_recorded():
    """Unlike histogram observations, a NaN curve point IS the finding."""
    tel.start()
    tel.scalar("train_loss", 7, float("nan"))
    (rec,) = _scalar_events(tel.events())
    assert rec["step"] == 7 and math.isnan(rec["value"])
    assert math.isnan(tel.scalars()["train_loss"]["value"])


def test_multi_rank_file_naming(monkeypatch, tmp_path):
    """Scalars ride the per-rank stream of the MXTPU launch contract."""
    base = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("MXNET_TELEMETRY", base)
    monkeypatch.setenv("MXTPU_PROCESS_ID", "2")
    assert tel._autostart() is True
    assert tel.sink_path() == base + ".rank2"
    tel.scalar("train_loss", 0, 1.0)
    tel.stop()
    assert not os.path.exists(base)
    events = _load_jsonl(base + ".rank2")
    assert any(e["type"] == "scalar" and e["name"] == "train_loss"
               for e in events)


# ---------------------------------------------------------------- fit wiring
def test_fit_emits_training_curves(tmp_path):
    fname = str(tmp_path / "fit.jsonl")
    _fit(fname, num_epoch=2, eval_data=True)
    sc = _scalar_events(_load_jsonl(fname))
    names = {e["name"] for e in sc}
    for required in ("train_accuracy", "lr", "samples_per_sec",
                     "val_accuracy"):
        assert required in names, (required, sorted(names))
    # the step axis is global: it does NOT reset at the epoch boundary
    steps = [e["step"] for e in sc if e["name"] == "train_accuracy"]
    assert steps == sorted(steps) and len(steps) == len(set(steps)) == 8
    assert all(e["value"] == 0.1 for e in sc if e["name"] == "lr")
    # one eval point per epoch, on the same step axis
    assert [e["step"] for e in sc if e["name"] == "val_accuracy"] == [4, 8]


def test_fit_scalar_sampling(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_SCALARS_EVERY", "3")
    fname = str(tmp_path / "fit.jsonl")
    _fit(fname, num_epoch=2)   # 8 batches -> due steps 0, 3, 6
    sc = _scalar_events(_load_jsonl(fname))
    assert [e["step"] for e in sc if e["name"] == "train_accuracy"] == \
        [0, 3, 6]
    # epoch-end rollups are never sampled away
    assert len([e for e in sc if e["name"] == "samples_per_sec"]) == 2


def test_fit_zero_scalar_writes_when_disabled(monkeypatch):
    """Acceptance guard: with the telemetry env unset, a fit makes ZERO
    scalar writes and gains zero extra device syncs — the emission paths
    must not even be reached."""
    assert "MXNET_TELEMETRY" not in os.environ

    def boom(*a, **k):
        raise AssertionError("telemetry.scalar called while disabled")
    monkeypatch.setattr(tel, "scalar", boom)
    x = RS(0).rand(16, 6).astype(np.float32)
    y = RS(1).randint(0, 4, 16).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=8)
    mod = mx.Module(_small_net(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
    assert tel.scalars() == {} and tel.events() == []


def test_lr_scheduler_boundary_pinned():
    """The decay-boundary lr point is recorded by the scheduler itself,
    so sampling can never drop the step where the rate changed."""
    sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
    sched.base_lr = 0.4
    tel.start()
    for num_update in range(1, 6):
        sched(num_update)
    pts = [(e["step"], e["value"]) for e in _scalar_events(tel.events())
           if e["name"] == "lr"]
    assert (3, 0.2) in pts and (5, 0.1) in pts


def test_speedometer_publishes_throughput_scalar():
    from mxnet_tpu.model import BatchEndParam
    tel.start()
    meter = mx.callback.Speedometer(batch_size=10, frequent=2)
    for n in range(5):
        tel.counter("fit_batches")
        tel.counter("fit_samples", 10)
        meter(BatchEndParam(epoch=0, nbatch=n, eval_metric=None,
                            locals={}))
    pts = [(e["step"], e["value"]) for e in _scalar_events(tel.events())
           if e["name"] == "throughput"]
    assert pts, "Speedometer published no throughput scalar"
    # the step axis is the fit loop's global batch counter, not nbatch
    assert all(step == tel.value("fit_batches") - 1 or step >= 0
               for step, _ in pts)
    assert all(rate > 0 for _, rate in pts)


def test_speedometer_eval_loop_uses_own_batch_axis():
    """Driven by a loop that does not feed the fit counters (score()),
    the throughput step must follow the loop's batch index — not pile
    every report onto the frozen fit_batches value."""
    from mxnet_tpu.model import BatchEndParam
    tel.start()
    for _ in range(1000):  # a prior fit left the counters at 1000
        tel.counter("fit_batches")
        tel.counter("fit_samples", 10)
    meter = mx.callback.Speedometer(batch_size=10, frequent=2)
    for n in range(5):  # eval loop: counters frozen
        meter(BatchEndParam(epoch=0, nbatch=n, eval_metric=None,
                            locals={}))
    steps = [e["step"] for e in _scalar_events(tel.events())
             if e["name"] == "throughput"]
    assert steps == [2, 4], steps


def test_monitor_stats_flow_to_scalars(tmp_path):
    """Per-tensor Monitor stats become a plottable `monitor` series."""
    mon = mx.monitor.Monitor(interval=2, pattern=".*weight")
    fname = str(tmp_path / "mon.jsonl")
    _fit(fname, num_epoch=1, monitor=mon)
    sc = _scalar_events(_load_jsonl(fname))
    keys = {(e["name"], e["tags"]["tensor"]) for e in sc
            if e["name"] == "monitor"}
    assert ("monitor", "fc1_weight") in keys, sorted(keys)
    assert ("monitor", "fc2_weight") in keys
    # armed every 2nd tic -> steps 0 and 2 of the 4-batch epoch
    steps = sorted({e["step"] for e in sc if e["name"] == "monitor"})
    assert steps == [0, 2]


# --------------------------------------------------------- optimizer stats
def test_opt_stats_against_numpy(monkeypatch):
    """grad/weight norms and the update-to-weight ratio must match a
    numpy replication of the SGD step: w1 = w0 - lr*rescale*g."""
    monkeypatch.setenv("MXNET_OPT_STATS", "1")
    w0 = RS(3).rand(5, 4).astype(np.float32)
    g = RS(4).rand(5, 4).astype(np.float32)
    lr, rescale = 0.25, 0.5
    opt = mx.optimizer.SGD(learning_rate=lr, rescale_grad=rescale, wd=0.0,
                           param_idx2name={0: "fc1_weight"})
    updater = mx.optimizer.get_updater(opt)
    tel.start()
    updater(0, mx.nd.array(g), mx.nd.array(w0))
    recorded = tel.scalars()
    gn = recorded["grad_norm[param=fc1_weight]"]
    wn = recorded["weight_norm[param=fc1_weight]"]
    ratio = recorded["update_ratio[param=fc1_weight]"]
    # 0-based update index — aligned with the fit loop's global step
    assert gn["step"] == wn["step"] == ratio["step"] == 0
    np.testing.assert_allclose(gn["value"], np.linalg.norm(g), rtol=1e-5)
    np.testing.assert_allclose(wn["value"], np.linalg.norm(w0), rtol=1e-5)
    expected_ratio = lr * rescale * np.linalg.norm(g) / np.linalg.norm(w0)
    np.testing.assert_allclose(ratio["value"], expected_ratio, rtol=1e-5)


def test_opt_stats_sampled(monkeypatch):
    monkeypatch.setenv("MXNET_OPT_STATS", "1")
    monkeypatch.setenv("MXNET_SCALARS_EVERY", "2")
    opt = mx.optimizer.SGD(learning_rate=0.1, param_idx2name={0: "w"})
    updater = mx.optimizer.get_updater(opt)
    w = mx.nd.array(RS(0).rand(3, 3).astype(np.float32))
    tel.start()
    for _ in range(4):
        updater(0, mx.nd.array(RS(1).rand(3, 3).astype(np.float32)), w)
    # update indices 0..3; only the even ones are due — the same phase
    # the fit loop's gstep gate samples, so one set of sync steps
    assert [e["step"] for e in _scalar_events(tel.events())
            if e["name"] == "grad_norm"] == [0, 2]


def test_opt_stats_resume_step_axis(monkeypatch):
    """On checkpoint resume (begin_num_update > 0) the step axis still
    starts at 0, matching the resumed fit loop's own gstep so sampling
    stays phase-aligned."""
    monkeypatch.setenv("MXNET_OPT_STATS", "1")
    monkeypatch.setenv("MXNET_SCALARS_EVERY", "2")
    opt = mx.optimizer.SGD(learning_rate=0.1, begin_num_update=1001,
                           param_idx2name={0: "w"})
    updater = mx.optimizer.get_updater(opt)
    w = mx.nd.array(RS(0).rand(3, 3).astype(np.float32))
    tel.start()
    for _ in range(4):
        updater(0, mx.nd.array(RS(1).rand(3, 3).astype(np.float32)), w)
    assert [e["step"] for e in _scalar_events(tel.events())
            if e["name"] == "grad_norm"] == [0, 2]


def test_opt_stats_off_by_default(monkeypatch):
    monkeypatch.delenv("MXNET_OPT_STATS", raising=False)
    opt = mx.optimizer.SGD(learning_rate=0.1, param_idx2name={0: "w"})
    updater = mx.optimizer.get_updater(opt)
    tel.start()
    updater(0, mx.nd.array(RS(1).rand(3, 3).astype(np.float32)),
            mx.nd.array(RS(0).rand(3, 3).astype(np.float32)))
    assert not any(e["name"] == "grad_norm"
                   for e in _scalar_events(tel.events()))
    # and with telemetry off the hook is a strict no-op even when opted in
    tel.stop()
    monkeypatch.setenv("MXNET_OPT_STATS", "1")
    updater(0, mx.nd.array(RS(1).rand(3, 3).astype(np.float32)),
            mx.nd.array(RS(0).rand(3, 3).astype(np.float32)))
    assert tel.scalars() == {}


def test_opt_stats_update_still_correct(monkeypatch):
    """The introspection wrapper must not change the update itself."""
    monkeypatch.setenv("MXNET_OPT_STATS", "1")
    w0 = RS(3).rand(4, 4).astype(np.float32)
    g = RS(4).rand(4, 4).astype(np.float32)
    w = mx.nd.array(w0)
    opt = mx.optimizer.SGD(learning_rate=0.5, rescale_grad=1.0, wd=0.0,
                           param_idx2name={0: "w"})
    tel.start()
    mx.optimizer.get_updater(opt)(0, mx.nd.array(g), w)
    np.testing.assert_allclose(w.asnumpy(), w0 - 0.5 * g, rtol=1e-5)


def test_fused_fit_lr_reads_live_counter(monkeypatch, tmp_path):
    """On the fused path the optimizer's num_update only
    syncs back at epoch end — the fit loop's `lr` points must read the
    TrainStep's live counter, so a schedule visibly decays MID-epoch."""
    fname = str(tmp_path / "fused.jsonl")
    x = RS(0).rand(64, 6).astype(np.float32)
    y = RS(1).randint(0, 4, 64).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=8)
    mod = mx.Module(_small_net(), context=mx.cpu())
    tel.start(fname)
    try:
        mod.fit(it, num_epoch=1, optimizer_params={
            "learning_rate": 0.4,
            "lr_scheduler": mx.lr_scheduler.FactorScheduler(step=2,
                                                            factor=0.5)})
    finally:
        tel.stop()
    events = _load_jsonl(fname)
    assert any(e["type"] == "span" and e["name"] == "fused_step"
               for e in events), "fused path did not engage"
    lr_vals = [e["value"] for e in _scalar_events(events)
               if e["name"] == "lr"]
    assert len(set(lr_vals)) > 1, lr_vals   # decayed mid-epoch, not flat
    assert min(lr_vals) < 0.4


def _reject_const(x):
    raise ValueError("non-RFC8259 JSON token: %s" % x)


def test_metrics_json_nan_safe():
    """/metrics.json must stay strictly parseable while a NaN curve point
    is live — the incident it exists to surface."""
    from mxnet_tpu import metrics_server
    tel.start()
    tel.scalar("train_loss", 1, float("nan"))
    body = json.dumps(metrics_server.json_snapshot(), default=str)
    doc = json.loads(body, parse_constant=_reject_const)
    assert doc["scalars"]["train_loss"]["value"] == "nan"


# ------------------------------------------------------------- run_compare
def _write_stream(path, series):
    """{name: [(step, value), ...]} -> a scalar JSON-lines stream."""
    with open(path, "w") as f:
        for name, pts in series.items():
            for step, value in pts:
                f.write(json.dumps({"type": "scalar", "name": name,
                                    "ts": 0.0, "step": step,
                                    "value": value}) + "\n")
    return str(path)


def test_series_key_lockstep_with_telemetry():
    rc = _tool("run_compare")
    tags = {"param": "fc1_weight", "shard": 0}
    assert rc.series_key("grad_norm", tags) == \
        tel.series_key("grad_norm", tags)
    assert rc.series_key("lr") == tel.series_key("lr") == "lr"


def test_run_compare_regression_flagged(tmp_path, capsys):
    rc = _tool("run_compare")
    good = _write_stream(tmp_path / "good.jsonl", {
        "train_loss": [(s, 2.0 - 0.2 * s) for s in range(8)]})
    bad = _write_stream(tmp_path / "bad.jsonl", {
        "train_loss": [(s, 2.0 + 0.3 * s) for s in range(8)]})
    assert rc.main([good, bad]) == 0          # report-only: exit 0
    out = capsys.readouterr().out
    assert "train_loss" in out and "REGRESSION" in out
    assert rc.main([good, bad, "--check"]) == 2
    capsys.readouterr()


def test_run_compare_ok_within_threshold(tmp_path, capsys):
    rc = _tool("run_compare")
    a = _write_stream(tmp_path / "a.jsonl", {
        "train_loss": [(s, 1.0 - 0.1 * s) for s in range(6)],
        "val_acc": [(5, 0.90)]})
    b = _write_stream(tmp_path / "b.jsonl", {
        "train_loss": [(s, 1.02 - 0.1 * s) for s in range(6)],
        "val_acc": [(5, 0.89)]})
    assert rc.main([a, b, "--check"]) == 0
    assert "verdict: OK" in capsys.readouterr().out
    # tightening the threshold below the 1.1% acc drop flips the verdict
    assert rc.main([a, b, "--check", "--threshold", "0.005"]) == 2
    capsys.readouterr()


def test_run_compare_nan_final_is_regression(tmp_path, capsys):
    rc = _tool("run_compare")
    good = _write_stream(tmp_path / "g.jsonl",
                         {"train_loss": [(0, 1.0), (1, 0.8)]})
    diverged = _write_stream(tmp_path / "d.jsonl",
                             {"train_loss": [(0, 1.0),
                                             (1, float("nan"))]})
    assert rc.main([good, diverged, "--check"]) == 2
    assert "REGRESSION" in capsys.readouterr().out
    # the machine view of that verdict stays strictly parseable: the NaN
    # final value is stringified, never a bare NaN token
    assert rc.main([good, diverged, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out, parse_constant=_reject_const)
    (rec,) = [r for r in doc["runs"][0]["metrics"]
              if r["metric"] == "train_loss"]
    assert rec["final"] == "nan" and rec["verdict"] == "REGRESSION"


def test_run_compare_directionless_never_flags(tmp_path, capsys):
    rc = _tool("run_compare")
    a = _write_stream(tmp_path / "a.jsonl", {"lr": [(0, 0.1), (5, 0.1)]})
    b = _write_stream(tmp_path / "b.jsonl", {"lr": [(0, 10.0), (5, 10.0)]})
    assert rc.main([a, b, "--check"]) == 0
    assert "info" in capsys.readouterr().out
    # ... unless the operator assigns a direction
    assert rc.main([a, b, "--check", "--better", "lr=down"]) == 2
    capsys.readouterr()


def test_run_compare_json_output(tmp_path, capsys):
    rc = _tool("run_compare")
    good = _write_stream(tmp_path / "good.jsonl", {
        "train_loss": [(s, 2.0 - 0.2 * s) for s in range(8)]})
    bad = _write_stream(tmp_path / "bad.jsonl", {
        "train_loss": [(s, 2.5) for s in range(8)]})
    assert rc.main([good, bad, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    (run,) = doc["runs"]
    assert run["verdict"] == "REGRESSION"
    assert run["regressions"] == ["train_loss"]
    (rec,) = [r for r in run["metrics"] if r["metric"] == "train_loss"]
    assert rec["direction"] == "down" and rec["final_delta"] > 0.05


def test_run_compare_bench_ingestion(tmp_path, capsys):
    """BENCH_*.json records compare their headline img/s and chain to
    their scalar stream via meta.telemetry_scalars (bench.py stamps it)."""
    rc = _tool("run_compare")
    stream_a = _write_stream(tmp_path / "a_scalars.jsonl",
                             {"train_loss": [(0, 1.0), (9, 0.2)]})
    stream_b = _write_stream(tmp_path / "b_scalars.jsonl",
                             {"train_loss": [(0, 1.0), (9, 0.9)]})

    def bench(path, value, stream):
        # the driver-wrapper shape (bench.py's record under "parsed")
        doc = {"n": 1, "cmd": "python bench.py", "rc": 0, "tail": "",
               "parsed": {"metric": "resnet50_train_img_per_sec_b32",
                          "value": value, "unit": "img/s",
                          "meta": {"config": {"batch": 32}, "world_size": 1,
                                   "rank": None,
                                   "telemetry_scalars": stream}}}
        path.write_text(json.dumps(doc))
        return str(path)

    a = bench(tmp_path / "BENCH_a.json", 2900.0, stream_a)
    b = bench(tmp_path / "BENCH_b.json", 2400.0, stream_b)
    assert rc.main([a, b, "--check"]) == 2
    out = capsys.readouterr().out
    assert "resnet50_train_img_per_sec_b32" in out
    assert "train_loss" in out          # curves arrived via the chain
    assert out.count("REGRESSION") >= 2  # throughput AND the loss curve


def test_run_compare_unreadable_and_empty(tmp_path, capsys):
    rc = _tool("run_compare")
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert rc.main([str(empty), str(empty)]) == 1
    assert rc.main([str(tmp_path / "missing.jsonl"), str(empty)]) == 1


# ------------------------------------------------------------- curves view
def test_report_curves_smoke(tmp_path, capsys):
    fname = str(tmp_path / "fit.jsonl")
    _fit(fname, num_epoch=2)
    report = _tool("telemetry_report")
    assert report.main([fname, "--curves"]) == 0
    out = capsys.readouterr().out
    assert "Scalars (training curves)" in out
    assert "train_accuracy" in out and "lr" in out
    assert any(ch in out for ch in "▁▂▃▄▅▆▇█")


def test_report_curves_rejected_with_ranks(tmp_path):
    report = _tool("telemetry_report")
    with pytest.raises(SystemExit):
        report.main([str(tmp_path / "x.jsonl"), "--ranks", "--curves"])


def test_sparkline_handles_nan_and_flat():
    report = _tool("telemetry_report")
    assert set(report.sparkline([1.0, 1.0, 1.0])) <= set("▁▂▃▄▅▆▇█")
    assert "!" in report.sparkline([1.0, float("nan"), 2.0])
    assert report.sparkline([float("nan")] * 3) == "!!!"


# ------------------------------------------------------------ e2e demo
def test_e2e_bad_lr_run_flagged(tmp_path, capsys):
    """The acceptance demo: two synthetic fits, one with a deliberately
    hot lr; run_compare names the regressed training metric, and the good
    run passes the --check gate against itself."""
    rc = _tool("run_compare")
    good = str(tmp_path / "good.jsonl")
    bad = str(tmp_path / "bad.jsonl")
    _fit(good, lr=0.5, num_epoch=3, eval_metric="ce", n=64)
    _fit(bad, lr=150.0, num_epoch=3, eval_metric="ce", n=64)
    assert rc.main([good, bad, "--check", "--metric",
                    "train_cross-entropy"]) == 2
    out = capsys.readouterr().out
    assert "train_cross-entropy" in out and "REGRESSION" in out
    assert rc.main([good, good, "--check"]) == 0
    capsys.readouterr()
