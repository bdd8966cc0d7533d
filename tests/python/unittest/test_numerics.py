"""Numerics observatory tests (MXNET_MONITOR): the spec grammar + memoized
arming, the monitor-off byte-identity contract (no monitored program is
ever BUILT, and the fused-fit cache key carries the monitor field), the
sampled-step publication path (telemetry series + the bounded history
ring), non-finite provenance end-to-end under ``MXNET_SAN=all:raise``
(zero sanitizer violations while the replay syncs), the legacy Monitor
bridge on the fused fit path, the sentinel's ``grad_norm`` watched series
and the AMP-overflow quiet window, the reporting tools
(tools/numerics_report.py, tools/tpu_numerics_check.py), the committed
MULTICHIP_NUM record's run_compare self-gate, and what the monitored
cadence consists of (dispatches, fetches and builds, counted)."""
import importlib.util
import json
import logging
import math
import os
import subprocess
import sys
import warnings
from pathlib import Path

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu import numerics as num
from mxnet_tpu import sentinel as sen
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.monitor import Monitor

ROOT = Path(__file__).resolve().parents[3]

BATCH = 8


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch, tmp_path):
    """The monitor memo/ring, telemetry and sentinel are process-global:
    every test starts and ends disarmed, and diagnostics bundles land in
    tmp_path instead of the repo root."""
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))
    monkeypatch.delenv("MXNET_MONITOR", raising=False)
    num.reset()
    sen.disarm()
    tel.stop()
    tel.reset()
    yield
    num.reset()
    sen.disarm()
    tel.stop()
    tel.reset()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "tools" / ("%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mlp(classes=8):
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, name="fc1", num_hidden=16)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, name="fc2", num_hidden=16)
    h = mx.sym.FullyConnected(h, name="fc3", num_hidden=classes)
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _batch(seed=0, classes=8, width=32):
    rs = np.random.RandomState(seed)
    return {"data": rs.uniform(-1, 1, (BATCH, width)).astype(np.float32),
            "softmax_label": rs.randint(0, classes,
                                        (BATCH,)).astype(np.float32)}


def _train_step(**kw):
    from mxnet_tpu.train import TrainStep
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                           rescale_grad=1.0 / BATCH)
    ts = TrainStep(_mlp(), opt, **kw)
    p, s, a = ts.init({"data": (BATCH, 32)}, {"softmax_label": (BATCH,)})
    return ts, p, s, a


# ---------------------------------------------------------- spec grammar
def test_parse_spec_grammar():
    assert num.parse_spec(None) is None
    for off in ("", "0", "off", "false", "none"):
        assert num.parse_spec(off) is None
    sp = num.parse_spec("10")
    assert (sp.every_n, sp.stats, sp.raise_on_nonfinite) \
        == (10, ("grad", "update"), False)
    sp = num.parse_spec("5:grad,act")
    assert (sp.every_n, sp.stats) == (5, ("grad", "act"))
    sp = num.parse_spec("1:grad,update:raise")
    assert sp.raise_on_nonfinite is True
    assert num.parse_spec("on").every_n == 1
    # cadence semantics
    assert num.parse_spec("3").due(0) and num.parse_spec("3").due(6)
    assert not num.parse_spec("3").due(2)
    for bad in ("x", "-3", "1:bogus"):
        with pytest.raises(MXNetError):
            num.parse_spec(bad)


def test_spec_memo_follows_env(monkeypatch):
    assert num.spec() is None and num.monitor_key() is None
    monkeypatch.setenv("MXNET_MONITOR", "3:grad")
    sp = num.spec()
    assert sp.every_n == 3 and num.spec() is sp     # memoized
    assert num.monitor_key() == sp.key()
    monkeypatch.delenv("MXNET_MONITOR")
    assert num.spec() is None and num.monitor_key() is None


# -------------------------------------------------- off = byte-identical
def test_monitor_off_builds_no_monitored_program(monkeypatch):
    """With MXNET_MONITOR unset the monitored program must never be
    BUILT (not just never dispatched) — the unmonitored step stays
    byte-identical and the jit cache holds exactly the plain program."""
    from mxnet_tpu.train import TrainStep
    ts, p, s, a = _train_step()
    monkeypatch.setattr(
        TrainStep, "_monitored_step",
        lambda self: pytest.fail("monitored program built with "
                                 "MXNET_MONITOR unset"))
    batch = _batch()
    for _ in range(3):
        p, s, a, o = ts(p, s, a, batch)
    assert ts._mon_cache == {}
    assert ts._last_mon_entry is None
    assert num.history() == [] and num.bundle_section() is None


def test_fused_fit_cache_key_carries_monitor_field(monkeypatch):
    """The monitor spec joins the fused-fit cache key: flipping
    MXNET_MONITOR must change the key fields, so a monitor-off fit can
    never be served a monitored TrainStep (and vice versa)."""
    from mxnet_tpu.module.module import _fused_fit_key_fields, _monitor_key
    opt = mx.optimizer.SGD(learning_rate=0.1)
    off = _fused_fit_key_fields(opt, None)
    assert off["monitor"] is None
    monkeypatch.setenv("MXNET_MONITOR", "7:grad")
    on = _fused_fit_key_fields(opt, None)
    assert on["monitor"] == num.spec().key() == _monitor_key()
    assert off != on


# ------------------------------------------------- sampled-step publish
def test_sampled_steps_publish_ring_and_telemetry(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_MONITOR", "2:grad,update,act")
    num.reset()
    sink = tmp_path / "tel.jsonl"
    tel.start(str(sink))
    try:
        ts, p, s, a = _train_step()
        batch = _batch()
        for _ in range(5):
            p, s, a, o = ts(p, s, a, batch)
    finally:
        tel.stop()
    hist = num.history()
    assert [e["update"] for e in hist] == [0, 2, 4]
    ent = hist[-1]
    assert ent["who"] == "train_step"
    assert math.isfinite(ent["global_grad_norm"]) \
        and ent["global_grad_norm"] > 0
    assert set(ent["grad_norms"]) == {"fc1_weight", "fc1_bias",
                                      "fc2_weight", "fc2_bias",
                                      "fc3_weight", "fc3_bias"}
    assert all(math.isfinite(v) for v in ent["grad_norms"].values())
    assert all(v >= 0 for v in ent["update_ratios"].values())
    assert all(ent["heads_finite"])
    assert ent["act_rms"] and not num.entry_bad(ent)
    # the step instance hands the fit loop the entry it just published
    assert ts._last_mon_entry == ent
    assert num.last_global_norm() == ent["global_grad_norm"]
    sec = num.bundle_section()
    assert sec["spec"]["every_n"] == 2 and len(sec["history"]) == 3
    # only sampled updates built the monitored program (one trace env)
    assert len(ts._mon_cache) == 1
    text = sink.read_text()
    assert '"grad_norm"' in text and '"update_ratio"' in text
    assert '"grad_global_norm"' in text


def test_pipeline_monitor_merges_per_stage_stats(monkeypatch):
    """PipelineTrainStep samples too: each stage computes its own
    params' stats on its sub-mesh and the host merge covers the FULL
    parameter set.  No update/param ratio on this path — the stage
    updates donate the pre-update params before the new ones exist."""
    import jax
    from mxnet_tpu.parallel.mesh import make_pp_mesh
    from mxnet_tpu.train import PipelineTrainStep
    monkeypatch.setenv("MXNET_MONITOR", "1:grad,update")
    num.reset()
    mesh = make_pp_mesh(2, dp=1, devices=jax.devices()[:2])
    opt = mx.optimizer.SGD(learning_rate=0.1, rescale_grad=1.0 / BATCH)
    ts = PipelineTrainStep(_mlp(), opt, mesh=mesh, num_microbatches=2)
    p, s, a = ts.init({"data": (BATCH, 32)}, {"softmax_label": (BATCH,)})
    batch = _batch()
    rng = jax.random.PRNGKey(7)
    for _ in range(2):
        p, s, a, o = ts(p, s, a, batch, rng=rng)
    hist = num.history()
    assert [e["update"] for e in hist] == [0, 1]
    ent = hist[-1]
    assert ent["who"] == "pipeline_step"
    assert set(ent["grad_norms"]) == {"fc1_weight", "fc1_bias",
                                      "fc2_weight", "fc2_bias",
                                      "fc3_weight", "fc3_bias"}
    assert math.isfinite(ent["global_grad_norm"])
    assert "update_ratios" not in ent


def test_history_ring_is_bounded(monkeypatch):
    monkeypatch.setenv("MXNET_MONITOR", "1:grad")
    monkeypatch.setenv("MXNET_MONITOR_RING", "4")
    num.reset()
    assert num.ring_capacity() == 4
    ts, p, s, a = _train_step()
    batch = _batch()
    for _ in range(6):
        p, s, a, o = ts(p, s, a, batch)
    hist = num.history()
    assert len(hist) == 4
    assert [e["update"] for e in hist] == [2, 3, 4, 5]


# --------------------------------------------- non-finite provenance e2e
_PROV_CHILD = r"""
import glob, json, os
import numpy as np

import jax
import mxnet_tpu as mx
from mxnet_tpu import numerics as num
from mxnet_tpu.train import TrainStep

BATCH = 8
d = mx.sym.Variable("data")
h = mx.sym.FullyConnected(d, name="fc1", num_hidden=16)
h = mx.sym.Activation(h, act_type="relu")
h = mx.sym.FullyConnected(h, name="fc2", num_hidden=16)
h = mx.sym.FullyConnected(h, name="fc3", num_hidden=8)
net = mx.sym.SoftmaxOutput(h, name="softmax")

rs = np.random.RandomState(0)
batch = {"data": rs.uniform(-1, 1, (BATCH, 32)).astype(np.float32),
         "softmax_label": rs.randint(0, 8, (BATCH,)).astype(np.float32)}
opt = mx.optimizer.SGD(learning_rate=0.1, rescale_grad=1.0 / BATCH)
# AMP policy: the overflow skip keeps the returned params PRE-update, so
# the replay sees exactly the injected weight and names its layer
ts = TrainStep(net, opt, policy=True)
p, s, a = ts.init({"data": (BATCH, 32)}, {"softmax_label": (BATCH,)})
rng = jax.random.PRNGKey(7)
p, s, a, o = ts(p, s, a, batch, rng=rng)

w = np.array(p["fc2_weight"])
w[0, 0] = np.inf
p = dict(p)
p["fc2_weight"] = jax.device_put(w).astype(ts.params_dtype) \
    if hasattr(ts, "params_dtype") else jax.device_put(w)

raised = None
try:
    ts(p, s, a, batch, rng=rng)
except num.NumericsError as e:
    raised = str(e)
assert raised is not None, "NumericsError not raised under :raise"

bundles = glob.glob(os.path.join(os.environ["MXNET_DIAG_DIR"],
                                 "mxtpu_diag.numerics.*.json"))
assert len(bundles) == 1, bundles
doc = json.load(open(bundles[0]))
prov = doc["extra"]["numerics_provenance"]
trig = doc["extra"]["trigger"]
print("RESULT " + json.dumps({
    "verdict": prov.get("verdict"),
    "first_bad_op": prov.get("first_bad_op"),
    "bad_inputs": prov.get("bad_inputs"),
    "params_state": prov.get("params_state"),
    "trigger_update": trig.get("update"),
    "ring_section": sorted(doc.get("numerics", {})),
    "raised": raised,
    "bundle": bundles[0],
}))
"""


@pytest.mark.timeout(300)
def test_nonfinite_provenance_end_to_end(tmp_path):
    """Injected inf in fc2's weight at update 1 -> the sampled step's
    stats flag non-finite grads, the host replay names fc2 as the FIRST
    bad op, the ``numerics`` post-mortem bundle is written, and
    ``:raise`` escalates to NumericsError — all with MXNET_SAN=all:raise
    armed, so the monitor's own syncs must be planned (zero sanitizer
    violations, or the child dies non-zero)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("MXNET_", "MXTPU_"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_MONITOR"] = "1:grad,update:raise"
    env["MXNET_SAN"] = "all:raise"
    env["MXNET_DIAG_DIR"] = str(tmp_path)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"),) if p] + [str(ROOT)])
    proc = subprocess.run([sys.executable, "-B", "-c", _PROV_CHILD],
                          cwd=str(tmp_path), env=env,
                          capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout + proc.stderr
    res = json.loads(line[-1][len("RESULT "):])
    assert res["trigger_update"] == 1
    assert "fc2" in res["verdict"]
    assert "update 1" in res["verdict"]
    assert res["first_bad_op"]["op"] == "fc2"
    assert any(b["name"] == "fc2_weight" and b["input"] == "param"
               for b in res["bad_inputs"])
    assert "pre-update" in res["params_state"]
    assert "history" in res["ring_section"]
    assert res["verdict"] in res["raised"]
    # the report tool renders the bundle it names (PROVENANCE block)
    rep = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "numerics_report.py"),
         res["bundle"]], capture_output=True, text=True, timeout=60)
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert "VERDICT" in rep.stdout and "fc2" in rep.stdout


# ------------------------------------------------- legacy Monitor bridge
def _fit_with_monitor(monitor, num_epoch=1):
    os.environ["MXNET_FUSED_FIT"] = "1"
    try:
        np.random.seed(0)
        x = np.random.randn(120, 1, 12, 12).astype(np.float32)
        y = np.random.randint(0, 4, 120).astype(np.float32)
        it = mx.io.NDArrayIter(x, y, batch_size=30)
        mod = mx.Module(models.get_mlp(num_classes=4))
        mod.fit(it, num_epoch=num_epoch, optimizer="sgd",
                optimizer_params={"learning_rate": 0.01},
                initializer=mx.initializer.Xavier(magnitude=2.0),
                monitor=monitor)
        return mod
    finally:
        os.environ.pop("MXNET_FUSED_FIT", None)


def test_legacy_monitor_served_from_fused_path():
    rows = []

    class Capture(Monitor):
        def toc_print(self):
            rows.extend(self.toc())

    mod = _fit_with_monitor(Capture(interval=2))
    # the fused path engaged AND fed the monitor parameter rows
    assert getattr(mod, "_fused_ts_cache", None) is not None
    assert rows, "fused path fed no Monitor rows"
    names = {n for _, n, _ in rows}
    assert "fc1_weight" in names and "fc3_bias" in names
    for _, _, stat in rows:
        assert np.isfinite(float(stat)), stat
    # rows report the batch that was armed, interval-spaced
    steps = sorted({s for s, _, _ in rows})
    assert all(s % 2 == 0 for s in steps)


def test_legacy_monitor_custom_stat_func_falls_back(caplog):
    with caplog.at_level(logging.INFO):
        mod = _fit_with_monitor(Monitor(1, stat_func=lambda x: 0.0))
    # arbitrary host python cannot be traced into the donated program
    assert getattr(mod, "_fused_ts_cache", None) is None
    assert any("custom stat_func" in r.getMessage()
               for r in caplog.records)


# --------------------------------------------------- sentinel grad_norm
def _arm_fast(monkeypatch, warmup=4, consec=3):
    monkeypatch.setenv("MXNET_SENTINEL_WARMUP", str(warmup))
    monkeypatch.setenv("MXNET_SENTINEL_CONSEC", str(consec))
    assert sen.arm("step:3sigma") is True


def test_sentinel_grad_norm_series_joins_and_names_phase(monkeypatch):
    _arm_fast(monkeypatch)
    # jittered warmup so the time-phase sigmas are real (not the floor),
    # while the constant grad_norm baseline keeps only its relative floor
    for i, c in enumerate((0.08, 0.09, 0.10, 0.11, 0.09, 0.10)):
        sen.step_close(0.01 + c, 0.01, c, epoch=0, nbatch=i,
                       grad_norm=1.0)
    assert sen.anatomy()["series"]["grad_norm"]["mean"] \
        == pytest.approx(1.0, rel=0.01)
    # an explosion: step time diverges (the trigger) with grad_norm the
    # DOMINANT z — the anomaly names the training dynamics, not a phase
    with pytest.warns(sen.SentinelWarning, match="grad_norm"):
        for i in range(3):
            sen.step_close(0.2, 0.01, 0.19, epoch=0, nbatch=10 + i,
                           grad_norm=80.0)
    assert sen.last_anomaly()["phase"] == "grad_norm"
    assert sen.last_anomaly()["zscores"]["grad_norm"] > 3


def test_sentinel_grad_norm_nonfinite_not_folded(monkeypatch):
    _arm_fast(monkeypatch)
    for i in range(6):
        sen.step_close(0.1, 0.01, 0.09, epoch=0, nbatch=i,
                       grad_norm=float("inf"))
    # non-finite samples never join the baseline (the numerics monitor
    # escalates those itself) — the series simply stays absent
    assert "grad_norm" not in sen.anatomy()["series"]


def test_sentinel_overflow_opens_quiet_window(monkeypatch):
    """An AMP overflow burst legitimately perturbs every watched series:
    note_overflow() re-opens the warmup quiet window, so the divergent
    steps that follow fold into the baseline instead of firing."""
    _arm_fast(monkeypatch)
    for i in range(6):
        sen.step_close(0.1, 0.01, 0.09, epoch=0, nbatch=i, grad_norm=1.0)
    sen.note_overflow()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for i in range(4):
            sen.step_close(0.5, 0.01, 0.49, epoch=0, nbatch=6 + i,
                           grad_norm=90.0)
    assert sen.last_anomaly() is None


# ------------------------------------------------------- reporting tools
def test_numerics_report_help_and_curated_errors(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "numerics_report.py"),
         "--help"], capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "numerics" in proc.stdout

    nr = _load_tool("numerics_report")
    sectionless = tmp_path / "bundle.json"
    sectionless.write_text(json.dumps(
        {"type": "mxtpu_diagnostics", "reason": "fatal_signal"}))
    with pytest.raises(ValueError, match="no 'numerics' section"):
        nr.load_numerics(str(sectionless))
    junk = tmp_path / "junk.json"
    junk.write_text(json.dumps({"foo": 1}))
    with pytest.raises(ValueError, match="neither"):
        nr.load_numerics(str(junk))


def test_tpu_numerics_check_fails_off_tpu():
    """The on-chip kernel check checks nothing without a chip, and says so
    with a non-zero exit — never a SKIP that reads as a pass."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "tpu_numerics_check.py")],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert "nothing was checked" in proc.stderr
    assert "PASS" not in proc.stdout


def test_multichip_num_record_gates_itself():
    """The committed record must pass its own run_compare gate (the PR
    driver diffs a fresh run against this file with --check)."""
    path = ROOT / "MULTICHIP_NUM_r01.json"
    assert path.exists(), "MULTICHIP_NUM_r01.json not committed"
    rec = json.loads(path.read_text())
    assert rec["metric"] == "num_grad_norm_rel_err"
    grp = rec["num"]
    assert grp["num_grad_norm_rel_err"] <= 1e-6
    assert grp["num_monitor_overhead"] < 1.5
    assert grp["config"]["every_n"] == 10
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "run_compare.py"),
         str(path), str(path), "--check"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "REGRESSION" not in proc.stdout


# ------------------------------------------------ what the cadence consists of
def test_monitor_overhead_amortized_under_ten_percent(monkeypatch):
    """What amortises the monitor at every_n=10, counted and not timed (a
    ratio of two wall-clock times on a shared CPU fails when the machine is
    busy): of ten steps one dispatches the monitored program and nine the
    plain one, the sampled step makes the run's only device-to-host fetch,
    and the monitored program is built and traced once."""
    import jax

    every_n, steps = 10, 30
    monkeypatch.setenv("MXNET_MONITOR", "%d:grad,update" % every_n)
    num.reset()
    ts, p, s, a = _train_step()
    batch = _batch()
    counts = {"plain": 0, "monitored": 0, "built": 0, "fetch": 0}

    def counted(fn, name):
        def call(*args, **kw):
            counts[name] += 1
            return fn(*args, **kw)
        return call

    plain = ts._step
    ts._step = counted(plain, "plain")
    build = ts._monitored_step

    def monitored_step():
        before = len(ts._mon_cache)
        fn = build()
        counts["built"] += len(ts._mon_cache) - before
        return counted(fn, "monitored")
    ts._monitored_step = monitored_step
    monkeypatch.setattr(jax, "device_get",
                        counted(jax.device_get, "fetch"))
    for _ in range(steps):
        p, s, a, _ = ts(p, s, a, batch)
    jax.block_until_ready(p)

    assert counts == {"plain": steps - steps // every_n,
                      "monitored": steps // every_n, "built": 1,
                      "fetch": steps // every_n}
    assert len(num.history()) == steps // every_n     # cadence held
    (program,) = ts._mon_cache.values()
    assert program._cache_size() == 1 and plain._cache_size() == 1
