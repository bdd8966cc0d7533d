"""Live performance sentinel tests: spec parsing + arm/disarm symmetry,
rolling-baseline anomaly detection (quarantined folds, recompile
suppression, warn vs raise), the cross-rank straggler naming function,
per-program HBM attribution (capture vs jax's own memory_analysis,
tools/hbm_report.py, the run_compare hbm gate), the OOM post-mortem
bundle, and diagnose --json."""
import glob
import importlib.util
import json
import math
import os
from pathlib import Path

import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401  (registers ops; sentinel autostarts)
from mxnet_tpu import diagnostics as dg
from mxnet_tpu import sanitize as san
from mxnet_tpu import sentinel as sen
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError

ROOT = Path(__file__).resolve().parents[3]


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch, tmp_path):
    """The sentinel, HBM ledger and telemetry are process-global: every
    test starts and ends disarmed.  Diagnostics bundles default to the
    cwd, so any test that fires an anomaly without pinning
    ``MXNET_DIAG_DIR`` would litter the repo root — pin it here."""
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))
    sen.disarm()
    tel.stop()
    tel.reset()
    yield
    sen.disarm()
    tel.stop()
    tel.reset()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "tools" / ("%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _feed(n, step_s, data_wait_s=0.01, compute_s=None):
    """Feed n synthetic step closes; compute defaults to the remainder."""
    for _ in range(n):
        sen.step_close(step_s, data_wait_s,
                       compute_s if compute_s is not None
                       else step_s - data_wait_s, epoch=0, nbatch=_)


# ----------------------------------------------------------- spec + arming
def test_parse_spec_grammar():
    assert sen._parse_spec("step:3sigma") == (3.0, False, "warn")
    assert sen._parse_spec("step:2.5sigma:raise") == (2.5, False, "raise")
    assert sen._parse_spec("step:3sigma,hbm") == (3.0, True, "warn")
    assert sen._parse_spec("hbm") == (None, True, "warn")
    assert sen._parse_spec("step") == (3.0, False, "warn")
    assert sen._parse_spec("step:4sigma,hbm:warn") == (4.0, True, "warn")
    for bad in ("step:zsigma", "step:-1sigma", "bogus", "step:0sigma"):
        with pytest.raises(MXNetError):
            sen._parse_spec(bad)


def test_arm_disarm_symmetry():
    assert sen.arm("step:3sigma") is True
    assert sen.armed() and sen._detect
    assert san._hbm_on is True          # attribution rides any armed spec
    assert tel.flight_recorder_armed()  # self-contained anomaly bundles
    sen.disarm()
    assert not sen.armed() and not sen._detect
    assert san._hbm_on is False and san.hbm_ledger() == {}
    assert not tel.flight_recorder_armed()
    assert sen.anatomy() is None and sen.digest() is None


def test_arm_hbm_only_disables_detection():
    assert sen.arm("hbm") is True
    assert sen.armed() and not sen._detect
    assert san._hbm_on is True
    # detection entry points are inert: no baseline accrues
    _feed(5, 0.1)
    assert sen._steps == 0 and sen.digest() is None


def test_arm_respects_live_telemetry():
    tel.start()
    assert sen.arm("step:3sigma") is True
    # telemetry already records; the sentinel must not force the ring on
    assert sen._armed_fr is False
    sen.disarm()


# ------------------------------------------------------- anomaly detection
def _arm_fast(monkeypatch, spec="step:3sigma", **knobs):
    """Arm with a short warmup/trigger so tests stay fast."""
    monkeypatch.setenv("MXNET_SENTINEL_WARMUP", str(knobs.get("warmup", 4)))
    monkeypatch.setenv("MXNET_SENTINEL_CONSEC", str(knobs.get("consec", 3)))
    assert sen.arm(spec) is True


def test_anomaly_fires_and_names_phase(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))
    _arm_fast(monkeypatch)
    _feed(8, 0.1)
    # sustained 2x slowdown, all of it in data_wait
    with pytest.warns(sen.SentinelWarning, match="dominant divergent "
                      "phase 'data_wait'"):
        for i in range(3):
            sen.step_close(0.2, 0.11, 0.09, epoch=1, nbatch=i)
    an = sen.last_anomaly()
    assert an is not None and an["phase"] == "data_wait"
    assert an["consecutive"] == 3
    assert an["zscores"]["step"] > 3.0
    assert an["anatomy"]["step"] == pytest.approx(0.2)
    # the bundle is self-contained: the anomaly verdict, the sentinel
    # section (baseline + last step anatomy) and the flight-recorder ring
    (bundle_path,) = glob.glob(str(tmp_path / "mxtpu_diag.perf_anomaly*"))
    doc = json.loads(open(bundle_path).read())
    assert doc["reason"] == "perf_anomaly"
    assert doc["extra"]["perf_anomaly"]["phase"] == "data_wait"
    assert doc["extra"]["perf_anomaly"]["anatomy"]["step"] == \
        pytest.approx(0.2)
    assert "flight_recorder" in doc
    assert doc["sentinel"]["last_step"]["step"] == pytest.approx(0.2)
    assert "step" in doc["sentinel"]["anatomy"]["series"]


def test_quarantined_fold_keeps_baseline_clean(monkeypatch):
    """Over-threshold samples must NOT fold into the EWM baseline before
    the anomaly fires — a sustained slowdown folding itself in inflates
    the variance and dodges the K-consecutive trigger (the bug the
    quarantine exists for)."""
    _arm_fast(monkeypatch, consec=5)
    _feed(10, 0.1)
    base_before = sen.anatomy()["series"]["step"]["mean"]
    with pytest.warns(sen.SentinelWarning):
        _feed(5, 0.2)
    # the five anomalous samples were quarantined: baseline still ~0.1
    base_after = sen.anatomy()["series"]["step"]["mean"]
    assert base_after == pytest.approx(base_before, rel=0.01)
    an = sen.last_anomaly()
    # z stayed huge on every sample — the un-poisoned baseline held
    assert an["zscores"]["step"] > 10
    # post-fire quiet window folds unconditionally: the baseline starts
    # converging toward the new level instead of firing forever
    _feed(sen._warmup, 0.2)
    assert sen.anatomy()["series"]["step"]["mean"] > base_before * 1.05


def test_warmup_seed_is_robust_to_compile_outlier(monkeypatch):
    """The first fit step carries the XLA compile (often 100x the steady
    step).  The warmup window seeds the baseline from its median + MAD,
    so that outlier must leave no trace — and the post-warmup detector
    must fire off the CLEAN baseline, not a compile-inflated one."""
    _arm_fast(monkeypatch, warmup=6, consec=2)
    sen.step_close(3.0, 0.001, 2.999)      # the compile step
    _feed(5, 0.1)
    base = sen.anatomy()["series"]["step"]
    assert base["mean"] == pytest.approx(0.1, rel=0.01)
    assert base["sigma"] < 0.05            # the 3 s sample left no spread
    # digests carry the robust mean too — a fresh peer comparing against
    # this rank sees 100 ms, not a compile-poisoned seconds-scale mean
    assert sen.digest()["step"] == pytest.approx(0.1, rel=0.01)
    with pytest.warns(sen.SentinelWarning):
        _feed(2, 0.2)


def test_one_slow_step_is_noise(monkeypatch):
    _arm_fast(monkeypatch, consec=3)
    _feed(8, 0.1)
    sen.step_close(0.3, 0.01, 0.29)     # one glitch
    _feed(8, 0.1)                       # back to normal
    assert sen.last_anomaly() is None and sen._anomalies == 0


def test_note_recompile_suppresses(monkeypatch):
    """A declared recompile wave (sanitize.expect_recompile) re-opens the
    warmup window: the slow re-trace steps never fire."""
    _arm_fast(monkeypatch, warmup=4, consec=2)
    _feed(8, 0.1)
    san.expect_recompile("test-resize")
    _feed(4, 0.5)                       # slow wave inside the quiet window
    assert sen.last_anomaly() is None
    an = sen.anatomy()
    assert an["anomalies"] == 0


def test_raise_mode(monkeypatch):
    _arm_fast(monkeypatch, spec="step:3sigma:raise", consec=2)
    _feed(8, 0.1)
    with pytest.raises(sen.SentinelError, match="sigma over the rolling"):
        _feed(2, 0.3)


def test_anomaly_emits_telemetry_event(monkeypatch):
    tel.start()
    _arm_fast(monkeypatch, consec=2)
    _feed(8, 0.1)
    with pytest.warns(sen.SentinelWarning):
        _feed(2, 0.25)
    c = tel.counters()
    assert c.get("perf_anomaly[phase=compute]", c.get(
        "perf_anomaly[phase=data_wait]", 0)) >= 1 \
        or any(k.startswith("perf_anomaly") for k in c)
    assert any(k.startswith("perf_anomaly_zscore") for k in tel.gauges())


def test_autostart_variants(monkeypatch):
    monkeypatch.setenv("MXNET_SENTINEL", "step:2sigma:raise")
    assert sen._autostart() is True
    assert sen._mode == "raise" and sen._k_sigma == 2.0
    sen.disarm()
    monkeypatch.setenv("MXNET_SENTINEL", "nonsense")
    with pytest.warns(UserWarning, match="sentinel disabled"):
        assert sen._autostart() is False
    assert not sen.armed()
    monkeypatch.delenv("MXNET_SENTINEL")
    assert sen._autostart() is False


# --------------------------------------------------------- straggler naming
def _digest(step, data_wait=0.01, compute=None, stall=0.0):
    return {"steps": 30, "step": step, "data_wait": data_wait,
            "compute": compute if compute is not None else step - data_wait,
            "comm_mb": 12.5, "stall": stall}


def test_name_straggler_names_rank_and_phase():
    digests = {0: _digest(0.10), 1: _digest(0.10),
               2: _digest(0.30, data_wait=0.21)}
    rank, phase, slowdown = sen.name_straggler(digests)
    assert rank == 2 and phase == "data_wait"
    assert slowdown == pytest.approx(3.0)


def test_name_straggler_compute_bound():
    digests = {0: _digest(0.10), 1: _digest(0.14, compute=0.13)}
    rank, phase, slowdown = sen.name_straggler(digests)
    assert rank == 1 and phase == "compute"
    assert slowdown == pytest.approx(1.4)


def test_name_straggler_lockstep_attributes_self_phase():
    """A synchronous fit equalises step totals (every rank blocks in the
    collective for the slowest) and parks the absorbed wait in the
    WAITING ranks' compute — so with flat totals the verdict must come
    from the self-attributable host phases, naming the rank whose
    data_wait excess explains the inflated fleet step."""
    digests = {
        0: {"steps": 30, "step": 0.160, "data_wait": 0.001,
            "compute": 0.158, "stall": 0.001},     # absorbs the wait
        1: {"steps": 30, "step": 0.161, "data_wait": 0.061,
            "compute": 0.099, "stall": 0.001},     # the real straggler
    }
    rank, phase, slowdown = sen.name_straggler(digests)
    assert rank == 1 and phase == "data_wait"
    # slowdown = the step inflation the excess explains, not the ~1.0
    # total ratio lockstep pins it to
    assert slowdown == pytest.approx(1.0 + 0.060 / 0.160, rel=0.01)
    # compute excess alone (the absorbed wait on rank 0) must NOT name
    # rank 0: strip rank 1's data_wait signal and the verdict dissolves
    flat = {r: dict(d, data_wait=0.001) for r, d in digests.items()}
    assert sen.name_straggler(flat) is None


def test_name_straggler_lockstep_noise_floor():
    """Flat totals + sub-floor self-phase jitter is a healthy fleet, not
    a straggler — no verdict."""
    digests = {0: _digest(0.100, data_wait=0.010),
               1: _digest(0.101, data_wait=0.012)}
    assert sen.name_straggler(digests) is None


def test_name_straggler_degenerate_inputs():
    assert sen.name_straggler({}) is None
    assert sen.name_straggler({0: _digest(0.1)}) is None
    assert sen.name_straggler({0: None, 1: _digest(0.1)}) is None
    # zero peer median can't divide
    assert sen.name_straggler({0: {"step": 0.0}, 1: {"step": 0.1}}) is None


def test_digest_roundtrip(monkeypatch):
    _arm_fast(monkeypatch)
    assert sen.digest() is None          # pre-first-step
    _feed(6, 0.1)
    d = sen.digest()
    assert d["steps"] == 6
    assert d["step"] == pytest.approx(0.1, rel=0.01)
    json.dumps(d)                        # KV-exchange payload is JSON-safe


# ------------------------------------------------------- HBM attribution
def test_hbm_capture_matches_memory_analysis():
    """The ledger's numbers ARE jax's: capture on a pinned f32 program
    agrees byte-for-byte with a direct memory_analysis() call."""
    import jax
    import jax.numpy as jnp
    san.hbm_arm()
    try:
        fn = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((64, 64), jnp.float32)
        row = san.hbm_capture("pinned", fn, (x,))
        assert row is not None
        assert row["args"] == 64 * 64 * 4
        ms = fn.lower(x).compile().memory_analysis()
        assert row["args"] == int(ms.argument_size_in_bytes)
        assert row["outputs"] == int(ms.output_size_in_bytes)
        assert row["temps"] == int(ms.temp_size_in_bytes)
        assert row["total"] == (row["args"] + row["outputs"] + row["temps"]
                                + row["generated_code"] - row["alias"])
        assert san.hbm_ledger()["pinned"] == row
    finally:
        san.hbm_disarm()


def test_hbm_capture_disarmed_and_degraded():
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda x: x + 1)
    x = jnp.ones((4,), jnp.float32)
    assert san.hbm_capture("off", fn, (x,)) is None     # disarmed: no-op
    assert san.hbm_ledger() == {}
    san.hbm_arm()
    try:
        # a non-lowerable callable degrades to silent None, never an error
        assert san.hbm_capture("bad", lambda x: x, (x,)) is None
        assert "bad" not in san.hbm_ledger()
        assert san.hbm_wrap("w", lambda: 0)() == 0      # wrapper still calls
    finally:
        san.hbm_disarm()


def test_hbm_report_agrees_with_ledger(tmp_path, capsys):
    import jax
    import jax.numpy as jnp
    hr = _load_tool("hbm_report")
    san.hbm_arm()
    try:
        x = jnp.ones((64, 64), jnp.float32)
        san.hbm_capture("big", jax.jit(lambda x: x @ x), (x,))
        san.hbm_capture("small", jax.jit(lambda x: x.sum()), (x,))
        ledger = san.hbm_ledger()
    finally:
        san.hbm_disarm()
    path = tmp_path / "ledger.json"
    path.write_text(json.dumps(ledger))
    summary = hr.summarize(hr.load_ledger(str(path)))
    # rows sort by resident total, descending: the matmul holds more
    assert [n for n, _ in summary["programs"]][0] == "big"
    assert summary["totals"]["args"] == sum(
        r["args"] for r in ledger.values())
    assert hr.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "Per-program HBM attribution (2 program(s))" in out
    assert "TOTAL" in out
    assert hr.main([str(path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["programs"][0]["name"] == "big"
    assert doc["totals"] == summary["totals"]
    # error paths: not a ledger, bundle without an hbm section
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"type": "mxtpu_diagnostics"}))
    assert hr.main([str(bad)]) == 1
    assert "hbm" in capsys.readouterr().err


def test_fused_fit_populates_ledger_and_diag_section(monkeypatch):
    """An armed fused fit leaves per-program rows in the ledger, and the
    diagnostics bundle grows matching sentinel/hbm sections."""
    assert sen.arm("step:3sigma") is True
    x = np.random.RandomState(0).rand(32, 6).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 4, 32).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=8)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.Module(net, context=mx.cpu(),
                    data_names=("data",), label_names=("softmax_label",))
    mod.fit(it, num_epoch=2, optimizer_params={"learning_rate": 0.1})
    ledger = san.hbm_ledger()
    ts_rows = [k for k in ledger if k.startswith("train_step")]
    assert ts_rows, ledger
    for row in ledger.values():
        # a constant-producing op program (op._zeros) legitimately has
        # zero argument bytes — but every program holds SOMETHING
        assert row["total"] > 0
    assert ledger[ts_rows[0]]["args"] > 0
    # the fit fed the sentinel: a baseline exists and digests are live
    assert sen._steps > 0
    assert sen.digest()["step"] > 0
    doc = dg.snapshot("probe")
    assert doc["hbm"] == ledger
    assert doc["sentinel"]["anatomy"]["steps"] == sen._steps
    assert doc["sentinel"]["straggler"] is None     # single process


def test_oom_writes_post_mortem_bundle(monkeypatch, tmp_path):
    """A RESOURCE_EXHAUSTED escaping the fused step dumps an `oom` bundle
    (with the HBM ledger inside) before re-raising untouched."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.module.module import _FusedFit
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))
    assert sen.arm("hbm") is True
    san.hbm_capture("resident", jax.jit(lambda x: x * 2),
                    (jnp.ones((8, 8), jnp.float32),))
    ff = object.__new__(_FusedFit)

    def boom(*args):
        raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating "
                           "1073741824 bytes")
    ff._ts = boom
    ff._params = ff._state = ff._aux = {}

    class _Batch:
        _staged = {"data": None}

    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        ff.step(_Batch())
    (bundle_path,) = glob.glob(str(tmp_path / "mxtpu_diag.oom.*"))
    doc = json.loads(open(bundle_path).read())
    assert doc["reason"] == "oom"
    assert "RESOURCE_EXHAUSTED" in doc["exception"]["message"]
    assert doc["hbm"]["resident"]["args"] == 8 * 8 * 4
    # the same bundle feeds the report tool directly
    hr = _load_tool("hbm_report")
    assert hr.load_ledger(bundle_path) == doc["hbm"]
    # a non-OOM exception with nothing armed writes nothing
    sen.disarm()
    monkeypatch.delenv("MXNET_DIAG_DIR")
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        ff.step(_Batch())
    assert glob.glob(str(tmp_path / "mxtpu_diag.oom.*")) == [bundle_path]


# ------------------------------------------------------ run_compare hbm gate
def test_run_compare_gates_hbm_regression(tmp_path):
    """run_compare ingests the dryrun's `hbm` block: resident bytes gate
    through the hbm_bytes down-hint, the config block is identity, and
    the committed MULTICHIP_HBM_r01.json self-compares rc=0."""
    from tools import run_compare as rc

    def record(step_mb, zero_mb, devices=8):
        return {"metric": "hbm_bytes_step_total_mb", "value": step_mb,
                "unit": "mb",
                "hbm": {"hbm_bytes_step_total_mb": step_mb,
                        "hbm_bytes_zero_total_mb": zero_mb,
                        "config": {"devices": devices,
                                   "per_device_batch": 2}}}

    base = tmp_path / "a.json"
    base.write_text(json.dumps(record(500.0, 420.0)))
    same = tmp_path / "b.json"
    same.write_text(json.dumps(record(500.0, 420.0)))
    worse = tmp_path / "c.json"
    worse.write_text(json.dumps(record(750.0, 420.0)))
    other = tmp_path / "d.json"
    other.write_text(json.dumps(record(500.0, 420.0), ).replace(
        '"devices": 8', '"devices": 4'))
    assert rc.main([str(base), str(same), "--check"]) == 0
    # resident bytes going UP is a REGRESSION (the hbm_bytes down-hint)
    assert rc.main([str(base), str(worse), "--check"]) == 2
    # a different mesh is a different experiment, not a regression pair
    assert rc.main([str(base), str(other), "--check"]) == 0
    run = rc.load_run(str(base))
    assert run.bench["hbm_bytes_step_total_mb"] == pytest.approx(500.0)
    assert "config" not in run.bench
    committed = ROOT / "MULTICHIP_HBM_r01.json"
    assert committed.exists(), "committed hbm record missing"
    assert rc.main([str(committed), str(committed), "--check"]) == 0
    rec = rc.load_run(str(committed))
    assert rec.bench["hbm_bytes_step_total_mb"] > 0
    # ZeRO sheds resident bytes — pinned in the committed record too
    assert rec.bench["hbm_bytes_zero_args_mb"] < \
        rec.bench["hbm_bytes_step_args_mb"]


# ------------------------------------------------------------ diagnose --json
def test_diagnose_json_and_sentinel_sections(monkeypatch, tmp_path, capsys):
    diagnose = _load_tool("diagnose")
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))
    _arm_fast(monkeypatch)
    _feed(8, 0.1)
    with pytest.warns(sen.SentinelWarning):
        _feed(3, 0.2)
    (bundle_path,) = glob.glob(str(tmp_path / "mxtpu_diag.perf_anomaly*"))
    # rendered view names the sentinel sections
    assert diagnose.main([bundle_path]) == 0
    out = capsys.readouterr().out
    assert "Live sentinel" in out
    assert "ANOMALY" in out
    # --json round-trips the validated bundle as one machine document
    assert diagnose.main([bundle_path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["reason"] == "perf_anomaly"
    assert doc["sentinel"]["last_step"]["step"] == pytest.approx(0.2)
    assert doc["extra"]["perf_anomaly"]["phase"] in sen.PHASES
