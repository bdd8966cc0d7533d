"""Per-program cost-attribution tests: roofline peak resolution
(mxnet_tpu/cost.py), the capture-at-compile cost ledger + compile-seconds
accounting (sanitize), the sentinel's inverted MFU series, the fused
fit's MFU gauges + diagnostics `cost` section, tools/cost_report.py, the
run_compare cost gate, and the tools/*.py --help smoke test."""
import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401  (registers ops)
from mxnet_tpu import cost
from mxnet_tpu import diagnostics as dg
from mxnet_tpu import sanitize as san
from mxnet_tpu import sentinel as sen
from mxnet_tpu import telemetry as tel

ROOT = Path(__file__).resolve().parents[3]


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch, tmp_path):
    """Sentinel/ledgers/telemetry are process-global; the resolved peak
    pair is cached module-global.  Start and end every test disarmed
    with the peak cache dropped (so a monkeypatched env never leaks)."""
    monkeypatch.setenv("MXNET_DIAG_DIR", str(tmp_path))
    cost._cache = None
    sen.disarm()
    san.cost_disarm()
    tel.stop()
    tel.reset()
    yield
    sen.disarm()
    san.cost_disarm()
    tel.stop()
    tel.reset()
    cost._cache = None


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "tools" / ("%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- roofline peaks
def test_parse_rate_grammar():
    assert cost._parse_rate("275e12") == pytest.approx(275e12)
    assert cost._parse_rate("275T") == pytest.approx(275e12)
    assert cost._parse_rate("1228G") == pytest.approx(1228e9)
    assert cost._parse_rate(" 1.5p ") == pytest.approx(1.5e15)
    assert cost._parse_rate("819000M") == pytest.approx(819e9)
    for junk in (None, "", "fast", "-3T", "0", "T"):
        assert cost._parse_rate(junk) is None


def test_resolve_peaks_on_a_tpu_come_from_the_table_only(monkeypatch):
    """On a TPU the device-kind table decides: the kind jax reports for a
    v5e resolves, MXNET_PEAK_* cannot assert other peaks for a chip, and a
    kind the table does not know is an error, not a null MFU."""
    import jax
    from mxnet_tpu.base import MXNetError

    class Dev(object):
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "1T")
    monkeypatch.setattr(jax, "devices", lambda: [Dev("TPU v5 lite")])
    assert cost.resolve_peaks(refresh=True) == (197e12, 819e9)
    monkeypatch.setattr(jax, "devices", lambda: [Dev("TPU v9 imaginary")])
    with pytest.raises(MXNetError, match="TPU v9 imaginary"):
        cost.resolve_peaks(refresh=True)


def test_resolve_peaks_env_precedence(monkeypatch):
    # unset + CPU backend: strict no-op — nothing resolves
    monkeypatch.delenv("MXNET_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("MXNET_PEAK_BW", raising=False)
    assert cost.resolve_peaks(refresh=True) == (None, None)
    assert not cost.enabled()
    assert cost.mfu(1e9, 0.1) is None
    assert cost.ridge() is None
    assert cost.verdict(10.0) is None
    # off a TPU the env arms it; either alone is honoured (MFU needs only
    # FLOPS)
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "100G")
    assert cost.resolve_peaks(refresh=True) == (pytest.approx(100e9), None)
    assert cost.enabled()
    assert cost.ridge() is None
    monkeypatch.setenv("MXNET_PEAK_BW", "10G")
    assert cost.resolve_peaks(refresh=True) == (
        pytest.approx(100e9), pytest.approx(10e9))
    # cache: a later env change is invisible until refresh
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "200G")
    assert cost.resolve_peaks()[0] == pytest.approx(100e9)
    assert cost.resolve_peaks(refresh=True)[0] == pytest.approx(200e9)


def test_mfu_ridge_verdict(monkeypatch):
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "100G")
    monkeypatch.setenv("MXNET_PEAK_BW", "10G")
    cost.resolve_peaks(refresh=True)
    # 50 GFLOP in one second on a 100 GFLOP/s chip: MFU 0.5
    assert cost.mfu(50e9, 1.0) == pytest.approx(0.5)
    assert cost.mfu(0, 1.0) is None
    assert cost.mfu(50e9, 0.0) is None
    assert cost.ridge() == pytest.approx(10.0)
    assert cost.verdict(10.0) == "compute-bound"
    assert cost.verdict(9.99) == "memory-bound"
    assert cost.verdict(None) is None


# ---------------------------------------------------------------- cost ledger
def test_cost_capture_matches_cost_analysis():
    """The ledger's numbers ARE jax's: capture on a pinned f32 program
    agrees with a direct cost_analysis() call."""
    import jax
    import jax.numpy as jnp
    san.cost_arm()
    try:
        fn = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((64, 64), jnp.float32)
        out = san.program_capture("pinned", fn, (x,))
        assert out is not None and out["cost"] is not None
        row = out["cost"]
        props = san._cost_props(fn.lower(x).compile().cost_analysis())
        assert row["flops"] == int(props.get("flops", 0) or 0)
        assert row["bytes_accessed"] == int(
            props.get("bytes accessed", 0) or 0)
        # a 64x64 matmul costs 2*64^3 FLOPs plus the reduction
        assert row["flops"] >= 2 * 64 ** 3
        if row["bytes_accessed"]:
            assert row["intensity"] == pytest.approx(
                row["flops"] / row["bytes_accessed"], rel=1e-3)
        assert row["compile_seconds"] > 0
        assert san.cost_ledger()["pinned"] == row
    finally:
        san.cost_disarm()
    assert san.cost_ledger() == {}          # disarm clears


def test_capture_then_dispatch_compiles_once():
    """Arming attribution pays each compile once: the dispatch that follows
    a capture with the same arguments reuses the executable the capture
    compiled (donation included — the TrainStep case)."""
    import jax
    import jax.numpy as jnp
    from jax import monitoring
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)
    x = jnp.ones((32, 32), jnp.float32)
    y = jnp.ones((32, 32), jnp.float32)
    fn = jax.jit(lambda a, b: (a @ b, a + 1), donate_argnums=(0,))
    san.cost_arm()
    monitoring.register_event_duration_secs_listener(listen)
    try:
        assert san.program_capture("once", fn, (x, y))["cost"] is not None
        assert len(compiles) == 1
        jax.block_until_ready(fn(x, y))
        assert len(compiles) == 1, "the dispatch compiled the program again"
    finally:
        monitoring.unregister_event_duration_listener(listen)
        san.cost_disarm()


def test_cost_capture_disarmed_and_degraded(caplog):
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda x: x + 1)
    x = jnp.ones((4,), jnp.float32)
    assert san.program_capture("off", fn, (x,)) is None   # disarmed: no-op
    assert san.cost_ledger() == {}
    san.cost_arm()
    try:
        # a non-lowerable callable degrades to None, never an error — and
        # says so, because the ledger row will be missing
        with caplog.at_level("WARNING", logger="mxnet_tpu.sanitize"):
            assert san.program_capture("bad", lambda x: x, (x,)) is None
        assert "no HBM/cost row for program 'bad'" in caplog.text
        assert "bad" not in san.cost_ledger()
        assert san.program_wrap("w", lambda: 0)() == 0    # wrapper still calls
        # junk analysis objects degrade too
        assert san.cost_note("junk", None) is None
        assert san.cost_note("junk", []) is None
        assert "junk" not in san.cost_ledger()
    finally:
        san.cost_disarm()


def test_compile_seconds_accounting():
    """program_capture charges its compile to the cache handle; the
    per-cache totals surface in compile_seconds() and snapshot()."""
    import jax
    import jax.numpy as jnp
    h = san.register_cache("test_cost_cache_%d" % id(object()), kind="test")
    assert h.name not in san.compile_seconds()
    san.cost_arm()
    try:
        fn = jax.jit(lambda x: x * 2)
        san.program_capture("cached", fn, (jnp.ones((8,), jnp.float32),),
                            cache=h)
    finally:
        san.cost_disarm()
    comp = san.compile_seconds()
    assert comp[h.name] > 0
    assert comp["total"] >= comp[h.name]
    assert h.snapshot()["compile_seconds"] == comp[h.name]
    # explicit notes accumulate; junk is rejected by the caller contract
    h.compile_note(0.5)
    assert san.compile_seconds()[h.name] == pytest.approx(
        comp[h.name] + 0.5, abs=1e-6)
    san.reset()
    assert h.name not in san.compile_seconds()


# ------------------------------------------------------- sentinel MFU series
def test_sentinel_mfu_series_joins_baseline(monkeypatch):
    monkeypatch.setenv("MXNET_SENTINEL_WARMUP", "4")
    monkeypatch.setenv("MXNET_SENTINEL_CONSEC", "3")
    assert sen.arm("step:3sigma") is True
    for i in range(6):
        sen.step_close(0.1, 0.01, 0.09, epoch=0, nbatch=i, mfu=0.5)
    an = sen.anatomy()
    assert an["series"]["mfu"]["mean"] == pytest.approx(0.5, rel=0.01)
    d = sen.digest()
    assert d["mfu"] == pytest.approx(0.5, rel=0.01)
    json.dumps(d)
    # a fit without peaks never feeds mfu — the series simply stays absent
    sen.disarm()
    assert sen.arm("step:3sigma") is True
    for i in range(6):
        sen.step_close(0.1, 0.01, 0.09, epoch=0, nbatch=i)
    assert "mfu" not in sen.anatomy()["series"]
    assert "mfu" not in sen.digest()


def test_sentinel_mfu_inverted_z_names_dominant_phase(monkeypatch):
    """Utilization FALLING scores positive (inverted z) and can be the
    named dominant phase of a step-time anomaly."""
    monkeypatch.setenv("MXNET_SENTINEL_WARMUP", "4")
    monkeypatch.setenv("MXNET_SENTINEL_CONSEC", "3")
    assert sen.arm("step:3sigma") is True
    # jittered warmup so step/compute sigmas are real (not the floor),
    # while the constant-mfu baseline keeps only its 5% relative floor
    for i, c in enumerate((0.08, 0.09, 0.10, 0.11, 0.09, 0.10)):
        sen.step_close(0.01 + c, 0.01, c, epoch=0, nbatch=i, mfu=0.5)
    with pytest.warns(sen.SentinelWarning, match="mfu"):
        for i in range(3):
            # 2x step, all of it in compute — but utilization cratered
            # 16 sigma, farther than any time-phase moved
            sen.step_close(0.20, 0.01, 0.19, epoch=0, nbatch=10 + i,
                           mfu=0.1)
    assert sen._last_anomaly["phase"] == "mfu"
    assert sen._last_anomaly["zscores"]["mfu"] > 3
    assert sen._last_anomaly["baseline"]["mfu"]["mean"] == pytest.approx(
        0.5, rel=0.01)


# --------------------------------------------------- fused fit: MFU end-to-end
def test_fused_fit_mfu_gauges_and_cost_section(monkeypatch):
    """With peaks configured, an armed fused fit captures the step's
    cost, emits model_flops/mfu gauges, and the diagnostics bundle grows
    a `cost` section with the resolved peaks."""
    # peaks scaled to the toy model so its MFU lands in (0, 1) — a 1T
    # peak would round the gauge's 4 decimals to 0.0
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "100M")
    monkeypatch.setenv("MXNET_PEAK_BW", "100G")
    cost.resolve_peaks(refresh=True)
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
    ledger = san.cost_ledger()
    ts_rows = [k for k in ledger if k.startswith("train_step")]
    assert ts_rows, ledger
    assert ledger[ts_rows[0]]["flops"] > 0
    g = tel.gauges()
    assert g.get("model_flops", 0) > 0
    assert g.get("mfu") is not None and 0 < g["mfu"] < 1
    assert g.get("achieved_flops", 0) > 0
    # the sentinel's baseline watched the same series
    assert "mfu" in sen.anatomy()["series"]
    doc = dg.snapshot("probe")
    assert doc["cost"]["programs"] == ledger
    assert doc["cost"]["peaks"]["flops_per_sec"] == pytest.approx(100e6)
    assert doc["cost"]["compile_seconds"].get("total", 0) > 0


def test_fused_fit_without_peaks_stays_dark(monkeypatch):
    """No peaks -> no cost arming, no mfu gauge, no mfu series: the
    strict no-op contract holds even for an armed sentinel fit."""
    monkeypatch.delenv("MXNET_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("MXNET_PEAK_BW", raising=False)
    cost.resolve_peaks(refresh=True)
    assert sen.arm("step:3sigma") is True
    x = np.random.RandomState(0).rand(16, 6).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 4, 16).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=8)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.Module(net, context=mx.cpu(),
                    data_names=("data",), label_names=("softmax_label",))
    mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
    assert "mfu" not in tel.gauges()
    assert "mfu" not in (sen.anatomy() or {"series": {}})["series"]


# ------------------------------------------------------------ tools/cost_report
def test_cost_report_agrees_with_ledger(tmp_path, capsys):
    import jax
    import jax.numpy as jnp
    cr = _load_tool("cost_report")
    san.cost_arm()
    try:
        x = jnp.ones((64, 64), jnp.float32)
        san.program_capture("big", jax.jit(lambda x: x @ x), (x,))
        san.program_capture("small", jax.jit(lambda x: x.sum()), (x,))
        ledger = san.cost_ledger()
    finally:
        san.cost_disarm()
    path = tmp_path / "ledger.json"
    path.write_text(json.dumps(ledger))
    summary = cr.summarize(cr.load_cost(str(path)),
                           peak_flops=100e9, peak_bw=10e9)
    # rows sort by FLOPs, descending: the matmul costs more
    assert [n for n, _ in summary["programs"]][0] == "big"
    assert summary["totals"]["flops"] == sum(
        r["flops"] for r in ledger.values())
    assert summary["ridge"] == pytest.approx(10.0)
    for _, row in summary["programs"]:
        want = "compute" if row["intensity"] >= 10.0 else "memory"
        assert row["verdict"] == want
    assert cr.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "Per-program cost attribution (2 program(s))" in out
    assert "TOTAL" in out
    assert cr.main([str(path), "--json", "--peak-flops", "100G",
                    "--peak-bw", "10G"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["programs"][0]["name"] == "big"
    assert doc["ridge"] == pytest.approx(10.0)
    assert doc["totals"] == summary["totals"]


def test_cost_report_curated_errors(tmp_path, capsys):
    """A bundle with no cost section exits 1 with ONE human line on
    stderr — never a traceback (same contract as hbm_report)."""
    cr = _load_tool("cost_report")
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"type": "mxtpu_diagnostics"}))
    assert cr.main([str(bad)]) == 1
    err = capsys.readouterr().err
    assert err.startswith("cost_report: ")
    assert "no 'cost' section" in err
    assert len(err.strip().splitlines()) == 1
    junk = tmp_path / "junk.json"
    junk.write_text(json.dumps({"a": 1}))
    assert cr.main([str(junk)]) == 1
    assert "neither" in capsys.readouterr().err
    assert cr.main([str(tmp_path / "missing.json")]) == 1
    assert capsys.readouterr().err.startswith("cost_report: ")


def test_cost_report_reads_diag_bundle(monkeypatch, tmp_path):
    """The fused fit's bundle feeds the report tool directly, peaks and
    compile seconds included."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "1T")
    cost.resolve_peaks(refresh=True)
    cr = _load_tool("cost_report")
    h = san.register_cache("test_bundle_cache_%d" % id(object()))
    san.cost_arm()
    try:
        san.program_capture("resident", jax.jit(lambda x: x * 2),
                            (jnp.ones((8, 8), jnp.float32),), cache=h)
        doc = dg.snapshot("probe")
    finally:
        san.cost_disarm()
    path = tmp_path / "bundle.json"
    path.write_text(json.dumps(doc))
    loaded = cr.load_cost(str(path))
    assert "resident" in loaded["programs"]
    assert loaded["peaks"]["flops_per_sec"] == pytest.approx(1e12)
    assert loaded["compile_seconds"][h.name] > 0


# ------------------------------------------------------ run_compare cost gate
def test_run_compare_gates_cost_block(tmp_path):
    """run_compare ingests the `cost` block: mfu gates through the up-
    hint (a DROP regresses), compile_sec through the down-hint (a RISE
    regresses), config is identity, and the committed
    MULTICHIP_COST_r01.json self-compares rc=0."""
    from tools import run_compare as rc

    def record(mfu, compile_sec, gflops=50.0, devices=8):
        return {"metric": "cost_step_gflops", "value": gflops,
                "unit": "gflops",
                "cost": {"cost_step_gflops": gflops, "mfu": mfu,
                         "compile_sec": compile_sec,
                         "config": {"devices": devices,
                                    "per_device_batch": 2}}}

    base = tmp_path / "a.json"
    base.write_text(json.dumps(record(0.40, 30.0)))
    same = tmp_path / "b.json"
    same.write_text(json.dumps(record(0.40, 30.0)))
    mfu_drop = tmp_path / "c.json"
    mfu_drop.write_text(json.dumps(record(0.20, 30.0)))
    slow_compile = tmp_path / "d.json"
    slow_compile.write_text(json.dumps(record(0.40, 60.0)))
    other_mesh = tmp_path / "e.json"
    other_mesh.write_text(json.dumps(record(0.40, 30.0, devices=4)))
    assert rc.main([str(base), str(same), "--check"]) == 0
    # utilization going DOWN is a REGRESSION (the mfu up-hint)
    assert rc.main([str(base), str(mfu_drop), "--check"]) == 2
    # compile seconds going UP is a REGRESSION (the compile_sec down-hint)
    assert rc.main([str(base), str(slow_compile), "--check"]) == 2
    # a different mesh is a different experiment, not a regression pair
    assert rc.main([str(base), str(other_mesh), "--check"]) == 0
    run = rc.load_run(str(base))
    assert run.bench["mfu"] == pytest.approx(0.40)
    assert run.bench["compile_sec"] == pytest.approx(30.0)
    assert "config" not in run.bench
    committed = ROOT / "MULTICHIP_COST_r01.json"
    assert committed.exists(), "committed cost record missing"
    assert rc.main([str(committed), str(committed), "--check"]) == 0


# --------------------------------------------------------- tools --help smoke
def test_every_tool_answers_help():
    """Every tools/*.py with a CLI must exit 0 on --help: catches an
    import-time crash or argparse typo in any tool without needing its
    input files.  Library-only siblings (no __main__ block) are skipped."""
    tools = sorted((ROOT / "tools").glob("*.py"))
    assert tools, "tools/ directory went missing?"
    ran = 0
    for path in tools:
        text = path.read_text()
        if "__main__" not in text or "argparse" not in text:
            # shared library module (ledger_table) or a bare script with
            # no CLI contract to smoke (tpu_numerics_check)
            continue
        proc = subprocess.run(
            [sys.executable, str(path), "--help"],
            capture_output=True, text=True, timeout=120,
            cwd=str(ROOT))
        assert proc.returncode == 0, (
            "%s --help exited %d:\n%s" % (path.name, proc.returncode,
                                          proc.stderr))
        assert "usage" in (proc.stdout + proc.stderr).lower(), path.name
        ran += 1
    assert ran >= 5, "expected a fleet of CLI tools, found %d" % ran
