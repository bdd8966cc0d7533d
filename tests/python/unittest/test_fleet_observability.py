"""Fleet observability tests: latency histograms (bucket/quantile accuracy,
span auto-feed, merge associativity), cross-rank aggregation + straggler
detection (tools/telemetry_agg.py, telemetry_report --ranks), the live
metrics endpoint (Prometheus + JSON, per-rank port offset, clean shutdown),
observability-env propagation in tools/launch.py, predictor/bench wiring,
and the everything-off zero-overhead guard."""
import importlib.util
import json
import os
import socket
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import metrics_server as ms
from mxnet_tpu import telemetry as tel

RS = np.random.RandomState
ROOT = Path(__file__).resolve().parents[3]


@pytest.fixture(autouse=True)
def _clean_state():
    """Telemetry and the endpoint are process-global: every test starts
    and ends with both off."""
    ms.stop_server()
    tel.stop()
    tel.reset()
    yield
    ms.stop_server()
    tel.stop()
    tel.reset()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "tools" / ("%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _small_net():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _http_get(port, path):
    with urllib.request.urlopen(
            "http://127.0.0.1:%d%s" % (port, path), timeout=5) as r:
        return r.read().decode()


# ---------------------------------------------------------------- histograms
def test_histogram_quantile_accuracy():
    tel.start()
    for v in range(1, 1001):
        tel.histogram("lat", float(v))
    h = tel.histograms()["lat"]
    assert h["count"] == 1000
    assert h["sum"] == pytest.approx(500500.0)
    assert h["min"] == 1.0 and h["max"] == 1000.0
    # 20 log buckets/decade ⇒ ~6% bucket resolution; interpolation lands
    # well inside 10% of the exact percentiles
    assert tel.quantile("lat", 0.50) == pytest.approx(500, rel=0.10)
    assert tel.quantile("lat", 0.90) == pytest.approx(900, rel=0.10)
    assert tel.quantile("lat", 0.99) == pytest.approx(990, rel=0.10)
    # tails clamp to the observed extremes
    assert tel.quantile("lat", 0.0) == 1.0
    assert tel.quantile("lat", 1.0) == 1000.0


def test_histogram_edge_cases():
    tel.start()
    assert tel.quantile("nope", 0.5) is None
    tel.histogram("one", 42.0)
    for q in (0.0, 0.5, 0.99, 1.0):
        assert tel.quantile("one", q) == pytest.approx(42.0)
    # non-positive and huge values land in the underflow/overflow buckets
    # without breaking anything
    tel.histogram("wild", 0.0)
    tel.histogram("wild", -3.0)
    tel.histogram("wild", 1e12)
    h = tel.histograms()["wild"]
    assert h["count"] == 3 and "inf" in h["buckets"]
    assert tel.quantile("wild", 1.0) == pytest.approx(1e12)


def test_span_close_feeds_histogram():
    tel.start()
    with tel.span("region", cat="unit"):
        pass
    tel.record_span("region", time.time(), 0.002)
    h = tel.histograms()["region"]
    assert h["count"] == 2
    assert h["max"] == pytest.approx(2000.0, rel=0.01)   # µs
    # no 'hist' events for span-fed updates — the span event carries the
    # raw duration already
    assert not any(e["type"] == "hist" for e in tel.events())


def test_summary_event_embeds_histograms(tmp_path):
    fname = str(tmp_path / "t.jsonl")
    tel.start(fname)
    tel.histogram("h", 123.0, kind="explicit")
    tel.stop()
    events = [json.loads(line) for line in open(fname) if line.strip()]
    (hist_ev,) = [e for e in events if e["type"] == "hist"]
    assert hist_ev["value"] == 123.0 and hist_ev["tags"] == {
        "kind": "explicit"}
    (summary,) = [e for e in events if e["type"] == "summary"]
    h = summary["histograms"]["h"]
    assert h["count"] == 1 and h["sum"] == 123.0
    assert sum(h["buckets"].values()) == 1


def test_agg_quantile_matches_telemetry():
    """tools/telemetry_agg.py carries a stdlib copy of quantile_from_hist;
    this holds the two implementations in lockstep."""
    agg = _load_tool("telemetry_agg")
    tel.start()
    rng = RS(7)
    for v in 10.0 ** (rng.uniform(-2, 7, 500)):
        tel.histogram("x", float(v))
    h = tel.histograms()["x"]
    for q in (0.0, 0.1, 0.5, 0.9, 0.99, 1.0):
        assert agg.quantile_from_hist(h, q) == tel.quantile_from_hist(h, q)


def test_histogram_merge_associativity():
    agg = _load_tool("telemetry_agg")
    rng = RS(3)
    exports, all_vals = [], []
    for _ in range(3):
        vals = [float(v) for v in rng.randint(1, 100000, 200)]
        all_vals += vals
        tel.start()
        for v in vals:
            tel.histogram("m", v)
        exports.append(tel.histograms()["m"])
        tel.stop()
    ab_c = agg.merge_histograms(
        agg.merge_histograms(exports[0], exports[1]), exports[2])
    a_bc = agg.merge_histograms(
        exports[0], agg.merge_histograms(exports[1], exports[2]))
    assert ab_c == a_bc   # integer-valued observations ⇒ exact equality
    assert ab_c["count"] == 600
    assert ab_c["min"] == min(all_vals) and ab_c["max"] == max(all_vals)
    assert sum(ab_c["buckets"].values()) == 600
    got = agg.quantile_from_hist(ab_c, 0.5)
    assert got == pytest.approx(float(np.percentile(all_vals, 50)), rel=0.1)


# ------------------------------------------------- cross-rank agg + straggler
def _write_rank_files(base, rank_step_ms, nsteps=40):
    """Synthetic per-rank telemetry files with controlled span latencies."""
    for rank, step_ms in rank_step_ms.items():
        tel.start("%s.rank%d" % (base, rank))
        t = time.time()
        for i in range(nsteps):
            tel.record_span("step", t, step_ms / 1e3, cat="step",
                            epoch=0, nbatch=i)
            tel.record_span("dist.allreduce", t, step_ms / 4e3, cat="comm",
                            rank=rank)
        tel.counter("fit_samples", nsteps * 10)
        tel.gauge("epoch_time", step_ms * nsteps / 1e3)
        tel.stop()


def test_straggler_detection_flags_slow_rank(tmp_path):
    agg = _load_tool("telemetry_agg")
    base = str(tmp_path / "t.jsonl")
    _write_rank_files(base, {0: 10.0, 1: 10.0, 2: 31.0})
    files = agg.rank_files(base)
    assert [agg.rank_of(p) for p in files] == [0, 1, 2]
    merged = agg.aggregate(files)
    # counters summed, gauges per-rank
    assert merged["counters"]["fit_samples"] == 3 * 400
    assert set(merged["gauges_by_rank"]) == {0, 1, 2}
    # bucket-merged histogram covers all ranks
    assert merged["histograms"]["step"]["count"] == 120
    rep = merged["skew"]["step"]
    assert rep["slowest_rank"] == 2
    assert rep["straggler"] == 2
    assert rep["skew_ratio"] == pytest.approx(3.1, rel=0.05)
    assert rep["ranks"][2]["p99"] == pytest.approx(31000.0, rel=0.01)
    assert merged["skew"]["dist.allreduce"]["straggler"] == 2


def test_no_straggler_when_ranks_agree(tmp_path):
    agg = _load_tool("telemetry_agg")
    base = str(tmp_path / "t.jsonl")
    _write_rank_files(base, {0: 10.0, 1: 10.5})
    merged = agg.aggregate(agg.rank_files(base))
    rep = merged["skew"]["step"]
    assert rep["straggler"] is None
    assert rep["slowest_rank"] == 1


def test_agg_cli_and_report_ranks(tmp_path, capsys):
    agg = _load_tool("telemetry_agg")
    report = _load_tool("telemetry_report")
    base = str(tmp_path / "t.jsonl")
    _write_rank_files(base, {0: 10.0, 1: 30.0})
    assert agg.main([base]) == 0
    out = capsys.readouterr().out
    assert "2 rank file(s)" in out
    assert "STRAGGLER" in out and "slowest rank: 1" in out
    assert "fit_samples" in out and "800" in out
    # the report tool's --ranks view rides the same library
    assert report.main([base, "--ranks"]) == 0
    out = capsys.readouterr().out
    assert "Per-rank skew" in out and "STRAGGLER" in out
    # machine-readable view
    assert agg.main([base, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["skew"]["step"]["straggler"] == 1
    # missing files get a one-line message, not a traceback
    assert agg.main([str(tmp_path / "absent.jsonl")]) == 1
    assert "no files match" in capsys.readouterr().err
    # --ranks renders the fleet view only: single-rank flags are rejected
    # loudly instead of silently dropped
    for bad in (["--health"], ["--steps"], ["--epoch", "0"]):
        with pytest.raises(SystemExit):
            report.main([base, "--ranks"] + bad)
        assert "--ranks" in capsys.readouterr().err


def test_agg_live_file_without_summary(tmp_path):
    """A killed/live rank (no summary event) still folds from the stream —
    including its HISTOGRAMS, rebuilt from span durations and hist events,
    so the merged fleet tail latency covers the dead rank too."""
    agg = _load_tool("telemetry_agg")
    base = str(tmp_path / "t.jsonl")
    # rank 0: completed run (summary present)
    tel.start(base + ".rank0")
    tel.record_span("step", time.time(), 0.01, cat="step")
    tel.stop()
    # rank 1: killed mid-run — no summary event
    tel.start(base + ".rank1")
    tel.record_span("step", time.time(), 0.03, cat="step")
    tel.histogram("queue_depth", 5.0)
    tel.counter("fit_samples", 10)
    tel.flush()   # file on disk, but no summary event written
    tel.reset()
    tel._enabled = False
    merged = agg.aggregate(agg.rank_files(base))
    assert merged["per_rank"][1]["has_summary"] is False
    assert merged["counters"]["fit_samples"] == 10
    assert merged["skew"]["step"]["ranks"][1]["count"] == 1
    # the dead rank's span durations joined the bucket merge
    assert merged["histograms"]["step"]["count"] == 2
    assert merged["histograms"]["step"]["max"] == pytest.approx(
        30000.0, rel=0.01)   # µs
    assert merged["histograms"]["queue_depth"]["count"] == 1


def test_rebuild_hist_matches_telemetry_export():
    """The agg tool's stdlib bucket-scheme copy stays in lockstep with
    mxnet_tpu.telemetry: rebuilding from raw values reproduces the
    exporter's histogram exactly (same bound keys, counts, stats)."""
    agg = _load_tool("telemetry_agg")
    vals = [float(v) for v in RS(11).uniform(0.01, 1e6, 300)]
    vals += [0.0, -1.0, 1e11, float("nan")]   # under/overflow + non-finite
    tel.start()
    for v in vals:
        tel.histogram("x", v)
    exported = tel.histograms()["x"]
    tel.stop()
    assert agg.rebuild_hist(vals) == exported
    assert agg.rebuild_hist([float("nan")]) is None


def test_rank_files_ignores_stale_base(tmp_path):
    """A leftover single-process file (no .rankN suffix) must not join a
    multi-process merge — it would shift every real rank's label and fold
    stale data into the fleet totals."""
    agg = _load_tool("telemetry_agg")
    base = str(tmp_path / "t.jsonl")
    _write_rank_files(base, {0: 10.0, 1: 30.0})
    Path(base).write_text("")   # stale single-process leftover
    files = agg.rank_files(base)
    assert [agg.rank_of(p) for p in files] == [0, 1]
    merged = agg.aggregate(files)
    assert merged["skew"]["step"]["straggler"] == 1
    # without rank files the bare base is still usable
    solo = str(tmp_path / "solo.jsonl")
    tel.start(solo)
    tel.counter("c", 1)
    tel.stop()
    assert agg.rank_files(solo) == [solo]


# ------------------------------------------------------------- live endpoint
def test_endpoint_serves_prometheus_and_json():
    tel.start()
    tel.counter("requests", 7)
    tel.gauge("temp", 21.5)
    tel.gauge("device_live_bytes[TFRT_CPU_0]", 1024)
    for v in (100.0, 200.0, 400.0):
        tel.histogram("lat", v)
    port = ms.start_server(0)
    assert port and ms.server_port() == port
    assert any(t.name == "mxtpu-metrics" for t in threading.enumerate())

    text = _http_get(port, "/metrics")
    assert "# TYPE mxtpu_requests_total counter" in text
    assert "mxtpu_requests_total 7" in text
    assert "mxtpu_temp 21.5" in text
    assert "mxtpu_device_live_bytes_TFRT_CPU_0 1024.0" in text
    assert "# TYPE mxtpu_lat histogram" in text
    assert 'mxtpu_lat_bucket{le="+Inf"} 3' in text
    assert "mxtpu_lat_sum 700.0" in text and "mxtpu_lat_count 3" in text
    # cumulative bucket counts are monotone and end at the total
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("mxtpu_lat_bucket")]
    assert cums == sorted(cums) and cums[-1] == 3

    # a counter and a span histogram that sanitize to the same family name
    # (dist_allreduce vs dist.allreduce) must not emit two conflicting
    # # TYPE lines — Prometheus drops the whole scrape on that
    tel.counter("dist_allreduce")
    tel.record_span("dist.allreduce", time.time(), 0.001)
    text = _http_get(port, "/metrics")
    families = [line.split()[2] for line in text.splitlines()
                if line.startswith("# TYPE")]
    assert len(families) == len(set(families))
    assert "# TYPE mxtpu_dist_allreduce_total counter" in text
    assert "# TYPE mxtpu_dist_allreduce histogram" in text

    doc = json.loads(_http_get(port, "/metrics.json"))
    assert doc["recording"] is True
    assert doc["counters"]["requests"] == 7
    assert doc["histograms"]["lat"]["count"] == 3
    assert doc["histograms"]["lat"]["quantiles"]["p99"] == pytest.approx(
        400.0, rel=0.1)
    assert _http_get(port, "/healthz").strip() == "ok"

    ms.stop_server()
    assert ms.server_port() is None
    with pytest.raises(Exception):
        _http_get(port, "/healthz")


def test_endpoint_rank_offset_and_autostart(monkeypatch):
    base = _free_port()
    monkeypatch.setenv("MXNET_METRICS_PORT", str(base))
    monkeypatch.setenv("MXTPU_PROCESS_ID", "1")
    assert ms._autostart() is True
    try:
        # launch contract: rank N serves on base+N, and the rank rides
        # every exposed metric as a label
        assert ms.server_port() == base + 1
        # autostart with MXNET_TELEMETRY unset began an in-memory session
        assert tel.enabled()
        tel.counter("c", 2)
        text = _http_get(base + 1, "/metrics")
        assert 'mxtpu_c_total{rank="1"} 2' in text
        doc = json.loads(_http_get(base + 1, "/metrics.json"))
        assert doc["rank"] == "1"
    finally:
        ms.stop_server()


def test_endpoint_bad_env_degrades(monkeypatch):
    monkeypatch.setenv("MXNET_METRICS_PORT", "not-a-port")
    with pytest.warns(UserWarning, match="metrics endpoint disabled"):
        assert ms._autostart() is False
    assert ms.server_port() is None
    monkeypatch.setenv("MXNET_METRICS_PORT", "0")
    assert ms._autostart() is False
    assert not tel.enabled()


def test_endpoint_bind_address(monkeypatch):
    """MXNET_METRICS_PORT accepts <port> or <host>:<port>; the default
    bind is loopback so a fit's internals are not network-visible unless
    asked."""
    assert ms._parse_endpoint("9100") == ("127.0.0.1", 9100)
    assert ms._parse_endpoint("0.0.0.0:9100") == ("0.0.0.0", 9100)
    assert ms._parse_endpoint("myhost:8080") == ("myhost", 8080)
    with pytest.raises(ValueError):
        ms._parse_endpoint("myhost:")
    with pytest.raises(ValueError):
        ms._parse_endpoint("nope")
    # env-driven start binds the host part; default is loopback
    port = _free_port()
    monkeypatch.setenv("MXNET_METRICS_PORT", "127.0.0.1:%d" % port)
    monkeypatch.delenv("MXTPU_PROCESS_ID", raising=False)
    tel.start()
    try:
        assert ms.start_server() == port
        assert ms._server.server_address[0] == "127.0.0.1"
        assert _http_get(port, "/healthz").strip() == "ok"
    finally:
        ms.stop_server()


# ------------------------------------------------------- launcher propagation
def test_launch_propagates_observability_env(monkeypatch):
    launch = _load_tool("launch")
    monkeypatch.setenv("MXNET_TELEMETRY", "/tmp/t.jsonl")
    monkeypatch.setenv("MXNET_METRICS_PORT", "9100")
    monkeypatch.setenv("MXNET_WATCHDOG_SEC", "300")
    monkeypatch.setenv("MXNET_DIAG_DIR", "/tmp/diag")
    monkeypatch.delenv("MXNET_CHECK_NUMERICS", raising=False)
    obs = launch.observability_env()
    assert obs == {"MXNET_TELEMETRY": "/tmp/t.jsonl",
                   "MXNET_METRICS_PORT": "9100",
                   "MXNET_WATCHDOG_SEC": "300",
                   "MXNET_DIAG_DIR": "/tmp/diag"}

    captured = []

    class _FakeProc:
        def __init__(self, cmd, env=None, **kw):
            captured.append((cmd, env))

        def poll(self):
            return 0

        def wait(self):
            return 0

        def kill(self):
            pass

    monkeypatch.setattr(launch.subprocess, "Popen", _FakeProc)
    assert launch.launch_local(2, ["true"]) == 0
    for _, env in captured:
        # local workers get the launcher's full environment (base port
        # verbatim: the per-rank offset lives in metrics_server); ssh
        # workers below need the explicit observability_env() forwarding
        assert env["MXNET_METRICS_PORT"] == "9100"
        assert env["MXNET_TELEMETRY"] == "/tmp/t.jsonl"
    assert {e["MXTPU_PROCESS_ID"] for _, e in captured} == {"0", "1"}

    captured.clear()
    assert launch.launch_ssh(["hostA", "hostB"], ["train.py"]) == 0
    for cmd, _ in captured:
        remote = cmd[-1]   # "cd ... && env K=V ... command"
        assert "MXNET_METRICS_PORT=9100" in remote
        assert "MXNET_TELEMETRY=/tmp/t.jsonl" in remote
        assert "MXNET_WATCHDOG_SEC=300" in remote


# ----------------------------------------------------------- predictor/bench
def test_predictor_telemetry_counters_and_span():
    from mxnet_tpu.predictor import Predictor
    pred = Predictor(_small_net(), {}, {"data": (4, 6)})
    x = RS(0).rand(4, 6).astype(np.float32)
    # disabled path first: no counters, no histograms
    pred.set_input("data", x)
    pred.forward()
    assert tel.counters() == {} and tel.histograms() == {}
    tel.start()
    pred.set_input("data", x)
    pred.forward()
    pred.forward()
    c = tel.counters()
    h = tel.histograms()
    p99 = tel.quantile("predict.forward", 0.99)
    tel.stop()
    assert c["predict_requests"] == 2
    assert c["predict_samples"] == 8
    assert h["predict.forward"]["count"] == 2
    assert h["predict.set_input"]["count"] == 1
    assert p99 is not None and p99 > 0


def test_bench_telemetry_summary():
    spec = importlib.util.spec_from_file_location("bench", ROOT / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert bench.telemetry_summary() is None   # telemetry off
    tel.start()
    t = time.time()
    for i, ms_ in enumerate((10.0, 11.0, 12.0, 13.0)):
        tel.record_span("step", t, ms_ / 1e3, cat="step", nbatch=i)
        tel.record_span("data_wait", t, ms_ / 1e4, cat="step", nbatch=i)
    tel.histogram("bench.step", 5000.0)
    s = bench.telemetry_summary()
    assert s["step"]["count"] == 4
    assert s["step"]["mean_ms"] == pytest.approx(11.5, rel=0.01)
    assert s["step"]["p99_ms"] == pytest.approx(13.0, rel=0.1)
    assert s["bench.step"]["p50_ms"] == pytest.approx(5.0, rel=0.1)
    assert s["data_wait_share"] == pytest.approx(0.1, rel=0.05)


# ---------------------------------------------------- zero-overhead default
def test_everything_off_guard(tmp_path):
    """With all observability env unset: no server thread, no socket, no
    recording, no histogram work — and the entry points stay no-ops."""
    for var in ("MXNET_TELEMETRY", "MXNET_METRICS_PORT", "MXNET_DIAG_DIR",
                "MXNET_WATCHDOG_SEC"):
        assert var not in os.environ
    assert ms._autostart() is False
    assert ms.server_port() is None
    assert not any(t.name == "mxtpu-metrics" for t in threading.enumerate())
    assert not tel.enabled()
    tel.histogram("h", 1.0)
    with tel.span("s", cat="x"):
        pass
    tel.record_span("s", time.time(), 0.001)
    assert tel.histograms() == {} and tel.quantile("s", 0.5) is None
    assert tel.counters() == {} and tel.events() == []
    assert list(tmp_path.iterdir()) == []


# ------------------------------------------------------------ end-to-end e2e
@pytest.mark.slow
@pytest.mark.timeout(300)
def test_launch_local_fleet_e2e(tmp_path):
    """The acceptance path: a 2-process launch_local synthetic fit serves
    live Prometheus text on both rank-offset ports mid-run; afterwards the
    merged rank files name the artificially slowed rank as the straggler."""
    import subprocess
    import sys
    agg = _load_tool("telemetry_agg")
    child = tmp_path / "child.py"
    child.write_text("""
import os, sys, time
sys.path.insert(0, %r)
import numpy as np
import mxnet_tpu as mx

rank = int(os.environ["MXTPU_PROCESS_ID"])
x = np.random.RandomState(0).rand(60, 6).astype(np.float32)
y = np.random.RandomState(1).randint(0, 4, 60).astype(np.float32)
it = mx.io.NDArrayIter(x, y, batch_size=10)
data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
net = mx.sym.SoftmaxOutput(net, name="softmax")
mod = mx.Module(net, context=mx.cpu(),
                data_names=("data",), label_names=("softmax_label",))

def slow_rank(param):
    time.sleep(0.15 if rank == 1 else 0.01)

mod.fit(it, num_epoch=8, batch_end_callback=slow_rank,
        optimizer_params={"learning_rate": 0.1})
print("OK rank", rank)
""" % str(ROOT))
    base_port = _free_port()
    tfile = str(tmp_path / "telemetry.jsonl")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_TELEMETRY"] = tfile
    env["MXNET_METRICS_PORT"] = str(base_port)
    proc = subprocess.Popen(
        [sys.executable, str(ROOT / "tools" / "launch.py"), "-n", "2",
         sys.executable, str(child)],
        env=env, cwd=str(ROOT), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    live = {}
    try:
        deadline = time.time() + 240
        while time.time() < deadline and len(live) < 2:
            if proc.poll() is not None:
                break
            for rank in (0, 1):
                if rank in live:
                    continue
                try:
                    text = _http_get(base_port + rank, "/metrics")
                except Exception:
                    continue
                # an empty exposition means the endpoint is up but the
                # first step hasn't landed yet — keep scraping
                if "# TYPE" in text:
                    live[rank] = text
            time.sleep(0.2)
        out, err = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, (out[-2000:], err[-4000:])
    assert out.count("OK rank") == 2
    # both rank-offset ports served Prometheus text DURING the run
    assert set(live) == {0, 1}, "endpoints never came up mid-run"
    for rank, text in live.items():
        assert 'rank="%d"' % rank in text
        assert "# TYPE" in text
    # post-mortem fleet merge names rank 1 as the straggler
    files = agg.rank_files(tfile)
    assert len(files) == 2
    merged = agg.aggregate(files)
    assert merged["histograms"]["step"]["count"] > 0
    rep = merged["skew"]["step"]
    assert rep["slowest_rank"] == 1 and rep["straggler"] == 1


# ----------------------------------------------------- fleet trace timeline
def _span_ev(name, ts_us, dur_us, cat="step", **tags):
    ev = {"type": "span", "name": name, "cat": cat,
          "ts": ts_us, "dur": dur_us}
    if tags:
        ev["tags"] = dict(tags)
    return ev


def test_trace_merge_corrects_known_skew(tmp_path):
    """Two synthetic rank streams with a KNOWN 3.5 s wall-clock skew:
    the merged chrome trace lands the simultaneous step on the same
    corrected timestamp, one track per rank, tags preserved."""
    tm = _load_tool("trace_merge")
    skew = 3.5
    t0 = 1_000_000_000.0    # µs
    r0 = [
        _span_ev("step", t0, 10_000.0, epoch=0, nbatch=0),
        {"type": "counter", "name": "fit_samples",
         "ts": t0 + 10_000.0, "total": 10},
        {"type": "gauge", "name": "clock_offset_sec",
         "ts": t0 + 11_000.0, "value": 0.0},
    ]
    r1 = [
        _span_ev("step", t0 + skew * 1e6, 14_000.0, epoch=0, nbatch=0),
        {"type": "gauge", "name": "clock_offset_sec",
         "ts": t0 + skew * 1e6 + 15_000.0, "value": skew},
    ]
    base = str(tmp_path / "t.jsonl")
    for rank, evs in ((0, r0), (1, r1)):
        with open("%s.rank%d" % (base, rank), "w") as f:
            for ev in evs:
                f.write(json.dumps(ev) + "\n")
    doc, notes = tm.merge_paths([base + ".rank0", base + ".rank1"])
    assert [n["rank"] for n in notes] == [0, 1]
    assert all(n["corrected"] for n in notes), notes
    assert notes[1]["offset_sec"] == pytest.approx(skew)
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    names = {e["pid"]: e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names == {0: "rank 0", 1: "rank 1"}
    spans = {e["pid"]: e for e in evs if e["ph"] == "X"}
    # offset correction: the skewed rank's step lands on the SAME
    # corrected timestamp as rank 0's
    assert spans[0]["ts"] == pytest.approx(t0)
    assert spans[1]["ts"] == pytest.approx(t0)
    assert spans[1]["args"] == {"epoch": 0, "nbatch": 0}
    assert {c["name"] for c in evs if c["ph"] == "C"} == {"fit_samples",
                                                          "clock_offset_sec"}
    # events are time-sorted (chrome-trace loaders expect it)
    ts = [e.get("ts", 0.0) for e in evs]
    assert ts == sorted(ts)
    # CLI round trip: ONE base path expands .rank*, the output file is
    # loadable JSON carrying the same events
    out = tmp_path / "fleet.trace.json"
    assert tm.main([base, "-o", str(out)]) == 0
    assert json.loads(out.read_text()) == doc


def test_trace_merge_mixes_bundle_and_jsonl(tmp_path):
    """A crash bundle (the flight-recorder ring) and a live JSONL merge
    into one timeline; a stream without clock_offset_sec merges
    uncorrected with a note instead of failing."""
    tm = _load_tool("trace_merge")
    base = str(tmp_path / "t.jsonl")
    with open(base + ".rank0", "w") as f:
        f.write(json.dumps(_span_ev("step", 5e8, 9_000.0,
                                    epoch=1, nbatch=3)) + "\n")
    bundle = {
        "type": "mxtpu_diagnostics", "reason": "fatal_signal", "rank": "1",
        "flight_recorder": {
            "capacity": 64, "recorded": 1,
            "last_step": {"epoch": 1, "nbatch": 2},
            "events": [_span_ev("step", 5e8 + 2e6, 12_000.0,
                                epoch=1, nbatch=2)]},
    }
    bpath = tmp_path / "mxtpu_diag.fatal_signal.pid7.rank1.json"
    bpath.write_text(json.dumps(bundle, indent=1) + "\n")
    doc, notes = tm.merge_paths([base + ".rank0", str(bpath)])
    by_rank = {n["rank"]: n for n in notes}
    assert by_rank[0]["source"] == "jsonl"
    assert by_rank[1]["source"] == "bundle"
    assert not by_rank[0]["corrected"] and not by_rank[1]["corrected"]
    names = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names[0] == "rank 0 (uncorrected clock)"
    assert names[1] == "rank 1 (uncorrected clock)"
    spans = {e["pid"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert spans[1]["args"] == {"epoch": 1, "nbatch": 2}


def test_step_anatomy_names_rank_and_phase(tmp_path, capsys):
    """The step-anatomy verdict names the straggler rank AND the phase
    responsible — all of rank 1's 4 ms excess sits in the comm family
    (nested inside the compute span, so compute stays exclusive)."""
    agg = _load_tool("telemetry_agg")
    base = str(tmp_path / "t.jsonl")
    for rank, (step_ms, comm_ms) in {0: (10.0, 2.0), 1: (14.0, 6.0)}.items():
        tel.start("%s.rank%d" % (base, rank))
        t = time.time()
        for i in range(30):
            tel.record_span("step", t, step_ms / 1e3, cat="step",
                            epoch=0, nbatch=i)
            tel.record_span("data_wait", t, 1.0 / 1e3, cat="step")
            # comm nests INSIDE the fused compute span (the kvstore
            # allreduce runs inside update)
            tel.record_span("fused_step", t, (step_ms - 1.0) / 1e3,
                            cat="step")
            tel.record_span("dist.allreduce", t, comm_ms / 1e3, cat="comm")
        tel.stop()
    merged = agg.aggregate(agg.rank_files(base))
    an = merged["anatomy"]
    assert an["slowest_rank"] == 1 and an["straggler"] == 1
    assert an["skew_ratio"] == pytest.approx(1.4, rel=0.01)
    assert an["slow_phase"] == "comm"
    assert an["slow_phase_excess_ms"] == pytest.approx(4.0, rel=0.01)
    r0, r1 = an["ranks"][0], an["ranks"][1]
    assert r1["comm_ms"] == pytest.approx(6.0, rel=0.01)
    # compute exclusive of the nested comm span: identical across ranks
    assert r1["compute_ms"] == pytest.approx(r0["compute_ms"], rel=0.01)
    # the rendered table carries the same verdict, naming rank AND phase
    assert agg.main([base]) == 0
    out = capsys.readouterr().out
    assert "Step anatomy" in out
    assert "slowest rank: 1" in out
    assert "dominated by comm" in out and "STRAGGLER" in out
    # and the --json doc carries the anatomy block for machines
    assert agg.main([base, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["anatomy"]["slow_phase"] == "comm"


# --------------------------------------------------- wire-bytes accounting
def test_hlo_wire_bytes_from_synthetic_hlo():
    """The dryrun's HLO wire-bytes parser: result-shape payloads per
    collective kind, sync and async (``-start``) forms, ignoring
    non-collective lines."""
    spec = importlib.util.spec_from_file_location(
        "graft_entry", ROOT / "__graft_entry__.py")
    ge = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ge)
    hlo = "\n".join([
        "  %ar = f32[128,256] all-reduce(f32[128,256] %p0), to_apply=%add",
        "  %ar2 = f32[64]{0} all-reduce-start(f32[64] %p1)",
        "  %rs = bf16[32,8] reduce-scatter(bf16[256,8] %x), dimensions={0}",
        "  %ag = f32[1024] all-gather(f32[128] %y), dimensions={0}",
        "  %noise = f32[999] add(f32[999] %a, f32[999] %b)",
    ])
    w = ge.hlo_wire_bytes(hlo)
    assert w["all-reduce"] == 128 * 256 * 4 + 64 * 4
    assert w["reduce-scatter"] == 32 * 8 * 2
    assert w["all-gather"] == 1024 * 4
    assert "all-to-all" not in w
    assert ge.hlo_wire_bytes("no collectives here") == {}


def test_run_compare_gates_wire_bytes_regression(tmp_path):
    """run_compare ingests the dryrun's `wire_bytes` block: per-kind
    payload metrics gate through the wire_bytes down-hint (bytes on the
    wire regress by going UP), the config block is identity, and the
    committed MULTICHIP_WIRE_r01.json self-compares rc=0."""
    from tools import run_compare as rc

    def record(ar_mb, zero_ar_mb, devices=8):
        return {"metric": "wire_bytes_all_reduce_mb", "value": ar_mb,
                "unit": "mb",
                "wire_bytes": {"wire_bytes_all_reduce_mb": ar_mb,
                               "zero_wire_bytes_all_reduce_mb": zero_ar_mb,
                               "config": {"devices": devices,
                                          "per_device_batch": 2}}}

    base = tmp_path / "a.json"
    base.write_text(json.dumps(record(90.0, 30.0)))
    same = tmp_path / "b.json"
    same.write_text(json.dumps(record(90.0, 30.0)))
    worse = tmp_path / "c.json"
    worse.write_text(json.dumps(record(135.0, 30.0)))
    other = tmp_path / "d.json"
    other.write_text(json.dumps(record(45.0, 15.0, devices=4)))
    assert rc.main([str(base), str(same), "--check"]) == 0
    # payload bytes going UP is a REGRESSION (the wire_bytes down-hint)
    assert rc.main([str(base), str(worse), "--check"]) == 2
    # a different mesh is a different experiment, not a regression pair
    assert rc.main([str(base), str(other), "--check"]) == 0
    run = rc.load_run(str(base))
    assert run.bench["wire_bytes_all_reduce_mb"] == pytest.approx(90.0)
    assert "config" not in run.bench
    committed = ROOT / "MULTICHIP_WIRE_r01.json"
    assert committed.exists(), "committed wire record missing"
    assert rc.main([str(committed), str(committed), "--check"]) == 0
    rec = rc.load_run(str(committed))
    assert rec.bench["wire_bytes_all_reduce_mb"] > 0


@pytest.mark.slow
@pytest.mark.timeout(300)
def test_dist_observability_clean_timeline_and_wire_bytes(tmp_path):
    """The fleet-timeline acceptance: a 2-process dist fit under
    ``MXNET_SAN=all:raise`` exchanges clock samples at barrier entries
    (KV RPC only — zero ledger violations), accounts the kvstore
    all-reduce payload in ``dist.wire_bytes()``, and the per-rank
    telemetry streams merge into one offset-corrected chrome trace."""
    import re
    import subprocess
    import sys
    tm = _load_tool("trace_merge")
    tfile = str(tmp_path / "t.jsonl")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_SAN"] = "all:raise"
    env["MXNET_TELEMETRY"] = tfile
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "launch.py"), "-n", "2",
         sys.executable, str(ROOT / "tests" / "python" / "dist" /
                             "dist_observability.py")],
        env=env, cwd=str(ROOT), capture_output=True, text=True, timeout=280)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    assert out.count("OK rank") == 2, out[-3000:]
    # every rank accounted the kvstore all-reduce payload
    obs = dict(re.findall(r"OBS rank (\d) offset \S+ wire (.*)",
                          proc.stdout))
    assert set(obs) == {"0", "1"}
    for rank, wire_json in obs.items():
        wires = json.loads(wire_json)
        assert wires["dist.allreduce/worker"] > 0, (rank, wires)
    # the per-rank streams carry the clock estimate and merge corrected
    files = [tfile + ".rank0", tfile + ".rank1"]
    for f in files:
        assert os.path.exists(f), os.listdir(str(tmp_path))
    doc, notes = tm.merge_paths(files)
    assert [n["rank"] for n in notes] == [0, 1]
    assert all(n["corrected"] for n in notes), notes
    assert notes[0]["offset_sec"] == 0.0   # rank 0 IS the reference
    names = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names == {0: "rank 0", 1: "rank 1"}
    span_pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert span_pids == {0, 1}
    # the wire-bytes counters rode the same streams onto the timeline
    wire_tracks = {e["name"] for e in doc["traceEvents"]
                   if e["ph"] == "C" and "coll_wire_bytes" in e["name"]}
    assert any("dist.allreduce/worker" in n for n in wire_tracks), \
        wire_tracks


@pytest.mark.slow
@pytest.mark.timeout(300)
def test_flight_recorder_kill_rank_e2e(tmp_path):
    """THE flight-recorder acceptance: a 2-process launch with the ring
    armed, rank 1 killed mid-epoch → its ``fatal_signal`` bundle names
    the last completed step; trace_merge over rank 1's bundle + rank 0's
    flushed JSONL yields ONE Perfetto-loadable timeline with
    offset-corrected per-rank tracks."""
    import glob
    import subprocess
    import sys
    tm = _load_tool("trace_merge")
    tfile = str(tmp_path / "t.jsonl")
    diag = tmp_path / "diag"
    diag.mkdir()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_TELEMETRY"] = tfile
    env["MXNET_FLIGHT_RECORDER"] = "512"
    env["MXNET_DIAG_DIR"] = str(diag)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "launch.py"), "-n", "2",
         sys.executable, str(ROOT / "tests" / "python" / "dist" /
                             "dist_flight_recorder_kill.py")],
        env=env, cwd=str(ROOT), capture_output=True, text=True, timeout=280)
    out = proc.stdout + proc.stderr
    # the world died by design: the launcher saw rank 1's SIGTERM exit
    # and tore rank 0 down
    assert proc.returncode != 0, out[-3000:]
    assert "OK rank 1" not in out
    # rank 1 left its fatal_signal bundle, the ring flushed into it
    bundles = glob.glob(str(diag / "mxtpu_diag.fatal_signal.*.rank1.json"))
    assert len(bundles) == 1, os.listdir(str(diag))
    doc = json.loads(open(bundles[0]).read())
    assert doc["type"] == "mxtpu_diagnostics"
    assert doc["reason"] == "fatal_signal"
    assert doc["extra"]["signal_name"] == "SIGTERM"
    fr = doc["flight_recorder"]
    assert fr["capacity"] == 512 and fr["recorded"] > 0
    # batch_end_callback killed at (2, 2) BEFORE that step span closed,
    # so the last completed step the ring names is (2, 1)
    assert fr["last_step"] == {"epoch": 2, "nbatch": 1}, fr["last_step"]
    # the merged timeline: rank 0's flushed JSONL + rank 1's bundle,
    # both offset-corrected from the per-epoch clock exchange
    rank0 = tfile + ".rank0"
    assert os.path.exists(rank0), os.listdir(str(tmp_path))
    merged, notes = tm.merge_paths([rank0, bundles[0]])
    by_rank = {n["rank"]: n for n in notes}
    assert set(by_rank) == {0, 1}
    assert by_rank[0]["source"] == "jsonl"
    assert by_rank[1]["source"] == "bundle"
    assert all(n["corrected"] for n in notes), notes
    names = {e["pid"]: e["args"]["name"] for e in merged["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names == {0: "rank 0", 1: "rank 1"}
    span_pids = {e["pid"] for e in merged["traceEvents"] if e["ph"] == "X"}
    assert span_pids == {0, 1}
    # Perfetto-loadable: a plain JSON object with a traceEvents list
    json.dumps(merged)


# ------------------------------------------------------ snapshot atomicity
def test_metrics_snapshot_atomic_under_concurrent_scrapes():
    """A scrape is ONE consistent point in time: a writer mutates a
    counter and a gauge together under the registry lock while scrapers
    hammer both endpoint formats — every observed pair must agree.
    Stitching the registries from separate lock acquisitions (the bug
    ``registry_snapshot()`` exists for) tears within a few hundred
    iterations."""
    tel.start()
    port = ms.start_server(0)
    stop = threading.Event()
    tears = []

    def writer():
        i = 0
        while not stop.is_set():
            i += 1
            with tel._lock:
                tel._counters["atomic_probe"] = i
                tel._gauges["atomic_probe_twin"] = float(i)

    def scraper():
        while not stop.is_set():
            doc = ms.json_snapshot()
            c = doc["counters"].get("atomic_probe")
            g = doc["gauges"].get("atomic_probe_twin")
            if c is not None and g != float(c):
                tears.append(("json", c, g))

    threads = [threading.Thread(target=writer, daemon=True),
               threading.Thread(target=scraper, daemon=True)]
    for t in threads:
        t.start()
    try:
        deadline = time.time() + 8
        scrapes = 0
        while time.time() < deadline and scrapes < 150:
            doc = json.loads(_http_get(port, "/metrics.json"))
            c = doc["counters"].get("atomic_probe")
            g = doc["gauges"].get("atomic_probe_twin")
            if c is None:
                continue
            scrapes += 1
            if g != float(c):
                tears.append(("http", c, g))
            # the Prometheus exposition renders from the same snapshot
            text = _http_get(port, "/metrics")
            vals = {}
            for line in text.splitlines():
                if line.startswith("mxtpu_atomic_probe_total "):
                    vals["c"] = float(line.rsplit(" ", 1)[1])
                elif line.startswith("mxtpu_atomic_probe_twin "):
                    vals["g"] = float(line.rsplit(" ", 1)[1])
            if len(vals) == 2 and vals["c"] != vals["g"]:
                tears.append(("prom", vals["c"], vals["g"]))
        assert scrapes >= 150, "endpoint never served the probe pair"
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
    assert tears == [], tears[:5]


# --------------------------------------------------------- agg time windows
def test_agg_since_window_drops_old_steps(tmp_path, capsys):
    """``--since`` rebuilds every table from the windowed stream only:
    the early slow phase disappears from the step histogram, the summary
    totals are dropped (they cover the whole run), and the window is
    named in both renderings."""
    agg = _load_tool("telemetry_agg")
    base = str(tmp_path / "t.jsonl")
    cut_s = 1_700_000_100.0          # window boundary, seconds
    for rank in (0, 1):
        tel.start("%s.rank%d" % (base, rank))
        for i in range(20):          # old regime: 50 ms steps, pre-cut
            tel.record_span("step", cut_s - 100.0 + i, 0.050, cat="step",
                            epoch=0, nbatch=i)
        for i in range(20):          # new regime: 10 ms steps, post-cut
            tel.record_span("step", cut_s + i, 0.010, cat="step",
                            epoch=1, nbatch=i)
        tel.counter("fit_samples", 400)
        tel.stop()
    files = agg.rank_files(base)
    whole = agg.aggregate(files)
    assert whole["histograms"]["step"]["count"] == 80
    assert whole["counters"]["fit_samples"] == 800   # from the summaries
    win = agg.aggregate(files, since_us=cut_s * 1e6)
    assert win["histograms"]["step"]["count"] == 40
    # only the 10 ms regime is left — the old tail is gone
    assert win["histograms"]["step"]["max"] == pytest.approx(
        10_000.0, rel=0.05)
    # the summary was dropped, but the stream's own cumulative counter
    # events sit in-window (written at stop time) and still fold — the
    # histogram halving above is the proof the tables were rebuilt from
    # the windowed stream, not the summary
    assert win["counters"]["fit_samples"] == 800
    assert agg.main([base, "--since", "%f" % cut_s]) == 0
    out = capsys.readouterr().out
    assert "window: since" in out and "summaries dropped" in out
    assert agg.main([base, "--since", "%f" % cut_s, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["window"]["since"] == pytest.approx(cut_s)
    assert doc["histograms"]["step"]["count"] == 40


def test_agg_last_n_steps_window_and_anatomy(tmp_path, capsys):
    """``--last N`` anchors at each rank's N-th-from-last step span, and
    the step-anatomy verdict describes ONLY the window: a straggler that
    recovered mid-run vanishes from ``--last``, while the whole-run view
    still flags it."""
    agg = _load_tool("telemetry_agg")
    base = str(tmp_path / "t.jsonl")
    t0 = 1_700_000_000.0
    for rank in (0, 1):
        tel.start("%s.rank%d" % (base, rank))
        for i in range(30):
            # rank 1's first 15 steps are 3x slow (data_wait), then both
            # ranks agree at 10 ms
            slow = rank == 1 and i < 15
            step_s = 0.030 if slow else 0.010
            tel.record_span("step", t0 + i, step_s, cat="step",
                            epoch=0, nbatch=i)
            tel.record_span("data_wait", t0 + i,
                            0.021 if slow else 0.001,
                            cat="step")
            tel.record_span("fused_step", t0 + i, 0.009, cat="step")
        tel.stop()
    files = agg.rank_files(base)
    whole = agg.aggregate(files)
    assert whole["anatomy"]["straggler"] == 1
    assert whole["anatomy"]["slow_phase"] == "data_wait"
    tail = agg.aggregate(files, last_steps=10)
    assert tail["histograms"]["step"]["count"] == 20
    assert tail["anatomy"]["straggler"] is None   # it recovered
    assert tail["anatomy"]["skew_ratio"] == pytest.approx(1.0, rel=0.05)
    assert agg.main([base, "--last", "10"]) == 0
    out = capsys.readouterr().out
    assert "window: last 10 step(s)" in out
    assert "STRAGGLER" not in out
    # degenerate flag value: loud one-line error, not a traceback
    assert agg.main([base, "--last", "0"]) == 1
    assert "--last must be positive" in capsys.readouterr().err
    # --since composes with --last (both windows apply)
    assert agg.main([base, "--since", "%f" % t0, "--last", "5",
                     "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["window"] == {"since": pytest.approx(t0), "last": 5}
    assert doc["histograms"]["step"]["count"] == 10


# ------------------------------------------------- degenerate trace inputs
def test_trace_merge_degenerate_inputs(tmp_path, capsys):
    """Regression pins for the empty-input family: a zero-event JSONL, an
    empty file, a bundle with an empty flight-recorder ring, and a JSON
    document that isn't a bundle all merge into a VALID empty chrome
    trace (rc 0) with one named warning per degenerate stream — they
    used to crash the merge."""
    tm = _load_tool("trace_merge")
    base = str(tmp_path / "t.jsonl")
    # rank 0: one real span so the merged doc has content
    with open(base + ".rank0", "w") as f:
        f.write(json.dumps(_span_ev("step", 5e8, 9_000.0)) + "\n")
    # rank 1: zero-event stream (blank lines + non-dict JSON lines only)
    with open(base + ".rank1", "w") as f:
        f.write("\n[]\n42\n")
    # rank 2: completely empty file
    open(base + ".rank2", "w").close()
    doc, notes = tm.merge_paths([base + ".rank%d" % r for r in (0, 1, 2)])
    by_rank = {n["rank"]: n for n in notes}
    assert by_rank[0]["warning"] is None
    assert "zero-event telemetry stream" in by_rank[1]["warning"]
    assert "zero-event telemetry stream" in by_rank[2]["warning"]
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 1 and spans[0]["pid"] == 0
    # empty-ring bundle: valid, warned, zero spans
    bundle = {"type": "mxtpu_diagnostics", "reason": "probe", "rank": "3",
              "flight_recorder": {"capacity": 64, "recorded": 0,
                                  "events": []}}
    bpath = tmp_path / "mxtpu_diag.probe.pid1.rank3.json"
    bpath.write_text(json.dumps(bundle) + "\n")
    doc2, notes2 = tm.merge_paths([str(bpath)])
    assert "empty flight-recorder ring" in notes2[0]["warning"]
    assert doc2["traceEvents"] == [e for e in doc2["traceEvents"]
                                   if e["ph"] == "M"]
    json.dumps(doc2)                  # still a loadable chrome trace
    # a JSON document that isn't a diagnostics bundle: named, not crashed
    odd = tmp_path / "odd.rank4.json"
    odd.write_text("{}\n")
    _, notes3 = tm.merge_paths([str(odd)])
    assert "not an mxnet_tpu diagnostics bundle" in notes3[0]["warning"]
    # CLI: rc 0, warnings on stderr, output file is a valid empty trace
    out = tmp_path / "fleet.trace.json"
    assert tm.main([base + ".rank2", "-o", str(out)]) == 0
    err = capsys.readouterr().err
    assert "trace_merge: warning:" in err
    assert "zero-event telemetry stream" in err
    merged = json.loads(out.read_text())
    assert isinstance(merged["traceEvents"], list)


# ------------------------------------------------------- live sentinel e2e
@pytest.mark.slow
@pytest.mark.timeout(300)
def test_dist_sentinel_names_straggler_live(tmp_path):
    """THE live-sentinel acceptance: a 2-process dist fit with rank 1's
    data iterator artificially stalled — within K steps EVERY rank's
    ``dist.straggler()`` names rank 1 AND the data_wait phase mid-run
    (digests ride the coordination KV at barrier entries), all under
    ``MXNET_SAN=all:raise`` with zero collective-ledger violations."""
    import re
    import subprocess
    import sys
    tfile = str(tmp_path / "t.jsonl")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_SAN"] = "all:raise"
    env["MXNET_SENTINEL"] = "step:3sigma"
    env["MXNET_TELEMETRY"] = tfile
    env["MXNET_DEVICE_PREFETCH"] = "0"   # keep the stall in data_wait
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "launch.py"), "-n", "2",
         sys.executable, str(ROOT / "tests" / "python" / "dist" /
                             "dist_sentinel_straggler.py")],
        env=env, cwd=str(ROOT), capture_output=True, text=True, timeout=280)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    assert out.count("OK rank") == 2, out[-3000:]
    obs = re.findall(r"OBS rank (\d) first_step (\d+) verdict (.*)",
                     proc.stdout)
    assert {r for r, _, _ in obs} == {"0", "1"}, proc.stdout
    for rank, first_step, verdict_json in obs:
        # named LIVE: the verdict existed within a handful of steps
        assert int(first_step) <= 8, (rank, first_step)
        v = json.loads(verdict_json)
        assert v["rank"] == 1, (rank, v)
        assert v["phase"] == "data_wait", (rank, v)
        assert v["slowdown"] > 1.5, (rank, v)
    # the verdict rode telemetry into both rank streams as gauges
    agg = _load_tool("telemetry_agg")
    merged = agg.aggregate(agg.rank_files(tfile))
    for rank in (0, 1):
        g = merged["gauges_by_rank"][rank]
        assert any(k.startswith("straggler_rank") for k in g), g
