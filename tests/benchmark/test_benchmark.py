"""The benchmark's own tests, on the CPU at tiny sizes: the yardstick's
arithmetic (required FLOPs, the trace reduction and its reconciliation), the
contract of ``BENCHMARK.json`` (every file it names exists, every name and
unit is well formed, a cell, a configuration and a metric can be added by
files and entries alone), and what decides ``correct``: the plain references
against the program through the harness's own run, the lower-precision
control failing that comparison, and a run with the timed path broken
underneath coming out as not correct."""
import copy
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from benchmark import cells, check, gen, trace  # noqa: E402
from benchmark.flops import flash, resnet as resnet_flops  # noqa: E402
from benchmark.flops import transformer as transformer_flops  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ------------------------------------------------------------ required FLOPs
def test_conv_flops_by_hand():
    # 3x3, 64 -> 128 channels onto 28x28, batch 2: 2*2*28*28*128*64*9
    assert resnet_flops.conv_flops(2, 64, 128, 3, 28, 28) == 231211008


def test_fc_flops_by_hand():
    assert resnet_flops.fc_flops(256, 2048, 1000) == 2 * 256 * 2048 * 1000


def test_resnet50_forward_is_the_published_count():
    cfg = json.load(open(os.path.join(ROOT, "benchmark/configs/resnet50.json")))
    macs = resnet_flops.forward_flops(cfg, 1) / 2
    assert 4.0e9 < macs < 4.2e9          # He et al.: 3.8 GMACs v1, 4.1 v2
    assert resnet_flops.step_flops(cfg, 256) == 3 * 256 * 2 * macs


def test_opt_block_flops_by_hand():
    # qkv 3C^2 + proj C^2 + mlp 2*4C^2 = 12 C^2 multiply-adds a token
    assert transformer_flops.block_matmul_flops(8192, 2048, 8192) \
        == 2 * 8192 * 12 * 2048 * 2048


@pytest.mark.parametrize("causal", [True, False])
def test_attention_flops_by_hand(causal):
    full = 2 * 2 * 4 * 32 * 2048 * 2048 * 64
    assert transformer_flops.attention_flops(4, 32, 2048, 64, causal) \
        == (full // 2 if causal else full)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kernel,products", [("fwd", 2), ("dq", 3),
                                             ("dkv", 4)])
def test_flash_kernel_flops_by_hand(kernel, products, causal):
    full = products * 2 * 128 * 2048 * 2048 * 64
    assert flash.flash_flops(kernel, 128, 2048, 64, causal) \
        == (full // 2 if causal else full)


@pytest.mark.parametrize("kernel,arrays,rows", [("fwd", 4, 1), ("dq", 5, 2),
                                                ("dkv", 6, 2)])
def test_flash_kernel_bytes_by_hand(kernel, arrays, rows):
    assert flash.flash_bytes(kernel, 128, 2048, 64) \
        == arrays * 128 * 2048 * 64 * 2 + rows * 128 * 2048 * 4


def test_flash_roofline_names_its_bound():
    sec, bound = flash.least_seconds("fwd", 128, 2048, 64, True, 197e12,
                                     819e9)
    assert bound == "flops" and sec == pytest.approx(
        flash.flash_flops("fwd", 128, 2048, 64, True) / 197e12)
    sec, bound = flash.least_seconds("fwd", 128, 128, 64, True, 197e12, 819e9)
    assert bound == "bytes"


# ------------------------------------------------------ the trace reduction
@pytest.fixture()
def small():
    return json.load(open(os.path.join(HERE, "small_trace.json")))


def test_reduction_busy_idle_window(small):
    red = trace.reduce(small, steps=2)
    dev0 = red["per_device"]["0"]
    assert red["slowest"] == "0"
    assert dev0["busy"] == pytest.approx(0.070)
    assert trace.length(dev0["gaps"]) == pytest.approx(0.030)
    assert dev0["busy"] + trace.length(dev0["gaps"]) \
        == pytest.approx(red["window_s"])
    assert red["busy_s"] == pytest.approx(0.060)      # mean of 70 and 50 ms


def test_reduction_step_program_time(small):
    red = trace.reduce(small, steps=2)
    assert red["step_program"].startswith("jit_mxtpu_step_amp")
    assert red["step_runs"] == 2
    assert red["step_device_s"] == pytest.approx(0.035)


def test_a_collective_counts_as_busy_and_keeps_its_kind(small):
    """No cell runs over chips yet, so no reader takes collectives apart;
    the plain form keeps each operation's kind for the reader that will."""
    kinds = {op[0]: op[3] for op in small["devices"]["1"]}
    assert kinds["all-reduce.7"] == "collective"
    red = trace.reduce(small, steps=2)
    assert red["per_device"]["1"]["busy"] == pytest.approx(0.050)


def test_reduction_gap_goes_to_the_open_span(small):
    red = trace.reduce(small, steps=2)
    gaps = dict(trace.idle_by_span(small, "0",
                                   red["per_device"]["0"]["gaps"]))
    assert gaps["bench:dispatch_chunk"] == pytest.approx(0.010)
    assert gaps["bench:wait_chunk"] == pytest.approx(0.020)


def test_reduction_top_operations(small):
    top = trace.top_ops(small, "0")
    assert top[0][0].startswith("fusion.910") and top[0][1] \
        == pytest.approx(0.020)
    assert len(top) <= 10


def _ctx(small, cell_name="opt-1.3b-steps"):
    from benchmark import run
    cell = cells.Cell(cell_name)
    return run.Context(plain=small, reduced=trace.reduce(small, steps=2),
                       cell=cell, peaks=cell.peaks("TPU v5 lite"), chips=1,
                       compiles_in_window=0,
                       window={"stamps": [0.0, 0.05, 0.1]})


def test_named_kernels_time_and_roofline(small):
    from benchmark.readers import kernels
    ctx = _ctx(small)
    assert kernels._times(ctx) == {"fwd": (pytest.approx(0.010), 1),
                                   "dkv": (pytest.approx(0.008), 1),
                                   "dq": (pytest.approx(0.004), 1)}
    assert kernels.flash_time_share(ctx) == pytest.approx(100 * 22 / 70)
    least = flash.flash_flops("dq", 128, 2048, 64, True) / 197e12
    assert kernels.flash_roofline(ctx, "dq") \
        == pytest.approx(100 * least / 0.004)


def test_readers_of_the_small_trace(small):
    from benchmark.readers import compile_cache, device, fit_loop, step
    ctx = _ctx(small)
    assert device.idle_share(ctx) == pytest.approx(40.0)
    assert step.device_ms(ctx) == pytest.approx(35.0)
    assert compile_cache.programs_compiled(ctx) == 0.0
    # the metric's own file leads the loader to its reader
    assert ctx.cell.reader("step.device_ms") == (step.device_ms, {})
    assert fit_loop.host_ms_per_batch(ctx) == pytest.approx(15.0)
    want = 100 * transformer_flops.step_flops(ctx.cell.config, 4) * 2 \
        / 0.1 / 197e12
    assert step.mfu(ctx) == pytest.approx(want)


def test_a_reader_with_nothing_to_read_returns_nothing(small):
    from benchmark import run
    from benchmark.readers import kernels
    bare = copy.deepcopy(small)
    for dev in bare["devices"]:
        bare["devices"][dev] = [op for op in bare["devices"][dev]
                                if op[3] == "compute"
                                and "custom-call" not in op[4]]
    bare["modules"]["0"] = [["jit_mxtpu_step_amp(123)", 0.010, 0.030],
                            ["jit_mxtpu_step_amp(123)", 0.058, 0.070],
                            ["jit_mxtpu_step_amp(123)", 0.080, 0.090]]
    ctx = _ctx(bare)
    assert kernels.flash_time_share(ctx) is None
    assert kernels.flash_roofline(ctx, "fwd") is None
    assert "kernel.flash_time_share" not in run.per_layer_metrics(
        ctx.cell, ctx)


@pytest.mark.parametrize("what", ["modules_short", "no_modules",
                                  "no_operations", "host_faster_than_step"])
def test_reconciliation_refuses_an_inconsistent_trace(small, what):
    from benchmark.readers import fit_loop
    bad = copy.deepcopy(small)
    if what == "modules_short":      # the step program ran 40 ms of 70 busy
        bad["modules"]["0"] = [["jit_mxtpu_step_amp(123)", 0.010, 0.050]]
    elif what == "no_modules":
        bad["modules"] = {}
    elif what == "no_operations":
        bad["devices"] = {}
    if what == "host_faster_than_step":
        ctx = _ctx(small)
        ctx.reduced = dict(ctx.reduced, step_device_s=0.2)
        with pytest.raises(trace.Inconsistent):
            fit_loop.host_ms_per_batch(ctx)
    else:
        with pytest.raises(trace.Inconsistent):
            trace.reduce(bad, steps=2)


@pytest.mark.parametrize("text,name,opcode,kind", [
    ("%fusion.910 = (u8[64,3,7]{0,1,2:T(4,128)(4,1)S(1)}, f32[64,3,7,7]"
     "{0,1,2,3}) fusion(f32[2]{0} %a), kind=kLoop", "fusion.910", "fusion",
     "compute"),
    ("%all-reduce-start.1 = f32[10]{0} all-reduce-start(f32[10]{0} %p)",
     "all-reduce-start.1", "all-reduce-start", "collective"),
    ("%copy-start.5 = (f32[64]{0:T(128)S(1)}, f32[64]{0:T(128)}, u32[]"
     "{:S(2)}) copy-start(f32[64]{0:T(128)} %args_15_.1)", "copy-start.5",
     "copy-start", "transfer"),
])
def test_an_operation_is_read_from_its_hlo_line(text, name, opcode, kind):
    got_name, got_opcode, hlo = trace.split_hlo(text)
    assert (got_name, got_opcode) == (name, opcode)
    assert trace.op_kind(got_opcode) == kind and "{" not in hlo


# ------------------------------------------------- BENCHMARK.json's contract
def _named_files():
    files = [c["file"] for c in BENCH["configs"]]
    for w in BENCH["workloads"]:
        files.append("benchmark/traffic/%s.json" % w["traffic"])
        files.append("benchmark/limits/%s.json" % w["name"])
    for m in BENCH["per_layer"]:
        files.append("benchmark/metrics/%s.json" % m["name"])
    files.append("benchmark/peaks.json")
    return sorted(set(files))


@pytest.mark.parametrize("path", _named_files())
def test_every_file_the_benchmark_names_exists(path):
    assert any(path.startswith(p + "/") for p in BENCH["paths"])
    json.load(open(os.path.join(ROOT, path)))


def _names():
    out = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        out += [(key, e["name"]) for e in BENCH[key]]
    out += [("config", w["config"]) for w in BENCH["workloads"]]
    out += [("traffic", w["traffic"]) for w in BENCH["workloads"]]
    out += [("reduced", k) for c in BENCH["configs"] for k in c["reduced"]]
    return sorted(set(out))


@pytest.mark.parametrize("kind,name", _names())
def test_every_name_is_within_the_allowed_characters(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_is_well_formed(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    cells_ = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells_)) <= cells_
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.1
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
    else:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
            assert metric["unit"] == "%"


def test_the_file_as_a_whole():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in BENCH["end_to_end"])
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits the driver's 43200 seconds
    s = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
        body = json.load(open(os.path.join(ROOT, c["file"])))
        assert body["reduced"] == c["reduced"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_and_reports_what_the_contract_asks(cell):
    c = cells.Cell(cell)
    e2e = [m["name"] for m in c.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer()
    for m in c.per_layer():
        fn, args = c.reader(m["name"])
        assert callable(fn) and isinstance(args, dict)
    assert set(c.limits) and all(v >= 0 for v in c.limits.values())
    assert c.flops().step_flops(c.config, int(c.traffic["batch"])) > 0
    assert c.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        c.peaks("TPU v9 imaginary")
    assert hasattr(c.entry(), "Entry")


def test_a_cell_a_config_and_a_metric_are_added_by_files_alone(tmp_path):
    """A temporary copy of the benchmark gains one configuration, one
    traffic mix, one cell and one per-layer metric (with a reader of its
    own) by new files and new entries; no file that was there is edited
    but BENCHMARK.json, and the loader finds them all."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = copy.deepcopy(BENCH)
    b = os.path.join(root, "benchmark")
    cfg = json.load(open(os.path.join(b, "configs", "resnet50.json")))
    cfg.update(num_layers=101, units=[3, 4, 23, 3])
    json.dump(cfg, open(os.path.join(b, "configs", "resnet101.json"), "w"))
    tr = json.load(open(os.path.join(b, "traffic", "fit.json")))
    tr["batch"] = 32
    json.dump(tr, open(os.path.join(b, "traffic", "fit-b32.json"), "w"))
    json.dump({"loss1": 0.01},
              open(os.path.join(b, "limits", "resnet101-fit-b32.json"), "w"))
    json.dump({"module": "extra", "function": "steps_seen"},
              open(os.path.join(b, "metrics", "step.count.json"), "w"))
    with open(os.path.join(b, "readers", "extra.py"), "w") as f:
        f.write("def steps_seen(ctx):\n    return float(ctx.reduced"
                "['steps'])\n")
    bench["configs"].append({"name": "resnet101", "source": "He et al.",
                             "file": "benchmark/configs/resnet101.json",
                             "reduced": [], "why": "deeper"})
    bench["workloads"].append({"name": "resnet101-fit-b32",
                               "config": "resnet101", "traffic": "fit-b32",
                               "chips": 1, "why": "small batch"})
    bench["per_layer"].append({"name": "step.count", "unit": "count",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "fused step program",
                               "moves": "train_items_per_s",
                               "workloads": ["resnet101-fit-b32"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    code = (
        "from benchmark import cells\n"
        "c = cells.Cell('resnet101-fit-b32')\n"
        "assert c.config['units'] == [3, 4, 23, 3] and c.traffic['batch'] == 32\n"
        "names = [m['name'] for m in c.per_layer()]\n"
        "assert 'step.count' in names and 'flash_fwd_roofline' not in names\n"
        "fn, args = c.reader('step.count')\n"
        "class X: reduced = {'steps': 7}\n"
        "assert fn(X()) == 7.0\n"
        "assert c.flops().step_flops(c.config, 32) > 0\n"
        "print('loaded', c.name)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=root,
                         env=dict(os.environ, PYTHONPATH=root),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "loaded resnet101-fit-b32" in out.stdout


# ------------------------------------------------------- the entry's refusals
def test_run_refuses_a_cpu():
    """Without a TPU the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "Nothing was measured" in out.stderr


def test_an_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        cells.Cell("no-such-cell")


# ------------------------------------------------- what decides ``correct``
TINY_LIMITS = {
    # set from readings at these sizes on the CPU (PR 24), the first number
    # of each cell being the one that the control and the faults must fail:
    # steps reads mom2_p90 <= 0.0121 on 4 seeds, its control 0.026 on seed 3,
    # half a batch >= 0.31; fit reads grad1_med <= 0.0008 on 3 seeds, its
    # control >= 0.010, half a batch >= 0.41
    "opt-1.3b-steps": {"mom2_p90": 0.015, "mom2_med": 0.006,
                       "change2_med": 0.01, "loss2": 0.002},
    "resnet50-fit": {"grad1_med": 0.005, "grad1_p90": 0.02, "loss1": 0.002},
}
CELLS = sorted(TINY_LIMITS)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark whose configurations and traffic are cut to
    what the CPU can run: same files, same loader, same entries.  The fit
    cell computes in float32 there: at 8 rows of 64x64 the net is too
    ill-conditioned for bfloat16 to tell the program from the control."""
    import jax
    root = str(tmp_path_factory.mktemp("tinybench"))
    json.dump(BENCH, open(os.path.join(root, "BENCHMARK.json"), "w"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(root, "benchmark")

    def edit(rel, fn):
        path = os.path.join(b, rel)
        body = json.load(open(path))
        fn(body)
        json.dump(body, open(path, "w"))

    def small_resnet(c):
        c["symbol"]["args"].update(num_classes=10, image_shape="3,64,64")
        c.update(num_classes=10, image_shape=[3, 64, 64])
        c["precision"]["compute"] = "float32"
        c["optimizer"]["learning_rate"] = 0.01

    def small_opt(c):
        c["symbol"]["args"].update(vocab_size=64, seq_len=16, num_layers=2,
                                   num_hidden=32, num_heads=4)
        c.update(hidden_size=32, num_attention_heads=4, head_dim=8,
                 ffn_dim=128, num_hidden_layers=2, vocab_size=64,
                 max_position_embeddings=16)
    edit("configs/resnet50.json", small_resnet)
    edit("configs/opt-1.3b.json", small_opt)
    edit("traffic/fit.json", lambda t: t.update(
        batch=8, pool_batches=4, epoch_batches=100, warmup_batches=1,
        calibrate_batches=1, interval_batches=1))
    edit("traffic/steps.json", lambda t: t.update(
        batch=2, chunk=2, pool_chunks=2, warmup_chunks=1))
    for name, limits in TINY_LIMITS.items():
        json.dump(limits, open(os.path.join(b, "limits", name + ".json"),
                               "w"))
    # programs of one test are found again by the next, in a directory of
    # the test's own
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jaxcache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    yield root
    jax.config.update("jax_compilation_cache_dir", old[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old[1])


def _run(root, name, seed=3, traced=False):
    import time
    from benchmark import run
    cell = cells.Cell(name, root=root)
    for k, v in cell.traffic.get("env", {}).items():
        os.environ[k] = str(v)           # as run.main does; see no_env
    return run.run_cell(cell, seed, 0.2, traced,
                        t_process=time.perf_counter())


@pytest.fixture()
def no_env():
    """A cell's traffic may set the program's switches in the environment
    (``MXNET_ZERO``); the next test starts without them."""
    before = dict(os.environ)
    yield
    for k in set(os.environ) - set(before):
        del os.environ[k]
    os.environ.update(before)


@pytest.mark.parametrize("name", CELLS)
def test_the_program_agrees_with_the_plain_reference(tiny, no_env, name):
    """The harness's own run, after its look for a chip: the entry, the
    first steps, the window, the reference, the result line."""
    result, nums = _run(tiny, name)
    assert result["correct"] is True, result["checks"]
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(TINY_LIMITS[name])
    assert all(v <= lim for v, lim in result["checks"].values())
    assert {"correct", "attempted", "failed", "metrics", "device"} \
        <= set(result)
    assert set(result["metrics"]) == {"train_items_per_s", "peak_hbm_gib",
                                      "setup_s"}
    win = result["window"]
    assert result["attempted"] == win["steps"] > 0
    assert result["metrics"]["train_items_per_s"]["value"] > 0
    assert win["compiled_in_window"] == 0
    assert len(result["intervals"]) >= 1
    assert sum(result["intervals"]) == pytest.approx(win["seconds"],
                                                     rel=1e-2)
    n = {"opt-1.3b-steps": 2, "resnet50-fit": 3}[name]
    assert {"loss%d" % n, "mom%d_med" % n, "mom%d_worst" % n,
            "change%d_p90" % n, "change%d_wworst" % n} <= set(nums)
    # a scan chunk hands back no state after one step
    assert ("grad1_med" in nums) == (name == "resnet50-fit")


def _broken(monkeypatch, fault):
    """Break the timed path underneath the harness: the program's
    ``TrainStep`` either hands its state back unchanged, or sees only the
    first half of every batch (repeated, so that the mean is over that
    half)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.train import TrainStep
    real_call, real_many = TrainStep.__call__, TrainStep.run_steps
    parts = {"half": 2}.get(fault)

    def half(batch, axis):
        def cut(x):
            x = jnp.asarray(x)
            n = x.shape[axis] // parts
            first = jax.lax.slice_in_dim(x, 0, n, axis=axis)
            return jax.device_put(
                jnp.concatenate([first] * parts, axis=axis), x.sharding)
        return {k: cut(v) for k, v in batch.items()}

    def keep(tree):
        return jax.tree_util.tree_map(jnp.copy, tree)

    def call(self, params, opt_state, aux, batch, rng=None):
        if parts:
            batch = half(batch, 0)
        saved = keep((params, opt_state, aux)) if fault == "unchanged" \
            else None
        out = real_call(self, params, opt_state, aux, batch, rng=rng)
        return saved + (out[3],) if saved else out

    def many(self, params, opt_state, aux, batch, num_steps, rng=None,
             stacked=False):
        if parts:
            batch = half(batch, 1 if stacked else 0)
        saved = keep((params, opt_state, aux)) if fault == "unchanged" \
            else None
        out = real_many(self, params, opt_state, aux, batch, num_steps,
                        rng=rng, stacked=stacked)
        return saved + (out[3],) if saved else out

    monkeypatch.setattr(TrainStep, "__call__", call)
    monkeypatch.setattr(TrainStep, "run_steps", many)


@pytest.mark.parametrize("fault", ["unchanged", "half"])
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(tiny, no_env, monkeypatch, name,
                                            fault):
    _broken(monkeypatch, fault)
    result, nums = _run(tiny, name)
    assert result["correct"] is False, result["checks"]
    first = next(iter(TINY_LIMITS[name]))
    assert nums[first][0] > 10 * TINY_LIMITS[name][first]


@pytest.mark.parametrize("name", CELLS)
def test_the_lower_precision_control_is_not_correct(tiny, name):
    """The reference in the program's place, its products in fp8: the
    comparison that passes the program has to fail it."""
    from benchmark import calibrate
    cell = cells.Cell(name, root=tiny)
    read = calibrate.readings(cell, 3, ["control"])["control"]
    ok, table = check.decide({k: (v, None) for k, v in read.items()},
                             cell.limits)
    assert ok is False, table
    first = next(iter(cell.limits))
    assert read[first] > 1.5 * cell.limits[first]


def test_calibrate_reads_the_program_on_seed_after_seed(tiny, no_env):
    """One program, loaded with seed after seed: the readings that a limit's
    lower end is set from, each the same as a run of that seed reads."""
    from benchmark import calibrate
    cell = cells.Cell("opt-1.3b-steps", root=tiny)
    program = calibrate.program_readings(cell, [3, 2 ** 31 + 5])
    for seed, seen in program.items():
        read = calibrate.readings(cell, seed, ["program", "half"],
                                  program=seen)
        nums = {k: (v, None) for k, v in read["program"].items()}
        assert check.decide(nums, cell.limits)[0] is True, read["program"]
        halved = {k: (v, None) for k, v in read["half"].items()}
        assert check.decide(halved, cell.limits)[0] is False
    _, nums = _run(tiny, "opt-1.3b-steps", seed=3)
    mine = calibrate.readings(cell, 3, ["program"],
                              program=program[3])["program"]
    assert mine["mom2_worst"] == pytest.approx(nums["mom2_worst"][0])


def test_decide_needs_every_number_its_limits_name():
    nums = {"loss1": (0.5, None)}
    assert check.decide(nums, {"loss1": 1.0}) == (True,
                                                  {"loss1": [0.5, 1.0]})
    assert check.decide(nums, {"loss1": 0.1})[0] is False
    with pytest.raises(KeyError):
        check.decide(nums, {"grad1_med": 1.0})


def test_numbers_by_hand():
    ref = {"loss": {1: 2.0}, "grad": {"a": 1.0, "b": 2.0, "c": 1e-9},
           "moment": {"a": 1.0, "b": 2.0, "c": 4.0},
           "change": {"a": 0.1, "b": 0.2, "c": 0.3}}
    prog = {"loss": {1: 2.2}, "grad": {"a": 1.5, "b": 2.0, "c": 0.5},
            "moment": {"a": 1.0, "b": 3.0, "c": 4.0},
            "change": {"a": 0.1, "b": 0.1, "c": 9.0}}
    nums = check.numbers(prog, ref)
    assert nums["loss1"][0] == pytest.approx(0.1)
    assert nums["mom1_worst"] == (pytest.approx(0.5), "b")
    # an entry that cannot stop after one step reads no first gradient
    chunk = {k: v for k, v in prog.items() if k != "grad"}
    assert "grad1_worst" not in check.numbers(chunk, ref)
    assert "mom1_worst" in check.numbers(chunk, ref)
    # c's reference gradient is nought: it is held against the median
    # leaf's (1.0) for the gradient, and left out of the change
    assert nums["grad1_worst"] == (pytest.approx(0.5), "a")
    assert nums["change1_worst"] == (pytest.approx(0.5), "b")
    # of the operands of products alone (a and the parts of b), the median
    ones = {"a": 1.0, "b#0": 1.0, "b#1": 1.0, "c": 1.0}
    parts = {"loss": {1: 2.0}, "grad": {"a": 1.0, "b#0": 2.0, "b#1": 4.0,
                                        "c": 1.0},
             "moment": ones, "change": ones}
    moved = {"loss": {1: 2.0}, "grad": {"a": 1.1, "b#0": 2.4, "b#1": 5.2,
                                        "c": 9.0},
             "moment": ones,
             "change": {"a": 1.0, "b#0": 1.0, "b#1": 1.5, "c": 1.0}}
    nums = check.numbers(moved, parts, matrices=["a", "b"])
    assert nums["grad1_wmed"] == (pytest.approx(0.2), "b#0")
    assert nums["grad1_wworst"] == (pytest.approx(0.3), "b#1")
    assert nums["change1_wworst"] == (pytest.approx(0.5), "b#1")
    assert nums["grad1_worst"][1] == "c"
    assert nums["change1_wmed"][0] == 0.0
    assert "grad1_wmed" not in check.numbers(moved, parts)
    prog["grad"].pop("b")
    assert check.numbers(prog, ref)["grad1_worst"][0] == float("inf")


# ----------------------------------------------------------- what a seed is
def test_the_same_seed_gives_the_same_inputs_and_large_seeds_work():
    import numpy as np
    big = 2 ** 31 + 12345
    x1, y1 = gen.device_images(big, 2, 16, (3, 8, 8), 10)
    x2, y2 = gen.device_images(big, 2, 16, (3, 8, 8), 10)
    x3, _ = gen.device_images(big + 1, 2, 16, (3, 8, 8), 10)
    assert np.array_equal(x1, x2) and np.array_equal(y1, y2)
    assert not np.array_equal(x1, x3)
    assert x1.shape == (2, 16, 3, 8, 8) and y1.shape == (2, 16)
    assert x1.min() >= -1 and x1.max() < 1 \
        and len(np.unique(np.asarray(x1)[:, :, 0, 0, 0])) == 32
    shapes = {"a_weight": (4, 3), "b_gamma": (3,), "c_bias": (3,),
              "d_weight": (2, 3, 3, 3)}
    init = {"matrix_std": 0.02, "beta_bias_std": 0.02}
    w1 = gen.make_weights(shapes, init, big)
    w2 = gen.make_weights(shapes, init, big)
    w3 = gen.make_weights(shapes, init, big - 2 ** 31)
    assert all(np.array_equal(w1[k], w2[k]) for k in shapes)
    assert not np.array_equal(w1["a_weight"], w3["a_weight"])
    d1, l1 = gen.device_tokens(big, 3, 2, 8, 50)
    assert d1.shape == (3, 2, 8) and l1.shape == (3, 2, 8)
    assert np.array_equal(np.asarray(d1)[:, :, 1:],
                          np.asarray(l1)[:, :, :-1].astype(np.int32))
