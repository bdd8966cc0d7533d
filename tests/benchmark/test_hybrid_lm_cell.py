"""The cell ``nemotron-twotower-steps-t4096`` on the CPU at a tiny size, as
``test_benchmark.py`` does for the first two cells: its required FLOPs and
bytes by hand, its readers (device time by named scope from a trace
recorded on the chip, the expert layer's counters), and what decides
``correct``: the harness's own run agrees with the plain reference, the fp8
control and a broken timed path do not."""
import collections
import copy
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from benchmark import cells, check  # noqa: E402
from benchmark.flops import hybrid_lm as lm_flops  # noqa: E402
from benchmark.flops import moe as moe_flops, ssd as ssd_flops  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "nemotron-twotower-steps-t4096"
CONFIG = "benchmark/configs/nemotron-twotower-30b-a3b.json"
CFG = json.load(open(os.path.join(ROOT, CONFIG)))


# ------------------------------------------------------------ required FLOPs
def test_mamba_layer_flops_by_hand():
    # in_proj 2688 -> 4096 + 6144 + 64, out_proj 4096 -> 2688, 4 taps on the
    # 6144 channels of xBC, 4 FLOPs a state element (64 heads x 64 x 128)
    want = 2 * 2688 * 10304 + 2 * 4096 * 2688 + 2 * 6144 * 4 \
        + 4 * 64 * 64 * 128
    assert lm_flops.mamba_flops(CFG) == want == 79560704


def test_expert_layer_flops_by_hand():
    # router 128 outputs; shared expert of 3712; 6 of 128 experts a token of
    # which 8 are held: 0.375 routed experts a token, 2 products of
    # 2688 x 1856 each
    want = 2 * 2688 * 128 + 4 * 2688 * 3712 + 0.375 * 4 * 2688 * 1856
    assert lm_flops.expert_flops(CFG) == want


def test_attention_layer_flops_by_hand():
    # q 32 heads, k and v 2 heads of 128, o_proj; QK^T and PV at T = 4096
    # under the causal mask: half of 2 x 2 T D a query head
    proj = 2 * 2688 * 128 * (32 + 4) + 2 * 4096 * 2688
    assert lm_flops.attention_flops(CFG, 4096) \
        == proj + 2 * 4096 * 128 * 32


def test_step_flops_is_three_forwards_of_the_nine_layers_and_the_head():
    token = 4 * lm_flops.mamba_flops(CFG) + 4 * lm_flops.expert_flops(CFG) \
        + lm_flops.attention_flops(CFG, 4096) + 2 * 2688 * 16384
    assert lm_flops.step_flops(CFG, 1) == 3 * 4096 * token
    assert 8.3e12 < lm_flops.step_flops(CFG, 1) < 8.4e12
    assert lm_flops.items_per_step(CFG, 1) == 4096
    # the new mechanisms are about three quarters of it
    new = 4 * lm_flops.mamba_flops(CFG) + 4 * lm_flops.expert_flops(CFG)
    assert 0.7 < new / token < 0.8


def test_ssd_flops_and_bytes_by_hand():
    forward = 2 * 6144 * 4 + 4 * 64 * 64 * 128
    assert ssd_flops.ssd_flops(CFG, 4096) == 3 * 4096 * forward
    ins = 6144 + 64 + 4096                       # xBC, dt, z
    assert ssd_flops.ssd_bytes(CFG, 4096) \
        == 4096 * 2 * ((ins + 4096) + (ins + 4096 + ins))
    sec, bound = ssd_flops.least_seconds(CFG, 4096, 197e12, 819e9)
    assert bound == "bytes" and sec == pytest.approx(
        ssd_flops.ssd_bytes(CFG, 4096) / 819e9)


def test_routed_expert_flops_and_bytes_by_hand():
    assert moe_flops.routed_flops(CFG, 1536) == 3 * 1536 * 4 * 2688 * 1856
    weights = 8 * 2 * 2688 * 1856
    assert moe_flops.routed_bytes(CFG, 1536) \
        == 2 * (3 * weights + 3 * 1536 * 2 * 2688)
    # at a chip's share the held experts' weights bound it, not the FLOPs
    assert moe_flops.least_seconds(CFG, 1536, 197e12, 819e9)[1] == "bytes"
    assert moe_flops.least_seconds(CFG, 40000, 197e12, 819e9)[1] == "flops"


def test_the_configuration_states_its_cut():
    """Published widths unchanged; each reduced key beside its published
    value; the deployment; the second tower and the rotary under assumed."""
    catalog_widths = {
        "hidden_size": 2688, "head_dim": 128, "mamba_head_dim": 64,
        "mamba_num_heads": 64, "ssm_state_size": 128, "n_groups": 8,
        "conv_kernel": 4, "chunk_size": 128, "intermediate_size": 1856,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "num_attention_heads": 32, "num_key_value_heads": 2,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
        "expand": 2, "n_shared_experts": 1}
    for k, v in catalog_widths.items():
        assert CFG[k] == v and k not in CFG["reduced"], k
    assert set(CFG["reduced"]) == set(CFG["published"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "max_position_embeddings"}
    assert CFG["published"]["hybrid_override_pattern"].startswith(
        CFG["hybrid_override_pattern"])
    assert len(CFG["hybrid_override_pattern"]) == CFG["num_hidden_layers"]
    assert CFG["deployment"]["chips_sharing_a_layer"] == 16
    assumed = " ".join(CFG["assumed"])
    assert "second tower" in assumed and "rotary" in assumed
    args = CFG["symbol"]["args"]
    assert args["num_experts"] == 128 and args["experts_held"] == 8
    # the reference's leaves at this size: 667M parameters
    from benchmark.reference import hybrid_lm as ref
    count = sum(int(np.prod(s)) for s in ref.param_shapes(CFG).values())
    assert 666e6 < count < 668e6


# ------------------------------------------------------- readers: by scope
PROBE = os.path.join(HERE, "scopes_probe.xplane.pb")


def test_framework_names_of_a_trace_recorded_on_the_chip():
    """``scopes_probe.xplane.pb``: three runs of a jitted gradient with two
    named scopes, traced on a v5e (PR 29).  The dots keep their names."""
    from benchmark.readers import scopes
    names = scopes.framework_names(PROBE)
    assert list(names) == ["/device:TPU:0"]
    by_op = {k.split(" = ")[0]: v for k, v in names["/device:TPU:0"].items()}
    assert by_op["%fusion.5"] == "jit(f)/jvp(alpha_scope)/dot_general:"
    assert by_op["%fusion.12"] \
        == "jit(f)/transpose(jvp(alpha_scope))/dot_general:"
    assert by_op["%copy.6"] == "jit(f)/jvp(beta_scope)/exp:"
    assert "%reduce-window.1" not in by_op          # the compiler's own


def test_scope_seconds_of_the_recorded_trace():
    import jax
    from benchmark.readers import scopes
    names = scopes.framework_names(PROBE)
    profile = jax.profiler.ProfileData.from_file(PROBE)
    alpha, n_alpha = scopes.scope_seconds(profile, names, ["alpha_scope"],
                                          "0", 0.0, 1e9)
    # three dots a run (23.8, 25.6 and 26.0 us), three runs
    assert n_alpha >= 9 and alpha == pytest.approx(226.2e-6, rel=0.01)
    beta, _ = scopes.scope_seconds(profile, names, ["beta_scope"], "0", 0.0,
                                   1e9)
    both, _ = scopes.scope_seconds(profile, names,
                                   ["alpha_scope", "beta_scope"], "0", 0.0,
                                   1e9)
    assert both == pytest.approx(alpha + beta)
    # a scope is a whole component of the path, and a window cuts
    assert scopes.scope_seconds(profile, names, ["alpha"], "0", 0.0,
                                1e9) == (0.0, 0)
    assert scopes.scope_seconds(profile, names, ["alpha_scope"], "0", 0.0,
                                1e-9)[1] == 0
    assert scopes.scope_seconds(profile, names, ["alpha_scope"], "1", 0.0,
                                1e9) == (0.0, 0)


def _fake_profile(ops):
    """A profile of one device whose operations are (name, start ns,
    duration ns)."""
    Event = collections.namedtuple("Event", "name start_ns duration_ns")
    Line = collections.namedtuple("Line", "name events")
    Plane = collections.namedtuple("Plane", "name lines")
    Profile = collections.namedtuple("Profile", "planes")
    return Profile([Plane("/device:TPU:0", [Line("XLA Ops", [
        Event("%%%s = f32[8]{0} fusion(f32[8]{0} %%p)" % n, a, d)
        for n, a, d in ops])])])


def test_an_unnamed_operation_counts_between_two_of_the_scope():
    from benchmark.readers import scopes
    ops = [("a", 0, 10), ("u1", 10, 5), ("b", 15, 10), ("u2", 25, 5),
           ("c", 30, 10), ("u3", 40, 5), ("u4", 45, 5), ("d", 50, 10)]
    profile = _fake_profile(ops)
    line = profile.planes[0].lines[0]
    full = {e.name.split(" = ")[0].lstrip("%"): e.name for e in line.events}
    names = {"/device:TPU:0": {
        full["a"]: "jit(s)/jvp(mamba_ssd)/dot_general",
        full["b"]: "jit(s)/transpose(jvp(mamba_ssd))/mul",
        full["c"]: "jit(s)/moe_shared/dot_general",
        full["d"]: "jit(s)/while/body/mamba_conv/add"}}
    sec, n = scopes.scope_seconds(profile, names, ["mamba_ssd"], "0", 0, 1)
    assert (n, sec) == (3, pytest.approx(25e-9))      # a, u1, b
    sec, n = scopes.scope_seconds(profile, names,
                                  ["mamba_conv", "mamba_ssd"], "0", 0, 1)
    assert (n, sec) == (4, pytest.approx(35e-9))      # and d; u2-u4 border
    # a loop's own event spans its body's and is left out
    held = _fake_profile([("a", 0, 10)])
    ev = held.planes[0].lines[0].events[0]
    loop = ev._replace(name="%while.1 = (f32[8]{0}) while((f32[8]{0}) %t)")
    held.planes[0].lines[0].events.append(loop)
    names["/device:TPU:0"][loop.name] = "jit(s)/mamba_ssd/while"
    names["/device:TPU:0"][ev.name] = "jit(s)/mamba_ssd/dot_general"
    assert scopes.scope_seconds(held, names, ["mamba_ssd"], "0", 0, 1)[1] == 1


def _ctx(root=ROOT, **kw):
    from benchmark import run
    cell = cells.Cell(NAME, root=root)
    return run.Context(cell=cell, peaks=cell.peaks("TPU v5 lite"), chips=1,
                       plain={"window": [0.0, 1.0]},
                       reduced={"steps": 8, "slowest": "0"}, **kw)


def test_the_scope_metrics_read_nothing_without_a_trace_or_counters(
        monkeypatch):
    """What the parent's program gives: no trace file under the cell's
    directory, no device counters in telemetry."""
    from benchmark.readers import moe, scopes
    from mxnet_tpu import telemetry
    monkeypatch.setattr(telemetry, "_dev_recent", collections.deque())
    ctx = _ctx(profile=None)
    for metric in ("ssm.scan_ms", "ssd_scan_roofline", "moe.experts_ms",
                   "moe_experts_roofline", "moe.load_max_over_mean",
                   "moe.dropped_tokens"):
        fn, args = ctx.cell.reader(metric)
        assert fn(ctx, **args) is None, metric
    monkeypatch.delattr(telemetry, "device_counters")
    assert moe.counted(ctx) == (None, 0)
    assert scopes.moe_experts_roofline(ctx, ["moe_experts"]) is None


def test_the_counter_metrics_by_hand(monkeypatch):
    from benchmark.readers import moe
    from mxnet_tpu import telemetry
    # two expert layers over the window's 8 steps, two chunks of 4: [landed,
    # fullest, absent, uncomputed]; an older chunk is not the window's
    counted = np.array([[12288.0, 2304.0, 184320.0, 0.0],
                        [10240.0, 5120.0, 186368.0, 0.0]], np.float32)
    monkeypatch.setattr(telemetry, "_dev_recent", collections.deque(
        [({"moe": 7 * counted}, 4), ({"moe": 0.25 * counted}, 4),
         ({"moe": 0.75 * counted}, 4)]))
    ctx = _ctx(profile=None)
    assert moe.counted(ctx)[1] == 8
    # layer 1: 2304 / (12288 / 8) = 1.5; layer 2: 5120 / 1280 = 4
    assert moe.load_max_over_mean(ctx) == pytest.approx(2.75)
    assert moe.dropped_tokens(ctx) == 0.0
    telemetry.publish_device_counters(
        {"moe": np.array([[0, 0, 0, 0], [0, 0, 0, 3.0]], np.float32)}, 8)
    assert moe.dropped_tokens(ctx) == 3.0


def test_the_rooflines_by_hand(monkeypatch):
    from benchmark.readers import scopes
    from mxnet_tpu import telemetry
    ctx = _ctx(profile=None)
    monkeypatch.setattr(scopes, "_seconds_a_step", lambda ctx, s: 0.04)
    least = ssd_flops.least_seconds(CFG, 4096, 197e12, 819e9)[0]
    assert scopes.ssd_scan_roofline(ctx, ["mamba_ssd"]) \
        == pytest.approx(100 * 4 * least / 0.04)
    assert scopes.scope_ms(ctx, ["mamba_ssd"]) == pytest.approx(40.0)
    counted = np.array([[12288.0, 0, 0, 0]] * 4, np.float32)
    monkeypatch.setattr(telemetry, "_dev_recent",
                        collections.deque([({"moe": counted}, 8)]))
    one = moe_flops.least_seconds(CFG, 1536.0, 197e12, 819e9)[0]
    assert scopes.moe_experts_roofline(ctx, ["moe_experts"]) \
        == pytest.approx(100 * 4 * one / 0.04)


# ------------------------------------------------- what decides ``correct``
# Set from readings at this size on the CPU in bfloat16 (PR 29, seeds 3, 4, 5
# and 2**31 + 7): the program reads mom2_med <= 0.00246 and mom2_p90 <=
# 0.0172; the fp8 control mom2_med >= 0.00778 (0.00778 on seed 3); half a
# batch mom2_med >= 0.26 and mom2_p90 >= 0.44; a state left unchanged 1.
TINY_LIMITS = {"mom2_med": 0.0045, "mom2_p90": 0.03}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark whose new configuration and traffic are cut
    to what the CPU can run: same files, same loader, same entry.  Every
    kind of layer, T = 32 over chunks of 16, 4 of 8 experts held, two
    sequences a step so that half a batch is a batch."""
    import jax
    root = str(tmp_path_factory.mktemp("tinyhybrid"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(root, "benchmark")

    def edit(rel, fn):
        path = os.path.join(b, rel)
        body = json.load(open(path))
        fn(body)
        json.dump(body, open(path, "w"))

    def small(c):
        c["symbol"]["args"].update(
            pattern="ME*E", vocab_size=64, seq_len=32, num_hidden=32,
            ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=8,
            chunk_size=16, num_heads=4, num_kv_heads=2, head_dim=8,
            num_experts=8, experts_held=4, first_expert=0, expert_hidden=16,
            shared_hidden=32, experts_per_token=2)
        c.update(hybrid_override_pattern="ME*E", num_hidden_layers=4,
                 vocab_size=64, max_position_embeddings=32, hidden_size=32,
                 mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
                 ssm_state_size=8, chunk_size=16, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=8, n_routed_experts=4,
                 moe_intermediate_size=16,
                 moe_shared_expert_intermediate_size=32,
                 num_experts_per_tok=2)
        c["published"]["n_routed_experts"] = 8
        c["init"]["matrix_std"] = 0.1
    edit("configs/nemotron-twotower-30b-a3b.json", small)
    edit("traffic/steps-b1.json", lambda t: t.update(
        batch=2, chunk=2, pool_chunks=2, warmup_chunks=1))
    json.dump(TINY_LIMITS, open(os.path.join(b, "limits", NAME + ".json"),
                                "w"))
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jaxcache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    yield root
    jax.config.update("jax_compilation_cache_dir", old[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old[1])


def _run(root, seed=3):
    import time
    from benchmark import run
    cell = cells.Cell(NAME, root=root)
    return run.run_cell(cell, seed, 0.2, False,
                        t_process=time.perf_counter())


def test_the_program_agrees_with_the_plain_reference(tiny):
    from mxnet_tpu import telemetry
    result, nums = _run(tiny)
    assert result["correct"] is True, result["checks"]
    assert set(result["checks"]) == set(TINY_LIMITS)
    assert set(result["metrics"]) == {"train_items_per_s", "peak_hbm_gib",
                                      "setup_s"}
    win = result["window"]
    assert result["attempted"] == win["steps"] > 0
    assert win["compiled_in_window"] == 0
    assert result["metrics"]["train_items_per_s"]["value"] > 0
    assert {"loss2", "mom2_worst", "change2_wmed"} <= set(nums)
    assert "grad1_med" not in nums
    # the window's last chunk left its counters: two expert layers, the
    # chunk's two steps of 2 x 32 tokens choosing 2 experts each
    counted, steps = telemetry.device_counters()
    assert steps == 2 and counted["moe"].shape == (2, 4)
    assert (counted["moe"][:, 0] + counted["moe"][:, 2] == 256).all()
    assert not counted["moe"][:, 3].any()
    from benchmark.readers import moe
    ctx = _ctx(root=tiny, profile=None)
    assert 1.0 <= moe.load_max_over_mean(ctx) <= 4.0
    assert moe.dropped_tokens(ctx) == 0.0


@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_a_broken_timed_path_is_not_correct(tiny, monkeypatch, fault):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.train import TrainStep
    real = TrainStep.run_steps

    def many(self, params, opt_state, aux, batch, num_steps, rng=None,
             stacked=False):
        if fault == "half":          # the first sequence of each step, twice
            batch = {k: jnp.concatenate([v[:, :1]] * 2, axis=1)
                     for k, v in batch.items()}
        saved = jax.tree_util.tree_map(jnp.copy, (params, opt_state, aux)) \
            if fault == "unchanged" else None
        out = real(self, params, opt_state, aux, batch, num_steps, rng=rng,
                   stacked=stacked)
        return saved + (out[3],) if saved else out
    monkeypatch.setattr(TrainStep, "run_steps", many)
    result, nums = _run(tiny)
    assert result["correct"] is False, result["checks"]
    assert nums["mom2_med"][0] > 10 * TINY_LIMITS["mom2_med"]


def test_the_lower_precision_control_is_not_correct(tiny):
    from benchmark import calibrate
    cell = cells.Cell(NAME, root=tiny)
    read = calibrate.readings(cell, 3, ["control"])["control"]
    ok, table = check.decide({k: (v, None) for k, v in read.items()},
                             cell.limits)
    assert ok is False, table
    assert read["mom2_med"] > 1.5 * TINY_LIMITS["mom2_med"]


def test_the_reference_is_float32_and_takes_the_recurrence():
    """The reference imports nothing of the program and does not use the
    program's chunked algorithm."""
    src = open(os.path.join(ROOT, "benchmark/reference/hybrid_lm.py")).read()
    assert "mxnet_tpu" not in src.split('"""', 2)[2]
    assert "lax.scan(step" in src and "cumsum" not in src
    assert "Precision.HIGHEST" in src
