"""Pipeline schedule v2: 1F1B + interleaved virtual stages + overlapped dp
gradient communication.

Pins, on the 8-device virtual CPU mesh:
- the schedule tables (parallel/schedule.py): complete/topological orders,
  simulated bubble == the closed form for every (schedule, pp, M, v), the
  1F1B boundary-stash bound (pp, not M);
- training parity of 1f1b and interleaved vs the GPipe schedule AND the
  single-program TrainStep at f32 2e-5 (pp2/pp4, M=4, dp2 x pp4 — the
  overlapped bucketed gradient path included);
- composition: AMP overflow-skip under 1f1b, ZeRO-1 sharded updates per
  schedule (the bucket-consuming update), BN microbatch semantics, the
  live-bytes-bounded-by-pp memory pin, checkpoint save-under-1f1b /
  restore-under-gpipe (and pp4 -> pp2) via the any-topology matrix;
- fit dispatch (MXNET_PP_SCHEDULE / MXNET_PP_INTERLEAVE read once, cache
  keyed), schedule-tagged telemetry + the agg fold, the run_compare
  identity contract, and mxsan cleanliness of the overlap path.
"""
import json

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import amp
from mxnet_tpu import sanitize as san
from mxnet_tpu import telemetry as tel
from mxnet_tpu import checkpoint as ckpt
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import schedule as sch
from mxnet_tpu.parallel.mesh import make_pp_mesh
from mxnet_tpu.train import (TrainStep, PipelineTrainStep,
                             pipeline_bubble_fraction)

RTOL, ATOL = 2e-5, 1e-6
BATCH = 8


def _mlp(classes=8):
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, name="fc1", num_hidden=16)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, name="fc2", num_hidden=16)
    h = mx.sym.Activation(h, act_type="tanh")
    h = mx.sym.FullyConnected(h, name="fc3", num_hidden=classes)
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _deep_mlp(classes=8, depth=6):
    # enough ops for pp4 x v2 = 8 virtual stages
    h = mx.sym.Variable("data")
    for i in range(depth):
        h = mx.sym.FullyConnected(h, name="fc%d" % i, num_hidden=16)
        h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, name="fc_out", num_hidden=classes)
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _convnet(classes=4):
    d = mx.sym.Variable("data")
    h = mx.sym.Convolution(d, name="c1", num_filter=8, kernel=(3, 3),
                           pad=(1, 1), no_bias=True)
    h = mx.sym.BatchNorm(h, name="bn1", fix_gamma=False)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.Convolution(h, name="c2", num_filter=8, kernel=(3, 3),
                           pad=(1, 1), no_bias=True)
    h = mx.sym.BatchNorm(h, name="bn2", fix_gamma=False)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.Pooling(h, global_pool=True, pool_type="avg", kernel=(1, 1))
    h = mx.sym.Flatten(h)
    h = mx.sym.FullyConnected(h, name="fc", num_hidden=classes)
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _mlp_batch(seed=0, classes=8, batch=BATCH):
    rs = np.random.RandomState(seed)
    return {"data": rs.uniform(-1, 1, (batch, 32)).astype(np.float32),
            "softmax_label": rs.randint(0, classes,
                                        (batch,)).astype(np.float32)}


def _conv_batch(seed=0, classes=4):
    rs = np.random.RandomState(seed)
    return {"data": rs.uniform(-1, 1, (BATCH, 3, 8, 8)).astype(np.float32),
            "softmax_label": rs.randint(0, classes,
                                        (BATCH,)).astype(np.float32)}


def _opt(batch=BATCH):
    return mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                            rescale_grad=1.0 / batch)


MLP_SHAPES = ({"data": (BATCH, 32)}, {"softmax_label": (BATCH,)})
CONV_SHAPES = ({"data": (BATCH, 3, 8, 8)}, {"softmax_label": (BATCH,)})


def _ref_steps(net, batch, shapes, n=2, policy=None, key=7):
    ts = TrainStep(net, _opt(), policy=policy)
    p, s, a = ts.init(*shapes)
    b = ts.shard_batch(batch)
    rng = jax.random.PRNGKey(key)
    for _ in range(n):
        p, s, a, o = ts(p, s, a, b, rng=rng)
    return ts, p, a, o


def _pp_steps(net, batch, shapes, pp, dp=1, M=2, n=2, policy=None,
              zero=False, schedule="gpipe", interleave=None, key=7):
    mesh = make_pp_mesh(pp, dp=dp, devices=jax.devices()[:pp * dp])
    ts = PipelineTrainStep(net, _opt(), mesh=mesh, num_microbatches=M,
                           policy=policy, zero=zero, schedule=schedule,
                           interleave=interleave)
    p, s, a = ts.init(*shapes)
    rng = jax.random.PRNGKey(key)
    for _ in range(n):
        p, s, a, o = ts(p, s, a, batch, rng=rng)
    return ts, p, s, a, o


def _close(got, want, rtol=RTOL, atol=ATOL, what=""):
    for n in sorted(want):
        np.testing.assert_allclose(np.asarray(got[n]), np.asarray(want[n]),
                                   rtol=rtol, atol=atol,
                                   err_msg="%s: %s" % (what, n))


# ---------------------------------------------------------- schedule tables
@pytest.mark.parametrize("schedule,v", [("gpipe", 1), ("1f1b", 1),
                                        ("interleaved", 2),
                                        ("interleaved", 3)])
@pytest.mark.parametrize("pp,M", [(1, 4), (2, 2), (2, 8), (4, 4), (4, 8)])
def test_simulated_bubble_matches_closed_form(schedule, v, pp, M):
    if schedule == "interleaved" and M % pp:
        pytest.skip("interleaved needs M %% pp == 0")
    orders = sch.stage_orders(pp, M, schedule, v)
    items, sim = sch.dispatch_order(orders, pp, v)
    want = pipeline_bubble_fraction(pp, M, v)
    assert sim["bubble"] == pytest.approx(want, abs=1e-12)
    # every (kind, m, virtual stage) item exactly once, on its own slice
    V = pp * v
    expect = {(k, m, s) for k in ("fwd", "bwd") for m in range(M)
              for s in range(V)}
    assert set(items) == expect and len(items) == len(expect)
    for d, order in enumerate(orders):
        assert all(k % pp == d for _, _, k in order)


def test_dispatch_order_is_topological():
    for schedule, v in (("gpipe", 1), ("1f1b", 1), ("interleaved", 2)):
        pp, M = 4, 4
        V = pp * v
        items, _ = sch.dispatch_order(sch.stage_orders(pp, M, schedule, v),
                                      pp, v)
        done = set()
        for kind, m, k in items:
            if kind == "fwd":
                assert k == 0 or ("fwd", m, k - 1) in done
            else:
                assert ("fwd", m, k) in done
                assert k == V - 1 or ("bwd", m, k + 1) in done
            done.add((kind, m, k))


def test_1f1b_stash_bounded_by_pp_gpipe_by_m():
    for pp, M in ((2, 8), (4, 8)):
        for schedule, bound in (("1f1b", pp), ("gpipe", M)):
            items, _ = sch.dispatch_order(
                sch.stage_orders(pp, M, schedule), pp)
            live, peak = {}, {}
            for kind, m, k in items:
                d = k % pp
                live[d] = live.get(d, 0) + (1 if kind == "fwd" else -1)
                peak[d] = max(peak.get(d, 0), live[d])
            assert max(peak.values()) == bound, (schedule, pp, M, peak)


def test_schedule_validation_errors():
    with pytest.raises(MXNetError, match="MXNET_PP_SCHEDULE"):
        sch.validate_schedule("zigzag", 2, 4, 1)
    with pytest.raises(MXNetError, match="interleaved"):
        sch.validate_schedule("1f1b", 2, 4, 2)
    with pytest.raises(MXNetError, match="interleave"):
        sch.validate_schedule("interleaved", 2, 4, 1)
    with pytest.raises(MXNetError, match="divisible"):
        sch.validate_schedule("interleaved", 4, 6, 2)
    # and through the step constructor (ctor-time, not first-step-time)
    mesh = make_pp_mesh(2, dp=1, devices=jax.devices()[:2])
    with pytest.raises(MXNetError, match="divisible"):
        PipelineTrainStep(_mlp(), _opt(), mesh=mesh, num_microbatches=3,
                          schedule="interleaved", interleave=2)
    with pytest.raises(MXNetError, match="MXNET_PP_SCHEDULE"):
        PipelineTrainStep(_mlp(), _opt(), mesh=mesh, schedule="bogus")


def test_bubble_fraction_generalised():
    assert pipeline_bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert pipeline_bubble_fraction(4, 4, 2) == pytest.approx(3 / 11)
    assert pipeline_bubble_fraction(4, 4, 4) == pytest.approx(3 / 19)
    assert pipeline_bubble_fraction(1, 4, 2) == 0.0
    # interleaving strictly shrinks the bubble at fixed (pp, M)
    fr = [pipeline_bubble_fraction(4, 4, v) for v in (1, 2, 3, 4)]
    assert fr == sorted(fr, reverse=True)


# ------------------------------------------------------------------- parity
@pytest.mark.parametrize("schedule,v,pp,dp,M", [
    ("1f1b", None, 2, 1, 4),
    ("1f1b", None, 4, 1, 4),
    ("1f1b", None, 4, 2, 4),      # dp x pp: the overlapped-comm path
    ("interleaved", 2, 2, 1, 4),
    ("interleaved", 2, 2, 2, 4),  # overlap + virtual stages
])
def test_v2_parity_vs_gpipe_and_single(schedule, v, pp, dp, M):
    batch = _mlp_batch()
    _, p_ref, _, o_ref = _ref_steps(_mlp(), batch, MLP_SHAPES)
    _, p_g, _, _, _ = _pp_steps(_mlp(), batch, MLP_SHAPES, pp, dp=dp, M=M)
    ts, p, _, _, o = _pp_steps(_mlp(), batch, MLP_SHAPES, pp, dp=dp, M=M,
                               schedule=schedule, interleave=v)
    what = "%s v=%s pp=%d dp=%d M=%d" % (schedule, v, pp, dp, M)
    _close(p, p_ref, what=what + " vs single")
    _close(p, p_g, what=what + " vs gpipe")
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(o_ref[0]),
                               rtol=RTOL, atol=ATOL)
    assert ts.schedule() == (schedule, v or 1)
    assert len(ts.stages()) == pp * (v or 1)


def test_interleaved_deep_net_pp4():
    # pp4 x v2 = 8 virtual stages over a deeper net; slice d owns two
    # non-contiguous chunks
    batch = _mlp_batch()
    _, p_ref, _, _ = _ref_steps(_deep_mlp(), batch, MLP_SHAPES)
    ts, p, _, _, _ = _pp_steps(_deep_mlp(), batch, MLP_SHAPES, 4, M=4,
                               schedule="interleaved", interleave=2)
    _close(p, p_ref, what="interleaved pp4 v2")
    assert len(ts.stages()) == 8
    homes = {k: k % 4 for k in range(8)}
    for k, st in enumerate(ts.stages()):
        for n in st.params:
            sub = ts.param_sharding(n).mesh
            assert sub is ts._subs[homes[k]]


def test_1f1b_bn_microbatch_reference():
    # BN batch stats are per microbatch; the reordered 1f1b backward must
    # reproduce the same-microbatching pp=1 reference exactly like GPipe
    batch = _conv_batch()
    _, p1, _, a1, _ = _pp_steps(_convnet(), batch, CONV_SHAPES, 1, M=2)
    _, p, _, a, _ = _pp_steps(_convnet(), batch, CONV_SHAPES, 2, M=2,
                              schedule="1f1b")
    _close(p, p1, what="1f1b bn params")
    _close(a, a1, what="1f1b bn aux")


# ---------------------------------------------------------------------- AMP
@pytest.mark.parametrize("schedule,v,dp", [("1f1b", None, 1),
                                           ("1f1b", None, 2),
                                           ("interleaved", 2, 2)])
def test_amp_clean_parity_v2(schedule, v, dp):
    pol = lambda: amp.Policy(compute_dtype="float32", loss_scale=1024.0)
    batch = _mlp_batch()
    ts_r, p_ref, _, _ = _ref_steps(_mlp(), batch, MLP_SHAPES, policy=pol())
    ts_p, p, _, _, _ = _pp_steps(_mlp(), batch, MLP_SHAPES, 2, dp=dp, M=4,
                                 policy=pol(), schedule=schedule,
                                 interleave=v)
    _close(p, p_ref, what="amp %s" % schedule)
    assert ts_r.amp_stats() == ts_p.amp_stats() == (1024.0, 0)


def test_amp_overflow_skip_under_1f1b():
    pol = lambda: amp.Policy(compute_dtype="float32", loss_scale=1024.0)
    batch = _conv_batch()
    batch["data"][0, 0, 0, 0] = np.inf
    ts_r, p_ref, a_ref, _ = _ref_steps(_convnet(), batch, CONV_SHAPES,
                                       n=1, policy=pol())
    ts_p, p, _, a, _ = _pp_steps(_convnet(), batch, CONV_SHAPES, 2, dp=2,
                                 M=2, n=1, policy=pol(), schedule="1f1b")
    # the overflow rides the overlapped bucket: the gathered finite flag
    # still skips every stage's update and halves the scale exactly once
    assert ts_r.amp_stats() == ts_p.amp_stats() == (512.0, 1)
    for name in sorted(p_ref):
        np.testing.assert_array_equal(np.asarray(p[name]),
                                      np.asarray(p_ref[name]))
    for name in sorted(a_ref):
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(a_ref[name]))


# --------------------------------------------------------------------- ZeRO
def _assert_dp_sharded(state, dp=2):
    for n, st in state.items():
        for leaf in st:
            assert leaf.sharding.spec == jax.sharding.PartitionSpec("dp"), n
            assert {x.data.shape[0] for x in leaf.addressable_shards} \
                == {leaf.shape[0] // dp}, \
                "zero optimizer state is not dp-sharded: %s" % n


@pytest.mark.parametrize("classes", [8, 5])
@pytest.mark.parametrize("schedule,v", [("1f1b", None), ("interleaved", 2)])
def test_zero_sharded_update_per_schedule(schedule, v, classes):
    # the ZeRO update consumes the flat (dp, chunk) gradient bucket
    # directly — the stage's dp comm is done when its backward finishes;
    # a leaf kept in its shape takes its rows of the bucket as its part
    # of the leading axis (a head of 5 over a dp of 2 stays flat)
    batch = _mlp_batch(classes=classes)
    _, p_ref, _, _ = _ref_steps(_mlp(classes), batch, MLP_SHAPES)
    ts, p, s, _, _ = _pp_steps(_mlp(classes), batch, MLP_SHAPES, 2, dp=2,
                               M=4, zero=True, schedule=schedule,
                               interleave=v)
    _close(p, p_ref, what="zero %s" % schedule)
    _assert_dp_sharded(s)
    for n, st in s.items():
        want = tuple(p[n].shape) if p[n].shape[0] % 2 == 0 \
            else (2, -(-p[n].size // 2))
        assert all(tuple(leaf.shape) == want for leaf in st), n


def test_amp_zero_overlap_compose():
    # AMP x ZeRO-1 x 1f1b on a dp x pp mesh: the loss-scale unscale rides
    # the flat gradient bucket (acc * 1/S) before the sharded update
    pol = lambda: amp.Policy(compute_dtype="float32", loss_scale=1024.0)
    batch = _mlp_batch()
    ts_r, p_ref, _, _ = _ref_steps(_mlp(), batch, MLP_SHAPES, policy=pol())
    ts_p, p, s, _, _ = _pp_steps(_mlp(), batch, MLP_SHAPES, 2, dp=2, M=4,
                                 policy=pol(), zero=True, schedule="1f1b")
    _close(p, p_ref, what="amp+zero+1f1b")
    assert ts_r.amp_stats() == ts_p.amp_stats() == (1024.0, 0)
    _assert_dp_sharded(s)


# ---------------------------------------------------------------- live bytes
def test_live_bytes_bounded_by_pp():
    # fixed microbatch size (2 rows), growing M: under gpipe the peak
    # boundary stash grows with M; under 1f1b it is bounded by pp.
    def live(schedule, M):
        batch = _mlp_batch(batch=2 * M)
        shapes = ({"data": (2 * M, 32)}, {"softmax_label": (2 * M,)})
        ts, _, _, _, _ = _pp_steps(_mlp(), batch, shapes, 2, M=M, n=1,
                                   schedule=schedule)
        return ts.last_live_bytes

    g2, g8 = live("gpipe", 2), live("gpipe", 8)
    f2, f8 = live("1f1b", 2), live("1f1b", 8)
    assert g8[0] > g2[0], (g2, g8)           # gpipe stash grows with M
    assert f8[0] == f2[0], (f2, f8)          # 1f1b flat in M (bound: pp)
    assert f8[0] < g8[0], (f8, g8)


# --------------------------------------------------------------- checkpoint
def test_checkpoint_save_1f1b_restore_gpipe(tmp_path):
    # the schedule is a dispatch-order property, not a state property:
    # a 1f1b checkpoint restores under gpipe (and pp4 -> pp2) exactly
    batch = _mlp_batch()
    mesh = make_pp_mesh(4, dp=1, devices=jax.devices()[:4])
    ts = PipelineTrainStep(_mlp(), _opt(), mesh=mesh, num_microbatches=4,
                           schedule="1f1b")
    p, s, a = ts.init(*MLP_SHAPES)
    rng = jax.random.PRNGKey(7)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, batch, rng=rng)
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, batch, rng=rng)
    ref = {n: np.asarray(v) for n, v in p.items()}

    mesh2 = make_pp_mesh(2, dp=1, devices=jax.devices()[:2])
    ts2 = PipelineTrainStep(_mlp(), _opt(), mesh=mesh2, num_microbatches=4,
                           schedule="gpipe")
    p2, s2, a2, man = ckpt.restore_into(ts2, path)
    assert ts2.num_update == 2 and man["topology"]["pp"] == 4
    for _ in range(2):
        p2, s2, a2, _ = ts2(p2, s2, a2, batch, rng=rng)
    _close(p2, ref, what="1f1b pp4 -> gpipe pp2")

    # and back up: gpipe checkpoint resumed under interleaved
    ts3 = PipelineTrainStep(_mlp(), _opt(), mesh=mesh2, num_microbatches=4,
                            schedule="interleaved", interleave=2)
    p3, s3, a3, _ = ckpt.restore_into(ts3, path)
    for _ in range(2):
        p3, s3, a3, _ = ts3(p3, s3, a3, batch, rng=rng)
    _close(p3, ref, what="1f1b pp4 -> interleaved pp2")


# ------------------------------------------------------------- fit dispatch
def _fit_data(classes=4):
    rs = np.random.RandomState(0)
    X = rs.uniform(-1, 1, (64, 16)).astype(np.float32)
    W = rs.randn(16, classes)
    y = np.argmax(X @ W, axis=1).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=16, shuffle=False,
                             label_name="softmax_label")


def _fit_net(classes=4):
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, name="fc1", num_hidden=32)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, name="fc2", num_hidden=classes)
    return mx.sym.SoftmaxOutput(h, name="softmax")


def test_fit_dispatch_schedule_env(monkeypatch):
    monkeypatch.setenv("MXNET_PP", "2")
    monkeypatch.setenv("MXNET_PP_MICROBATCH", "2")
    monkeypatch.setenv("MXNET_PP_SCHEDULE", "1f1b")
    data = _fit_data()
    mod = mx.Module(_fit_net(), context=mx.cpu())
    mod.fit(data, num_epoch=4, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.init.Xavier(), eval_metric="acc")
    ts = mod._fused_ts_cache[1]
    assert isinstance(ts, PipelineTrainStep)
    assert ts.schedule() == ("1f1b", 1)
    data.reset()
    score = dict(mod.score(data, mx.metric.Accuracy()))
    assert score["accuracy"] > 0.8, score
    # toggling the schedule between fits rebuilds through the cache key
    monkeypatch.setenv("MXNET_PP_SCHEDULE", "interleaved")
    monkeypatch.setenv("MXNET_PP_INTERLEAVE", "2")
    data.reset()
    mod.fit(data, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    ts2 = mod._fused_ts_cache[1]
    assert ts2 is not ts and ts2.schedule() == ("interleaved", 2)
    # unset restores the gpipe default and rebuilds again
    monkeypatch.delenv("MXNET_PP_SCHEDULE")
    monkeypatch.delenv("MXNET_PP_INTERLEAVE")
    data.reset()
    mod.fit(data, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    assert mod._fused_ts_cache[1].schedule() == ("gpipe", 1)


# ---------------------------------------------------------------- telemetry
def test_schedule_tagged_signals(tmp_path):
    tel.start(str(tmp_path / "t.jsonl"))
    try:
        _pp_steps(_mlp(), _mlp_batch(), MLP_SHAPES, 2, M=4, n=1,
                  schedule="1f1b")
        evs = tel.events()
        stages = [e for e in evs if e.get("name") == "pp.stage"]
        assert stages and all(e["tags"]["schedule"] == "1f1b"
                              for e in stages)
        bub = [e for e in evs if e.get("name") == "pp.bubble"]
        assert bub[0]["tags"]["schedule"] == "1f1b"
        assert bub[0]["tags"]["interleave"] == 1
        g = tel.gauges()
        assert g["pp_bubble_fraction"] == pytest.approx(
            pipeline_bubble_fraction(2, 4))
    finally:
        tel.stop()


def test_agg_slow_stage_names_schedule(tmp_path, capsys):
    from tools import telemetry_agg as agg
    path = tmp_path / "t.jsonl.rank0"
    evs = []
    for step in range(20):
        for stage, dur in ((0, 4000.0), (1, 11900.0), (2, 4100.0)):
            evs.append({"type": "span", "name": "pp.stage",
                        "cat": "pipeline", "ts": step * 1e6, "dur": dur,
                        "tags": {"stage": stage, "microbatches": 4,
                                 "schedule": "1f1b"}})
    path.write_text("\n".join(json.dumps(e) for e in evs) + "\n")
    merged = agg.aggregate([str(path)])
    sk = merged["stage_skew"]
    assert sk["slowest_stage"] == "1@1f1b"
    assert sk["slowest_schedule"] == "1f1b"
    assert sk["slow_stage"] == "1@1f1b"
    assert sk["stages"]["1@1f1b"]["schedule"] == "1f1b"
    agg.render(merged)
    out = capsys.readouterr().out
    assert "SLOW STAGE" in out and "[schedule 1f1b]" in out


def test_agg_mixed_schedules_no_cross_group_verdict(tmp_path):
    # a mid-run schedule toggle must not fabricate a SLOW STAGE verdict
    # by comparing one schedule's warmup-skewed group against the other
    # schedule's steady state — skew is judged within a schedule group
    from tools import telemetry_agg as agg
    path = tmp_path / "t.jsonl.rank0"
    evs = []
    # two slow gpipe observations (compile warmup), then a long balanced
    # 1f1b steady state
    for stage, dur in ((0, 30000.0), (1, 30500.0)):
        evs.append({"type": "span", "name": "pp.stage", "cat": "pipeline",
                    "ts": 0.0, "dur": dur,
                    "tags": {"stage": stage, "schedule": "gpipe"}})
    for step in range(20):
        for stage in (0, 1):
            evs.append({"type": "span", "name": "pp.stage",
                        "cat": "pipeline", "ts": (step + 1) * 1e6,
                        "dur": 4000.0 + stage,
                        "tags": {"stage": stage, "schedule": "1f1b"}})
    path.write_text("\n".join(json.dumps(e) for e in evs) + "\n")
    sk = agg.aggregate([str(path)])["stage_skew"]
    # both groups are internally balanced: no verdict, even though the
    # gpipe means are 7x the 1f1b means
    assert sk["slow_stage"] is None, sk


# -------------------------------------------------------------- run_compare
def test_run_compare_schedule_identity_not_regression_pair(tmp_path):
    from tools import run_compare as rc

    def record(schedule, interleave, bubble, live_mb):
        return {"metric": "pp_ladder_bubble_fraction", "value": bubble,
                "unit": "bubble_fraction",
                "pipeline": {"pp_bubble_fraction": bubble,
                             "pp_live_bytes_max_mb": live_mb,
                             "config": {"pp": 4, "dp": 1,
                                        "microbatches": 4,
                                        "schedule": schedule,
                                        "interleave": interleave}}}
    a = tmp_path / "a.json"
    a.write_text(json.dumps(record("1f1b", 1, 0.43, 10.0)))
    worse_same = tmp_path / "b.json"
    worse_same.write_text(json.dumps(record("1f1b", 1, 0.6, 20.0)))
    gpipe_worse = tmp_path / "c.json"
    gpipe_worse.write_text(json.dumps(record("gpipe", 1, 0.6, 20.0)))
    # same identity: worse bubble AND worse live bytes gate (down-hints)
    assert rc.main([str(a), str(worse_same), "--check"]) == 2
    # different schedule: a schedule change, not a regression pair
    assert rc.main([str(a), str(gpipe_worse), "--check"]) == 0
    base, cand = rc.load_run(str(a)), rc.load_run(str(gpipe_worse))
    recs = rc.compare_runs(base, cand, 0.05)
    by_name = {r["metric"]: r for r in recs}
    assert by_name["pp_bubble_fraction"]["verdict"] == "info"
    assert "identity differs" in by_name["pp_bubble_fraction"]["note"]
    # the down-hints fire on the new fields when identity matches
    assert rc.direction_of("pp_live_bytes_max_mb") == "down"
    assert rc.direction_of("pp_bubble_fraction") == "down"


# -------------------------------------------------------------------- mxsan
def test_v2_sanitizer_clean_and_plan_cache():
    # "all" now includes the collective checker: the v2 overlap path's
    # bucketed gather must ride a FULLY sanitized run clean, and its
    # dispatches land in the collective ledger (stage-named, dp axis)
    san.arm("all", mode="raise")
    san.reset()
    try:
        before = dict(san.stats())
        ts, p, s, a, _ = _pp_steps(_mlp(), _mlp_batch(), MLP_SHAPES, 2,
                                   dp=2, M=2, n=3, schedule="1f1b")
        after = san.stats()
        for k in ("sync_violations", "donate_violations",
                  "recompile_violations", "collective_violations"):
            assert after[k] == before.get(k, 0), (k, after)
        gathers = [e for e in san.ledger_tail(4096)
                   if e["kind"] == "mxtpu_pp_gather"]
        assert gathers, "overlap gather never reached the ledger"
        assert gathers[0]["axes"] == "dp"
        assert gathers[0]["name"].startswith("stage")
        # the sig must carry the REAL flat-bucket shape (dp, chunk) —
        # "f32(2,...)" — not a degenerate "?()" (a rank with divergent
        # gather payloads is named by exactly this field)
        import re as _re
        assert _re.match(r"f32\(2,\d+\)$", gathers[0]["sig"][0]), gathers
        plans = [c for c in san.caches()
                 if c["name"] == "pipeline.schedule"]
        assert plans and plans[0]["entries"] == 1
        # donated params re-entering are named before XLA's crash
        p_old = p
        p, s, a, _ = ts(p, s, a, _mlp_batch())
        with pytest.raises(san.SanitizerError, match="donated"):
            ts(p_old, s, a, _mlp_batch())
    finally:
        san.disarm()
