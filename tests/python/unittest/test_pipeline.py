"""Pipeline parallelism (PipelineTrainStep, the pp mesh axis).

Pins, on the virtual 8-device CPU mesh (tests/conftest.py):
- stage partitioning: coverage, contiguity, fusion glue, footprint
  balance, cross-stage weight-sharing rejection;
- parity vs the single-program TrainStep: MLP at M>1 (per-sample heads
  accumulate to the identical gradient), BN nets at M=1 exactly, BN nets
  at M>1 vs the microbatched reference (the documented batch-stat
  caveat), 'batch'-normalized heads compensated by 1/M, dp x pp and
  ZeRO-1 composition;
- AMP: clean parity, overflow-skip parity (update + aux skipped, scale
  halved, overflow counted) against TrainStep's policy automaton;
- mxsan: clean steps under recompile,sync,donate:raise; donated-buffer
  re-use caught; the program cache keys on trace_env_key();
- fit dispatch: MXNET_PP engages the pipeline, unset is byte-identical
  to the plain fused path, toggling rebuilds via the fused-fit cache key;
- telemetry: pp.stage/pp.bubble spans + gauges, strict no-op disabled;
  run_compare pipeline-block gating; telemetry_agg per-stage skew.

Float tolerances: pipelined gradient accumulation sums microbatch
partials in a different order than the single full-batch reduction, so
f32 parity is pinned at rtol=2e-5 (the dryrun pins the same identity at
1e-9 in f64).
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp
from mxnet_tpu import sanitize as san
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.executor import _Lowered
from mxnet_tpu.parallel.mesh import make_pp_mesh, pp_submeshes
from mxnet_tpu.train import (TrainStep, PipelineTrainStep,
                             pipeline_bubble_fraction)

RTOL, ATOL = 2e-5, 1e-6
BATCH = 8


def _mlp(classes=8, norm=None):
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, name="fc1", num_hidden=16)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, name="fc2", num_hidden=16)
    h = mx.sym.Activation(h, act_type="tanh")
    h = mx.sym.FullyConnected(h, name="fc3", num_hidden=classes)
    kw = {"normalization": norm} if norm else {}
    return mx.sym.SoftmaxOutput(h, name="softmax", **kw)


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


def _mlp_batch(seed=0, classes=8):
    rs = np.random.RandomState(seed)
    return {"data": rs.uniform(-1, 1, (BATCH, 32)).astype(np.float32),
            "softmax_label": rs.randint(0, classes,
                                        (BATCH,)).astype(np.float32)}


def _conv_batch(seed=0, classes=4):
    rs = np.random.RandomState(seed)
    return {"data": rs.uniform(-1, 1, (BATCH, 3, 8, 8)).astype(np.float32),
            "softmax_label": rs.randint(0, classes,
                                        (BATCH,)).astype(np.float32)}


def _opt():
    return mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                            rescale_grad=1.0 / BATCH)


def _ref_steps(net, batch, shapes, n=2, policy=None, key=7):
    ts = TrainStep(net, _opt(), policy=policy)
    p, s, a = ts.init(*shapes)
    b = ts.shard_batch(batch)
    rng = jax.random.PRNGKey(key)
    for _ in range(n):
        p, s, a, o = ts(p, s, a, b, rng=rng)
    return ts, p, a, o


def _pp_steps(net, batch, shapes, pp, dp=1, M=1, n=2, policy=None,
              zero=False, key=7):
    mesh = make_pp_mesh(pp, dp=dp, devices=jax.devices()[:pp * dp])
    ts = PipelineTrainStep(net, _opt(), mesh=mesh, num_microbatches=M,
                           policy=policy, zero=zero)
    p, s, a = ts.init(*shapes)
    rng = jax.random.PRNGKey(key)
    for _ in range(n):
        p, s, a, o = ts(p, s, a, batch, rng=rng)
    return ts, p, s, a, o


def _assert_trees_close(got, want, rtol=RTOL, atol=ATOL, what=""):
    for name in sorted(want):
        np.testing.assert_allclose(
            np.asarray(got[name]), np.asarray(want[name]), rtol=rtol,
            atol=atol, err_msg="%s mismatch: %s" % (what, name))


MLP_SHAPES = ({"data": (BATCH, 32)}, {"softmax_label": (BATCH,)})
CONV_SHAPES = ({"data": (BATCH, 3, 8, 8)}, {"softmax_label": (BATCH,)})


# ---------------------------------------------------------- stage partition
def test_stage_partition_covers_graph():
    low = _Lowered(_mlp())
    stages = low.stage_partition(3, input_names={"data", "softmax_label"})
    assert len(stages) == 3
    op_names = [n.name for n in low.order if not n.is_var]
    seen = []
    for st in stages:
        ops = [n.name for n in st.nodes if not n.is_var]
        assert ops, "empty stage %d" % st.index
        seen += ops
    assert seen == op_names        # contiguous, complete, in order
    assert stages[-1].final and not stages[0].final
    all_params = sorted(sum((st.params for st in stages), []))
    assert all_params == sorted(
        n for n in low.arg_names if n not in ("data", "softmax_label"))
    # every non-edge boundary hands at least one activation over
    for st in stages[:-1]:
        assert st.carry_out
        assert stages[st.index + 1].carry_in == st.carry_out


def test_stage_partition_glue_keeps_bn_relu_together():
    low = _Lowered(_convnet())
    for num in (2, 3, 4):
        for st in low.stage_partition(num, input_names={"data",
                                                        "softmax_label"}):
            names = [n.name for n in st.nodes if not n.is_var]
            for i, name in enumerate(names):
                if name.startswith("bn"):
                    # the fused-relu consumer sits in the same stage
                    assert i + 1 < len(names), (
                        "stage cut split %s from its relu" % name)


def test_stage_partition_balances_param_footprint():
    low = _Lowered(_mlp())
    sizes = {"fc1_weight": 10000, "fc1_bias": 16, "fc2_weight": 256,
             "fc2_bias": 16, "fc3_weight": 128, "fc3_bias": 8}
    stages = low.stage_partition(2, input_names={"data", "softmax_label"},
                                 param_sizes=sizes)
    # the heavy fc1 dominates: the cut isolates it in stage 0
    assert stages[0].params == ["fc1_weight", "fc1_bias"]


def test_stage_partition_rejects_cross_stage_weight_sharing():
    d = mx.sym.Variable("data")
    w = mx.sym.Variable("shared_weight")
    h = mx.sym.FullyConnected(d, weight=w, name="fa", num_hidden=32,
                              no_bias=True)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, weight=w, name="fb", num_hidden=32,
                              no_bias=True)
    net = mx.sym.SoftmaxOutput(h, name="softmax")
    low = _Lowered(net)
    with pytest.raises(MXNetError, match="shared_weight"):
        low.stage_partition(3, input_names={"data", "softmax_label"})


def test_stage_partition_too_many_stages():
    low = _Lowered(_mlp())
    with pytest.raises(MXNetError, match="stages"):
        low.stage_partition(100, input_names={"data", "softmax_label"})


def test_pp_submeshes_slices():
    mesh = make_pp_mesh(4, dp=2, devices=jax.devices())
    subs = pp_submeshes(mesh)
    assert len(subs) == 4
    assert all(tuple(s.axis_names) == ("dp",) and s.devices.shape == (2,)
               for s in subs)
    ids = [tuple(d.id for d in s.devices.flat) for s in subs]
    assert len({i for t in ids for i in t}) == 8   # disjoint cover
    # pure-pp mesh: single-device stages keep a size-1 dp axis
    mesh1 = make_pp_mesh(4, dp=1, devices=jax.devices()[:4])
    assert all(s.devices.shape == (1,) for s in pp_submeshes(mesh1))


# ------------------------------------------------------------------- parity
@pytest.mark.parametrize("pp,dp,M", [(2, 1, 4), (4, 1, 4), (4, 2, 2)])
def test_pp_parity_vs_single_program(pp, dp, M):
    batch = _mlp_batch()
    _, p_ref, a_ref, o_ref = _ref_steps(_mlp(), batch, MLP_SHAPES)
    _, p, _, _, o = _pp_steps(_mlp(), batch, MLP_SHAPES, pp, dp=dp, M=M)
    _assert_trees_close(p, p_ref, what="pp=%d dp=%d M=%d" % (pp, dp, M))
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(o_ref[0]),
                               rtol=RTOL, atol=ATOL)


def test_pp_parity_bn_net_m1():
    # M=1: the microbatch IS the global batch, so BN batch statistics
    # match the single-program step exactly (params AND moving stats)
    batch = _conv_batch()
    _, p_ref, a_ref, _ = _ref_steps(_convnet(), batch, CONV_SHAPES)
    _, p, _, a, _ = _pp_steps(_convnet(), batch, CONV_SHAPES, 2, M=1)
    _assert_trees_close(p, p_ref, what="bn params")
    _assert_trees_close(a, a_ref, what="bn aux")


def test_pp_bn_microbatch_reference():
    # M>1 BN semantics pin: per-microbatch batch statistics — identical
    # to the SAME microbatching without pipelining (pp=1), NOT to the
    # full-batch single program (the documented caveat)
    batch = _conv_batch()
    _, p2, _, a2, _ = _pp_steps(_convnet(), batch, CONV_SHAPES, 2, M=2)
    _, p1, _, a1, _ = _pp_steps(_convnet(), batch, CONV_SHAPES, 1, M=2)
    _assert_trees_close(p2, p1, what="bn microbatch params")
    _assert_trees_close(a2, a1, what="bn microbatch aux")


def test_pp_batch_normalized_heads_compensated():
    # normalization='batch' heads divide by the MICROBATCH size; the 1/M
    # head-scale compensation makes the accumulated gradient exact
    batch = _mlp_batch()
    net = _mlp(norm="batch")
    _, p_ref, _, _ = _ref_steps(net, batch, MLP_SHAPES)
    _, p, _, _, _ = _pp_steps(net, batch, MLP_SHAPES, 2, M=4)
    _assert_trees_close(p, p_ref, what="batch-normalized head")


def test_pp_valid_normalization_rejected():
    net = _mlp(norm="valid")
    mesh = make_pp_mesh(2, dp=1, devices=jax.devices()[:2])
    ts = PipelineTrainStep(net, _opt(), mesh=mesh, num_microbatches=2)
    with pytest.raises(MXNetError, match="valid"):
        ts.init(*MLP_SHAPES)


@pytest.mark.parametrize("net", ["mlp", "mlp5", "conv"])
def test_pp_zero_parity_and_sharded_state(net):
    """The pipeline step with ``zero=1`` agrees with the one-stage step,
    and asks the same placement plan how each leaf's state lies: in the
    leaf's shape where ``dp`` divides its leading axis (every leaf of
    ``mlp`` and ``conv``), as the flat ``(dp, chunk)`` view where not
    (``mlp5``'s head of 5 over a ``dp`` of 2)."""
    if net == "conv":
        sym, batch, shapes = _convnet(), _conv_batch(), CONV_SHAPES
    else:
        classes = 5 if net == "mlp5" else 8
        sym, batch, shapes = _mlp(classes), _mlp_batch(classes=classes), \
            MLP_SHAPES
    _, p_ref, _, _ = _ref_steps(sym, batch, shapes)
    # BatchNorm's statistics are a microbatch's: one microbatch for conv
    ts, p, s, _, _ = _pp_steps(sym, batch, shapes, 2, dp=2,
                               M=1 if net == "conv" else 2, zero=True)
    _assert_trees_close(p, p_ref, what="zero pp %s" % net)
    flat = set()
    for n, st in s.items():
        shape = tuple(p[n].shape)
        kept = shape[0] % 2 == 0
        assert ts.plan.keeps_shape(shape) is kept
        if not kept:
            flat.add(n)
        for leaf in st:
            assert tuple(leaf.shape) == (
                shape if kept else (2, -(-int(np.prod(shape)) // 2))), n
            assert leaf.sharding.spec == jax.sharding.PartitionSpec("dp")
            assert {x.data.shape[0] for x in leaf.addressable_shards} \
                == {leaf.shape[0] // 2}, \
                "pipeline zero optimizer state is not dp-sharded"
            assert ts.unflatten_host(n, np.asarray(leaf)).shape == shape
    assert flat == ({"fc3_weight", "fc3_bias"} if net == "mlp5" else set())


# ---------------------------------------------------------------------- AMP
def test_pp_amp_clean_parity():
    pol = lambda: amp.Policy(compute_dtype="float32", loss_scale=1024.0)
    batch = _mlp_batch()
    ts_r, p_ref, _, _ = _ref_steps(_mlp(), batch, MLP_SHAPES,
                                   policy=pol())
    ts_p, p, _, _, _ = _pp_steps(_mlp(), batch, MLP_SHAPES, 2, M=2,
                                 policy=pol())
    _assert_trees_close(p, p_ref, what="amp pp")
    assert ts_r.amp_stats() == ts_p.amp_stats() == (1024.0, 0)


def test_pp_amp_paramless_stage():
    # pp=4 over the MLP leaves the bare loss head as its own stage — the
    # AMP finite check must handle a stage with no accumulated gradients
    pol = amp.Policy(compute_dtype="float32", loss_scale=1024.0)
    batch = _mlp_batch()
    _, p_ref, _, _ = _ref_steps(_mlp(), batch, MLP_SHAPES,
                                policy=amp.Policy(compute_dtype="float32",
                                                  loss_scale=1024.0))
    _, p, _, _, _ = _pp_steps(_mlp(), batch, MLP_SHAPES, 4, M=2,
                              policy=pol)
    _assert_trees_close(p, p_ref, what="amp paramless stage")


def test_pp_amp_overflow_skip_parity():
    pol = lambda: amp.Policy(compute_dtype="float32", loss_scale=1024.0)
    batch = _conv_batch()
    batch["data"][0, 0, 0, 0] = np.inf
    ts_r, p_ref, a_ref, _ = _ref_steps(_convnet(), batch, CONV_SHAPES,
                                       n=1, policy=pol())
    ts_p, p, _, a, _ = _pp_steps(_convnet(), batch, CONV_SHAPES, 2, M=2,
                                 n=1, policy=pol())
    # both skipped the update: params, opt state and BN moving stats
    # untouched, scale halved, one overflow counted
    assert ts_r.amp_stats() == ts_p.amp_stats() == (512.0, 1)
    for name in sorted(p_ref):
        np.testing.assert_array_equal(np.asarray(p[name]),
                                      np.asarray(p_ref[name]))
    for name in sorted(a_ref):
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(a_ref[name]))


# -------------------------------------------------------------------- mxsan
def test_pp_sanitizer_clean_and_donate_ledger():
    san.arm("recompile,sync,donate", mode="raise")
    try:
        before = dict(san.stats())
        ts, p, s, a, _ = _pp_steps(_mlp(), _mlp_batch(), MLP_SHAPES, 2,
                                   dp=2, M=2, n=3)
        after = san.stats()
        for k in ("sync_violations", "donate_violations",
                  "recompile_violations"):
            assert after[k] == before.get(k, 0), (k, after)
        # the registered cache is visible with its programs
        pipe = [c for c in san.caches() if c["name"] == "pipeline.stages"]
        assert pipe and pipe[0]["entries"] > 0
        # stale (donated) params re-entering the step is named BEFORE
        # XLA's cryptic deleted-buffer crash
        p_old = p
        p, s, a, _ = ts(p, s, a, _mlp_batch())
        with pytest.raises(san.SanitizerError, match="donated"):
            ts(p_old, s, a, _mlp_batch())
    finally:
        san.disarm()


def test_pp_program_cache_trace_env_keyed(monkeypatch):
    ts, p, s, a, _ = _pp_steps(_mlp(), _mlp_batch(), MLP_SHAPES, 2, M=2,
                               n=1)
    n0 = len(ts._progs)
    p, s, a, _ = ts(p, s, a, _mlp_batch())
    assert len(ts._progs) == n0, "steady-state step rebuilt programs"
    # toggling a TRACE_ENV lever retraces instead of reusing stale
    # programs (CKEY001's dynamic half)
    monkeypatch.setenv("MXNET_CONV_LAYOUT", "NCHW")
    p, s, a, _ = ts(p, s, a, _mlp_batch())
    assert len(ts._progs) > n0, "trace-env toggle did not retrace"


# -------------------------------------------------------------- validation
def test_pp_validation_errors():
    from jax.sharding import Mesh
    ts = PipelineTrainStep(_mlp(), _opt(),
                           mesh=make_pp_mesh(2, dp=1,
                                             devices=jax.devices()[:2]),
                           num_microbatches=3)
    with pytest.raises(MXNetError, match="init"):
        ts({}, {}, {}, _mlp_batch())
    ts.init(*MLP_SHAPES)
    with pytest.raises(MXNetError, match="divisible"):
        p, s, a = ts.init(*MLP_SHAPES)
        ts(p, s, a, _mlp_batch())          # 8 % 3 != 0
    with pytest.raises(MXNetError, match="pp"):
        PipelineTrainStep(_mlp(), _opt(), mesh=None)
    dp_mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    with pytest.raises(MXNetError, match="pp"):
        PipelineTrainStep(_mlp(), _opt(), mesh=dp_mesh)


def test_pipeline_bubble_fraction_formula():
    assert pipeline_bubble_fraction(4, 1) == pytest.approx(0.75)
    assert pipeline_bubble_fraction(4, 4) == pytest.approx(3.0 / 7.0)
    fracs = [pipeline_bubble_fraction(4, m) for m in (1, 2, 4, 8, 16)]
    assert fracs == sorted(fracs, reverse=True)   # shrinks as M grows
    assert pipeline_bubble_fraction(1, 4) == 0.0  # pp=1: no bubble


# ---------------------------------------------------------------- telemetry
def test_pp_telemetry_signals(tmp_path):
    tel.start(str(tmp_path / "t.jsonl"))
    try:
        _pp_steps(_mlp(), _mlp_batch(), MLP_SHAPES, 4, M=4, n=1)
        evs = tel.events()
        stages = [e for e in evs if e.get("name") == "pp.stage"]
        bubbles = [e for e in evs if e.get("name") == "pp.bubble"]
        assert sorted(e["tags"]["stage"] for e in stages) == [0, 1, 2, 3]
        assert all(e["tags"]["schedule"] == "gpipe" for e in stages)
        assert len(bubbles) == 1
        assert bubbles[0]["tags"] == {"pp": 4, "microbatches": 4,
                                      "schedule": "gpipe", "interleave": 1}
        g = tel.gauges()
        assert g["pp_bubble_fraction"] == pytest.approx(
            pipeline_bubble_fraction(4, 4))
        live = [e for e in evs
                if str(e.get("name", "")).startswith("pp_stage")
                and str(e["name"]).endswith("_live_bytes")]
        assert sorted(e["tags"]["stage"] for e in live) == [0, 1, 2, 3]
        # non-empty stages account real bytes, and EVERY stage survives
        # in the name-keyed gauge registry (per-stage names, not tags)
        assert max(e["value"] for e in live) > 0
        for s in range(4):
            assert ("pp_stage%d_live_bytes" % s) in g
    finally:
        tel.stop()


def test_pp_telemetry_strict_noop():
    tel.reset()   # registry survives earlier in-process sessions
    assert not tel.enabled()
    ts, p, s, a, _ = _pp_steps(_mlp(), _mlp_batch(), MLP_SHAPES, 2, M=2,
                               n=1)
    assert tel.events() == []
    g = tel.gauges()
    assert "pp_bubble_fraction" not in g
    assert not any(k.startswith("pp_stage") for k in g)


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


def test_pp_fit_dispatch_trains(monkeypatch):
    monkeypatch.setenv("MXNET_PP", "2")
    monkeypatch.setenv("MXNET_PP_MICROBATCH", "2")
    data = _fit_data()
    mod = mx.Module(_fit_net(), context=mx.cpu())
    mod.fit(data, num_epoch=4, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.init.Xavier(), eval_metric="acc")
    assert isinstance(mod._fused_ts_cache[1], PipelineTrainStep)
    data.reset()
    score = dict(mod.score(data, mx.metric.Accuracy()))
    assert score["accuracy"] > 0.8, score
    # a second fit reuses the cached pipeline step (no rebuild)
    ts = mod._fused_ts_cache[1]
    data.reset()
    mod.fit(data, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    assert mod._fused_ts_cache[1] is ts


def test_pp_fit_env_unset_is_plain_fused_path(monkeypatch):
    monkeypatch.delenv("MXNET_PP", raising=False)
    monkeypatch.delenv("MXNET_PP_MICROBATCH", raising=False)
    calls = []
    import mxnet_tpu.train as train_mod
    orig = train_mod.PipelineTrainStep.__init__

    def spy(self, *a, **k):
        calls.append(1)
        return orig(self, *a, **k)
    monkeypatch.setattr(train_mod.PipelineTrainStep, "__init__", spy)
    data = _fit_data()
    mod = mx.Module(_fit_net(), context=mx.cpu())
    mod.fit(data, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    ts = mod._fused_ts_cache[1]
    assert isinstance(ts, TrainStep) and not calls, \
        "pp machinery engaged with MXNET_PP unset"


def test_pp_fit_toggle_rebuilds_via_cache_key(monkeypatch):
    monkeypatch.delenv("MXNET_PP", raising=False)
    data = _fit_data()
    mod = mx.Module(_fit_net(), context=mx.cpu())
    mod.fit(data, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    assert isinstance(mod._fused_ts_cache[1], TrainStep)
    monkeypatch.setenv("MXNET_PP", "2")
    data.reset()
    mod.fit(data, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    assert isinstance(mod._fused_ts_cache[1], PipelineTrainStep)
    # and back: unset restores the single-program step
    monkeypatch.delenv("MXNET_PP")
    data.reset()
    mod.fit(data, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    assert not isinstance(mod._fused_ts_cache[1], PipelineTrainStep)


def test_pp_fit_with_telemetry_keeps_pipeline(monkeypatch, tmp_path):
    # telemetry's step-breakdown fallback must never silently downgrade a
    # requested pipeline to the single-program general path — the
    # pipelined step provides its own per-stage breakdown
    monkeypatch.setenv("MXNET_PP", "2")
    tel.start(str(tmp_path / "t.jsonl"))
    try:
        data = _fit_data()
        mod = mx.Module(_fit_net(), context=mx.cpu())
        mod.fit(data, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1})
        assert isinstance(mod._fused_ts_cache[1], PipelineTrainStep)
        assert any(e.get("name") == "pp.stage" for e in tel.events())
    finally:
        tel.stop()


def test_pp_fit_bad_config_raises(monkeypatch):
    monkeypatch.setenv("MXNET_PP", "3")   # 8 devices % 3 != 0
    data = _fit_data()
    mod = mx.Module(_fit_net(), context=mx.cpu())
    with pytest.raises(MXNetError):
        mod.fit(data, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1})


@pytest.mark.slow
def test_pp_fit_sanitized_e2e(monkeypatch):
    # the acceptance sweep: a pipelined fit under the full sanitizer in
    # raise mode — recompiles, hot-path syncs and donation misuse all
    # fail fast; a clean run proves the ledger discipline
    monkeypatch.setenv("MXNET_PP", "2")
    monkeypatch.setenv("MXNET_PP_MICROBATCH", "2")
    san.arm("recompile,sync,donate", mode="raise")
    try:
        data = _fit_data()
        mod = mx.Module(_fit_net(), context=mx.cpu())
        mod.fit(data, num_epoch=2, optimizer="sgd",
                optimizer_params={"learning_rate": 0.5},
                initializer=mx.init.Xavier(), eval_metric="acc")
    finally:
        san.disarm()


# ------------------------------------------------- run_compare / agg tools
def test_run_compare_pipeline_block_gate(tmp_path):
    from tools import run_compare as rc
    assert rc.direction_of("pp_bubble_fraction") == "down"
    assert rc.direction_of("pp_stage_param_mb_max") == "down"
    assert rc.direction_of("pp_stage_live_bytes") == "down"
    assert rc.direction_of("pp_step_time_ms") == "down"

    def record(bubble, mem):
        return {"metric": "resnet50_train_img_per_sec_b32", "value": 2900.0,
                "unit": "img/s",
                "pipeline": {"pp_bubble_fraction": bubble,
                             "pp_stage_param_mb_max": mem,
                             "pp_step_time_ms": 120.0,
                             "config": {"pp": 4, "dp": 2,
                                        "microbatches": 8}}}
    base = tmp_path / "a.json"
    base.write_text(json.dumps(record(0.27, 25.0)))
    same = tmp_path / "b.json"
    same.write_text(json.dumps(record(0.27, 25.0)))
    worse = tmp_path / "c.json"
    worse.write_text(json.dumps(record(0.43, 25.0)))
    assert rc.main([str(base), str(same), "--check"]) == 0
    assert rc.main([str(base), str(worse), "--check"]) == 2
    run = rc.load_run(str(base))
    assert run.bench["pp_bubble_fraction"] == pytest.approx(0.27)
    assert "config" not in run.bench       # identity block stays out
    # the committed measured record self-compares clean (the pp ladder's
    # regression gate for future sessions: old vs new --check)
    committed = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                             "MULTICHIP_PP_r01.json")
    assert rc.main([committed, committed, "--check"]) == 0
    rec = rc.load_run(committed)
    assert rec.bench["pp_bubble_fraction"] == pytest.approx(0.4286)
    assert rec.bench["pp_stage_param_mb_max"] == pytest.approx(35.701)


def test_telemetry_agg_stage_skew(tmp_path, capsys):
    from tools import telemetry_agg as agg
    path = tmp_path / "t.jsonl.rank0"
    evs = []
    for step in range(20):
        for stage, dur in ((0, 4000.0), (1, 11900.0), (2, 4100.0)):
            evs.append({"type": "span", "name": "pp.stage", "cat":
                        "pipeline", "ts": step * 1e6, "dur": dur,
                        "tags": {"stage": stage, "microbatches": 4}})
        evs.append({"type": "span", "name": "step", "cat": "step",
                    "ts": step * 1e6, "dur": 20000.0})
    path.write_text("\n".join(json.dumps(e) for e in evs) + "\n")
    merged = agg.aggregate([str(path)])
    sk = merged["stage_skew"]
    assert sk["slowest_stage"] == "1"
    assert sk["slow_stage"] == "1"
    assert sk["skew_ratio"] == pytest.approx(11900.0 / 4050.0)
    assert sk["stages"]["1"]["count"] == 20
    agg.render(merged)
    out = capsys.readouterr().out
    assert "Per-stage skew" in out and "SLOW STAGE" in out
    # no pipeline spans -> no stage section
    bare = tmp_path / "b.jsonl.rank0"
    bare.write_text(json.dumps({"type": "span", "name": "step",
                                "ts": 0.0, "dur": 1.0}) + "\n")
    assert agg.aggregate([str(bare)])["stage_skew"] == {}
