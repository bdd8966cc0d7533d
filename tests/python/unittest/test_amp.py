"""Mixed-precision policy (amp.Policy) + device input pipeline tests.

Covers the PR-7 contract end to end:
- policy resolution (MXNET_AMP / MXNET_LOSS_SCALE, dispatch-time only);
- policy-off guard: numerics bit-identical, compiled TrainStep reused
  (no new jit cache entries between identical fits);
- the loss-scale automaton vs a numpy replication, the injected-inf skip
  (weights unchanged, scale halved), growth after N good steps, and the
  scan-carried state in run_steps;
- power-of-two scale exactness: an f32 policy trains bit-identically to
  the unscaled step (scale/unscale by 2^k are exact float ops);
- bf16 fused fit convergence with f32 master weights;
- telemetry signals (loss_scale gauge, amp_overflow_steps counter,
  train_loss_scale curve) + the strict no-op guard;
- device prefetch: byte-identical training, the measured data_wait share
  dropping with the double buffer on, and the fused-fit toggle.
"""
import os
import time

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu import random as mxr
from mxnet_tpu import telemetry as tel
from mxnet_tpu.amp import Policy, resolve_policy
from mxnet_tpu.train import TrainStep

RS = np.random.RandomState


def _net():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _make(policy=None, momentum=0.9, seed=1):
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=momentum)
    ts = TrainStep(_net(), opt, policy=policy)
    params, state, aux = ts.init({"data": (8, 10)}, {"softmax_label": (8,)},
                                 seed=seed)
    return ts, params, state, aux


def _data(seed=0, inf_at=None):
    rng = RS(seed)
    x = rng.rand(8, 10).astype(np.float32)
    if inf_at is not None:
        x[inf_at] = np.inf
    y = rng.randint(0, 4, 8).astype(np.float32)
    return {"data": x, "softmax_label": y}


# ------------------------------------------------------------- resolution
def test_resolve_policy_env(monkeypatch):
    monkeypatch.delenv("MXNET_AMP", raising=False)
    monkeypatch.delenv("MXNET_LOSS_SCALE", raising=False)
    assert resolve_policy() is None
    fallback = Policy("bfloat16")
    assert resolve_policy(default=fallback) is fallback

    monkeypatch.setenv("MXNET_AMP", "0")
    assert resolve_policy(default=fallback) is None

    monkeypatch.setenv("MXNET_AMP", "1")
    p = resolve_policy()
    assert p.compute_dtype == "bfloat16" and p.dynamic
    monkeypatch.setenv("MXNET_AMP", "float16")
    assert resolve_policy().compute_dtype == "float16"
    monkeypatch.setenv("MXNET_AMP", "int8")
    with pytest.raises(mx.base.MXNetError):
        resolve_policy()

    monkeypatch.setenv("MXNET_AMP", "1")
    monkeypatch.setenv("MXNET_LOSS_SCALE", "128")
    p = resolve_policy()
    assert not p.dynamic and p.loss_scale == 128.0
    monkeypatch.setenv("MXNET_LOSS_SCALE", "dynamic:256")
    p = resolve_policy()
    assert p.dynamic and p.loss_scale == 256.0
    monkeypatch.setenv("MXNET_LOSS_SCALE", "lots")
    with pytest.raises(mx.base.MXNetError):
        resolve_policy()


def test_policy_explicit_forms():
    assert resolve_policy(True).compute_dtype == "bfloat16"
    assert resolve_policy("float16").compute_dtype == "float16"
    p = Policy("bf16")
    assert p.compute_dtype == "bfloat16"
    with pytest.raises(mx.base.MXNetError):
        Policy("int8")
    with pytest.raises(mx.base.MXNetError):
        TrainStep(_net(), mx.optimizer.SGD(), dtype="bfloat16",
                  policy=Policy())


# ------------------------------------------------- loss-scale correctness
def test_pow2_scale_is_exact():
    """f32 compute + power-of-two scale: scaling/unscaling are exact, so
    the policy path must train BIT-identically to the unscaled step —
    this isolates the loss-scale machinery from the dtype change."""
    ts0, p0, s0, a0 = _make()
    bd0 = ts0.shard_batch(_data())
    ts1, p1, s1, a1 = _make(Policy("float32", loss_scale=8.0,
                                   growth_interval=10 ** 6))
    bd1 = ts1.shard_batch(_data())
    for _ in range(3):
        p0, s0, a0, o0 = ts0(p0, s0, a0, bd0, rng=jax.random.PRNGKey(5))
        p1, s1, a1, o1 = ts1(p1, s1, a1, bd1, rng=jax.random.PRNGKey(5))
    for k in p0:
        np.testing.assert_array_equal(np.asarray(p0[k]), np.asarray(p1[k]),
                                      err_msg=k)
    np.testing.assert_array_equal(np.asarray(o0[0]), np.asarray(o1[0]))


def test_overflow_skips_update_and_halves_scale():
    ts, p, s, a = _make(Policy("float32", loss_scale=16.0,
                               growth_interval=50))
    bad = ts.shard_batch(_data(inf_at=(0, 0)))
    before = {k: np.asarray(v).copy() for k, v in p.items()}
    mom_before = {k: tuple(np.asarray(x).copy() for x in st)
                  for k, st in s.items()}
    p, s, a, outs = ts(p, s, a, bad)
    for k in before:   # update skipped: weights AND optimizer state frozen
        np.testing.assert_array_equal(before[k], np.asarray(p[k]),
                                      err_msg=k)
        for m0, m1 in zip(mom_before[k], s[k]):
            np.testing.assert_array_equal(m0, np.asarray(m1))
    host = jax.device_get(ts._scale_state)
    assert float(host["scale"]) == 8.0        # halved
    assert int(host["overflow"]) == 1
    assert int(host["good"]) == 0


def test_scale_automaton_matches_numpy_replication():
    """Drive a finite/overflow step sequence through the jitted state and
    through a plain-numpy replica of the automaton — they must agree at
    every step (growth, backoff, clamping, overflow count)."""
    pol = Policy("float32", loss_scale=4.0, growth_interval=2,
                 growth_factor=2.0, backoff_factor=0.5, min_scale=1.0,
                 max_scale=64.0)
    ts, p, s, a = _make(pol)
    good_bd = ts.shard_batch(_data())
    bad_bd = ts.shard_batch(_data(inf_at=(0, 0)))

    # numpy replica
    scale, good, overflow = pol.loss_scale, 0, 0
    seq = [True, True, True, False, True, False, False, True, True]
    for finite in seq:
        p, s, a, _ = ts(p, s, a, good_bd if finite else bad_bd)
        if finite:
            good += 1
            if good >= pol.growth_interval:
                scale = min(scale * pol.growth_factor, pol.max_scale)
                good = 0
        else:
            scale = max(scale * pol.backoff_factor, pol.min_scale)
            good = 0
            overflow += 1
        host = jax.device_get(ts._scale_state)
        assert float(host["scale"]) == scale, (finite, host)
        assert int(host["good"]) == good
        assert int(host["overflow"]) == overflow


def test_static_scale_never_moves():
    ts, p, s, a = _make(Policy("float32", loss_scale=32.0, dynamic=False))
    bad = ts.shard_batch(_data(inf_at=(1, 2)))
    good = ts.shard_batch(_data())
    p, s, a, _ = ts(p, s, a, bad)
    p, s, a, _ = ts(p, s, a, good)
    host = jax.device_get(ts._scale_state)
    assert float(host["scale"]) == 32.0
    assert int(host["overflow"]) == 1


def test_run_steps_carries_scale_through_scan():
    """The fused chunk (lax.scan) must advance the loss-scale state per
    inner step exactly like sequential stepping."""
    def mk():
        return _make(Policy("float32", loss_scale=4.0, growth_interval=2))
    ts1, p1, s1, a1 = mk()
    bd1 = ts1.shard_batch(_data())
    p1, s1, a1, _ = ts1.run_steps(p1, s1, a1, bd1, 3)   # 4 fused steps

    ts2, p2, s2, a2 = mk()
    bd2 = ts2.shard_batch(_data())
    for _ in range(4):
        p2, s2, a2, _ = ts2(p2, s2, a2, bd2)
    h1 = jax.device_get(ts1._scale_state)
    h2 = jax.device_get(ts2._scale_state)
    assert float(h1["scale"]) == float(h2["scale"]) == 16.0
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)


def test_bf16_policy_master_weights_and_outputs():
    ts, p, s, a = _make(Policy("bfloat16"))
    bd = ts.shard_batch(_data())
    p, s, a, outs = ts(p, s, a, bd)
    assert np.asarray(p["fc1_weight"]).dtype == np.float32  # f32 masters
    assert np.asarray(outs[0]).dtype == np.float32  # loss surface in f32
    assert np.isfinite(np.asarray(outs[0])).all()


# ----------------------------------------------------------- fused Module.fit
def _fit(env=None, seed=0, epochs=3, n=120, classes=4, lr=0.01,
         separable=False, batch=30, **fit_kw):
    env = dict(env or {})
    old = {k: os.environ.get(k) for k in env}
    for k, v in env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    try:
        np.random.seed(seed)
        if separable:
            y = np.random.randint(0, classes, n).astype(np.float32)
            x = (np.random.randn(n, 1, 12, 12) * 0.4
                 + y[:, None, None, None]).astype(np.float32)
        else:
            x = np.random.randn(n, 1, 12, 12).astype(np.float32)
            y = np.random.randint(0, classes, n).astype(np.float32)
        it = mx.io.NDArrayIter(x, y, batch_size=batch)
        net = models.get_mlp(num_classes=classes) \
            if hasattr(models, "get_mlp") \
            else models.get_lenet(num_classes=classes)
        mod = mx.Module(net)
        mxr.seed(7)
        mod.fit(it, num_epoch=epochs, optimizer="sgd",
                optimizer_params={"learning_rate": lr, "momentum": 0.9},
                initializer=mx.initializer.Xavier(magnitude=2.0), **fit_kw)
        arg, _ = mod.get_params()
        return mod, {k: v.asnumpy() for k, v in arg.items()}, (x, y)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_policy_off_guard_bitwise_and_cached():
    """With MXNET_AMP unset the fused fit must (a) train bit-identically
    across runs and to an explicit MXNET_AMP=0 run, and (b) reuse the
    cached compiled TrainStep across fit() calls — no new jit entries."""
    m1, p1, _ = _fit()
    m2, p2, _ = _fit({"MXNET_AMP": "0"})
    for k in p1:
        np.testing.assert_array_equal(p1[k], p2[k], err_msg=k)
    assert m1._fused_ts_cache[1].policy is None
    # second identical fit on the same module reuses the compiled step
    ts_before = m1._fused_ts_cache[1]
    np.random.seed(0)
    x = np.random.randn(60, 1, 12, 12).astype(np.float32)
    y = np.random.randint(0, 4, 60).astype(np.float32)
    m1.fit(mx.io.NDArrayIter(x, y, batch_size=30), num_epoch=1,
           optimizer="sgd",
           optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
           force_init=False)
    assert m1._fused_ts_cache[1] is ts_before


def test_policy_toggle_takes_effect_after_prior_compile():
    """The satellite-1 cache-key fix: toggling MXNET_AMP between fit()
    calls must rebuild the TrainStep (new cache key), not silently reuse
    the f32 program (modeled on test_env_toggle.py)."""
    m, _, (x, y) = _fit()
    ts_f32 = m._fused_ts_cache[1]
    key_f32 = m._fused_ts_cache[0]
    os.environ["MXNET_AMP"] = "1"
    try:
        m.fit(mx.io.NDArrayIter(x, y, batch_size=30), num_epoch=1,
              optimizer="sgd",
              optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
              force_init=False)
    finally:
        os.environ.pop("MXNET_AMP", None)
    assert m._fused_ts_cache[1] is not ts_f32
    assert m._fused_ts_cache[0] != key_f32
    assert m._fused_ts_cache[1].policy.compute_dtype == "bfloat16"


def test_amp_fused_fit_converges():
    """MXNET_AMP=1: the fused fit trains in bf16 with f32 masters and
    still converges within the usual threshold on a separable task."""
    m, params, (x, y) = _fit({"MXNET_AMP": "1"}, epochs=8, n=200,
                             classes=2, lr=0.05, separable=True, batch=40)
    ts = m._fused_ts_cache[1]
    assert ts.policy is not None and ts.policy.compute_dtype == "bfloat16"
    for k, v in params.items():
        assert v.dtype == np.float32, k
    score = m.score(mx.io.NDArrayIter(x, y, batch_size=40),
                    mx.metric.Accuracy())
    assert score[0][1] > 0.9, score


def test_explicit_fit_policy_kwarg():
    pol = Policy("float32", loss_scale=8.0)
    m, p1, _ = _fit(policy=pol)
    assert m._fused_ts_cache[1].policy is pol
    # power-of-two f32 policy == plain f32 run, end to end through fit —
    # to f32 round-off, not bitwise.  Scaling by 8 and back is exact per
    # element, but the two fits are two different XLA programs (the policy
    # step adds the finite check, the lax.cond and the scale multiplies),
    # and XLA is free to fuse them differently and so to sum the batch
    # reductions in another order; those last-bit differences compound
    # over the 12 momentum updates (7e-9 absolute under jax 0.9's XLA:CPU).
    # Bitwise equality across separately compiled programs is not a
    # contract this repo can hold; agreement at 1e-6 of the tensor's scale
    # is what the test means.
    m0, p0, _ = _fit()
    for k in p0:
        np.testing.assert_allclose(p1[k], p0[k], rtol=1e-6,
                                   atol=1e-6 * np.abs(p0[k]).max(),
                                   err_msg=k)


# ------------------------------------------------------------- telemetry
def test_amp_telemetry_signals():
    tel.reset()
    tel.start()
    try:
        _fit({"MXNET_AMP": "1"}, epochs=1)
    finally:
        gauges = tel.gauges()
        scalars = tel.scalars()
        tel.stop()
        tel.reset()
    assert "loss_scale" in gauges and gauges["loss_scale"] > 0
    assert "train_loss_scale" in scalars
    assert scalars["train_loss_scale"]["value"] == gauges["loss_scale"]


def test_amp_overflow_counter():
    ts, p, s, a = _make(Policy("float32", loss_scale=16.0))
    bad = ts.shard_batch(_data(inf_at=(0, 0)))
    tel.reset()
    tel.start()
    try:
        p, s, a, _ = ts(p, s, a, bad)
        counters = tel.counters()
        gauges = tel.gauges()
    finally:
        tel.stop()
        tel.reset()
    assert counters.get("amp_overflow_steps") == 1
    assert gauges.get("loss_scale") == 8.0


def test_amp_strict_noop_when_telemetry_off():
    """AMP training with telemetry disabled must emit nothing and never
    sync the scale state on the hot path."""
    assert not tel.enabled()
    ts, p, s, a = _make(Policy("float32", loss_scale=8.0))
    bd = ts.shard_batch(_data())
    p, s, a, _ = ts(p, s, a, bd)
    assert tel.events() == [] and tel.counters() == {}
    assert ts._overflow_seen == 0   # amp_stats never ran


# -------------------------------------------------------- device prefetch
def test_prefetch_fit_byte_identical_and_counted():
    """Artificially slow loader through the fused fit: prefetch on vs off
    must produce byte-identical parameters; the staged path actually
    engages (the producer thread's input.stage spans count it)."""
    class SlowIter(mx.io.ResizeIter):
        def next(self):
            time.sleep(0.002)
            return super().next()

    def run(env):
        env = dict(env)
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            np.random.seed(0)
            x = np.random.randn(90, 1, 12, 12).astype(np.float32)
            y = np.random.randint(0, 3, 90).astype(np.float32)
            base = mx.io.NDArrayIter(x, y, batch_size=30)
            it = SlowIter(base, 3)
            net = models.get_mlp(num_classes=3) \
                if hasattr(models, "get_mlp") \
                else models.get_lenet(num_classes=3)
            mod = mx.Module(net)
            mxr.seed(3)
            mod.fit(it, num_epoch=2, optimizer="sgd",
                    optimizer_params={"learning_rate": 0.01},
                    initializer=mx.initializer.Xavier(magnitude=2.0))
            arg, _ = mod.get_params()
            return {k: v.asnumpy() for k, v in arg.items()}
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    tel.reset()
    tel.start()
    try:
        p_on = run({})
        staged = [e for e in tel.events() if e.get("name") == "input.stage"]
    finally:
        tel.stop()
        tel.reset()
    assert len(staged) >= 6
    p_off = run({"MXNET_DEVICE_PREFETCH": "0"})
    for k in p_on:
        np.testing.assert_array_equal(p_on[k], p_off[k], err_msg=k)


def test_prefetch_overlap_drops_data_wait_share():
    """bench.measure_data_wait with an artificially slow stage: the
    double-buffered share must land well under the synchronous one.  The
    model is sized so one chunk's compute exceeds the stage time —
    overlap can only hide work shorter than the compute window."""
    import bench
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=256, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    ts = TrainStep(net, mx.optimizer.SGD(learning_rate=0.01))
    p, s, a = ts.init({"data": (64, 512)}, {"softmax_label": (64,)})
    rng = RS(0)
    hb = {"data": rng.rand(64, 512).astype(np.float32),
          "softmax_label": rng.randint(0, 64, 64).astype(np.float32)}

    def slow_stage(b):
        time.sleep(0.02)   # artificially slow loader
        staged = ts.shard_batch(b)
        jax.block_until_ready(list(staged.values()))
        return staged

    stats = bench.measure_data_wait(ts, p, s, a, hb, chunk=40, chunks=3,
                                    stage=slow_stage)
    assert stats["device_prefetch"] == 2
    assert stats["data_wait_share_sync"] > 0.05
    assert stats["data_wait_share"] < 0.5 * stats["data_wait_share_sync"], \
        stats


def test_measure_data_wait_respects_prefetch_off(monkeypatch):
    import bench
    monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "0")
    ts, p, s, a = _make()
    stats = bench.measure_data_wait(ts, p, s, a, _data(), chunk=4, chunks=2)
    assert stats["device_prefetch"] == 0
    assert stats["data_wait_share"] == stats["data_wait_share_sync"]


# ------------------------------------------------------- run_compare gate
def test_bench_record_gates_with_run_compare(tmp_path):
    """A new-format BENCH record (amp + data_wait_share stamped) compares
    against an old-format baseline (the driver-wrapper shape: no amp, no
    telemetry block) through run_compare --check: a faster run passes, a
    >5% slower one exits 2 (the mechanical gate)."""
    import json
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))
    from tools import run_compare
    base = tmp_path / "BENCH_base.json"
    base.write_text(json.dumps({
        "n": 5, "cmd": "python bench.py", "rc": 0, "tail": "",
        "parsed": {"metric": "resnet50_train_img_per_sec_b32",
                   "value": 2950.0, "unit": "img/s",
                   "vs_baseline": 16.251}}))
    base = str(base)

    def rec(value):
        return {"metric": "resnet50_train_img_per_sec_b32", "value": value,
                "unit": "img/s", "vs_baseline": round(value / 181.53, 3),
                "meta": {"config": {"batch": 32, "amp":
                                    "bfloat16/dyn-scale-32768"},
                         "world_size": 1, "rank": None},
                "telemetry": {"data_wait_share": 0.001,
                              "data_wait_share_sync": 0.21,
                              "device_prefetch": 2}}

    fast = tmp_path / "BENCH_new_fast.json"
    slow = tmp_path / "BENCH_new_slow.json"
    fast.write_text(json.dumps(rec(3100.0)))
    slow.write_text(json.dumps(rec(2500.0)))
    assert run_compare.main([base, str(fast), "--check"]) == 0
    assert run_compare.main([base, str(slow), "--check"]) == 2


# ----------------------------------------------------------- mesh / ZeRO-1
def test_amp_on_dp_mesh_and_zero():
    """The policy composes with the SPMD mesh path (8-device virtual CPU
    mesh) and with ZeRO-1: scale state rides replicated, updates match the
    unscaled mesh step bitwise under an f32 power-of-two policy."""
    from mxnet_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({"dp": 8})

    def one(policy, zero):
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
        ts = TrainStep(_net(), opt, mesh=mesh, zero=zero, policy=policy)
        p, s, a = ts.init({"data": (8, 10)}, {"softmax_label": (8,)},
                          seed=2)
        bd = ts.shard_batch(_data())
        for _ in range(2):
            p, s, a, outs = ts(p, s, a, bd, rng=jax.random.PRNGKey(3))
        return ts, {k: np.asarray(v) for k, v in p.items()}

    pol = Policy("float32", loss_scale=4.0, growth_interval=10 ** 6)
    for zero in (False, True):
        ts_amp, p_amp = one(pol, zero)
        _, p_ref = one(None, zero)
        for k in p_ref:
            np.testing.assert_array_equal(p_ref[k], p_amp[k],
                                          err_msg="zero=%s %s" % (zero, k))
        host = jax.device_get(ts_amp._scale_state)
        assert float(host["scale"]) == 4.0 and int(host["overflow"]) == 0


def test_amp_run_steps_stacked_on_mesh():
    """Stacked multi-step chunks shard the batch on axis 1 with the scale
    in the carry — the sharding-slot bookkeeping the bi index guards."""
    from mxnet_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({"dp": 8})
    rng = RS(3)
    xs = rng.rand(3, 8, 10).astype(np.float32)
    ys = rng.rand(3, 8).astype(np.float32) * 0 + \
        rng.randint(0, 4, (3, 8)).astype(np.float32)
    pol = Policy("float32", loss_scale=8.0, growth_interval=10 ** 6)

    def mk(policy):
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
        ts = TrainStep(_net(), opt, mesh=mesh, policy=policy)
        p, s, a = ts.init({"data": (8, 10)}, {"softmax_label": (8,)},
                          seed=4)
        return ts, p, s, a

    ts1, p1, s1, a1 = mk(pol)
    p1, s1, a1, _ = ts1.run_steps(p1, s1, a1,
                                  {"data": xs, "softmax_label": ys}, 2,
                                  stacked=True)
    ts0, p0, s0, a0 = mk(None)
    p0, s0, a0, _ = ts0.run_steps(p0, s0, a0,
                                  {"data": xs, "softmax_label": ys}, 2,
                                  stacked=True)
    for k in p0:
        np.testing.assert_array_equal(np.asarray(p0[k]), np.asarray(p1[k]),
                                      err_msg=k)


def test_prefetch_drained_on_mid_epoch_exception(monkeypatch):
    """A mid-epoch exception must not leave the prefetch producer thread
    alive/blocked holding staged batches — the fit loop drains it."""
    from mxnet_tpu import io as mio
    created = []
    orig = mio.DevicePrefetchIter

    class Spy(orig):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            created.append(self)

    monkeypatch.setattr(mio, "DevicePrefetchIter", Spy)

    def boom(param):
        raise RuntimeError("callback boom")

    with pytest.raises(RuntimeError, match="callback boom"):
        _fit(batch_end_callback=boom)
    assert created, "prefetcher never engaged"
    for c in created:
        assert not c._thread.is_alive()
        assert c._exhausted
