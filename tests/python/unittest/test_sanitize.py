"""mxsan (mxnet_tpu/sanitize.py): the runtime sanitizer.

Covers every checker with a seeded violation (an unstable cache key, a
hot-path ``.item()``, a read-after-donate), the warmup budget and its
``MXNET_SAN_WARMUP`` override, warn-vs-raise modes, ``allow_sync``
scoping, the strict no-op disabled path, env autostart, the
registry-sourced ``jit_cache_size`` gauge, the PR-7 fused-fit regression
(mxsan names the offending key field), and the
no-recompile-on-second-call pins for the CKEY001 fixes."""
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu import sanitize as san
from mxnet_tpu import telemetry


@pytest.fixture(autouse=True)
def _clean_sanitizer():
    yield
    san.disarm()
    san.reset()
    os.environ.pop("MXNET_SAN_WARMUP", None)


def _mlp_symbol(num_hidden=4, num_classes=3, name="fc"):
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=num_hidden, name=name)
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def _train_step(**kwargs):
    from mxnet_tpu.train import TrainStep
    ts = TrainStep(_mlp_symbol(), mx.optimizer.SGD(learning_rate=0.1),
                   **kwargs)
    p, s, a = ts.init({"data": (8, 6)}, {"softmax_label": (8,)})
    batch = {"data": np.random.randn(8, 6).astype(np.float32),
             "softmax_label": np.random.randint(0, 3, 8)
             .astype(np.float32)}
    return ts, p, s, a, batch


def _fit_once(mod=None, num_epoch=1):
    np.random.seed(0)
    x = np.random.randn(60, 1, 12, 12).astype(np.float32)
    y = np.random.randint(0, 4, 60).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=30)
    if mod is None:
        net = models.get_mlp(num_classes=4) if hasattr(models, "get_mlp") \
            else models.get_lenet(num_classes=4)
        mod = mx.Module(net)
    mod.fit(it, num_epoch=num_epoch,
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.initializer.Xavier(magnitude=2.0))
    return mod


# ------------------------------------------------------------- arm/disarm
def test_spec_parsing_and_arming():
    assert san.arm("recompile,sync:raise")
    assert san.armed() == frozenset({"recompile", "sync"})
    assert san._mode == "raise"
    san.disarm()
    assert san.armed() == frozenset()
    assert san.arm("all")
    assert san.armed() == frozenset(san.CHECKERS)
    assert san._mode == "warn"
    with pytest.raises(mx.MXNetError):
        san.arm("recompile,typo")


def test_disabled_is_strict_noop():
    """MXNET_SAN unset: no patched function, no logging handler, and the
    hot-region/allow-sync entry points return the shared no-op."""
    import jax
    import logging
    assert san.armed() == frozenset()
    assert not hasattr(jax.device_get, "_mxsan_orig")
    assert not hasattr(jax.block_until_ready, "_mxsan_orig")
    assert logging.getLogger(
        "jax._src.interpreters.pxla").handlers == []
    assert san.hot_region("x") is san.hot_region("y")
    assert san.allow_sync("r") is san.allow_sync("r2")


def test_disarm_restores_patches_and_logger():
    import jax
    import logging
    logger = logging.getLogger("jax._src.interpreters.pxla")
    prev = (logger.level, logger.propagate)
    san.arm("recompile,sync,donate")
    assert hasattr(jax.device_get, "_mxsan_orig")
    assert logger.handlers
    san.disarm()
    assert not hasattr(jax.device_get, "_mxsan_orig")
    assert logger.handlers == []
    assert (logger.level, logger.propagate) == prev


def test_env_autostart_subprocess():
    child = ("import mxnet_tpu.sanitize as s; "
             "print('ARMED', sorted(s.armed()), s._mode)")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("MXNET_", "MXTPU_"))}
    env.update(JAX_PLATFORMS="cpu", MXNET_SAN="recompile,donate:raise",
               PYTHONPATH=os.pathsep.join(
                   [p for p in (os.environ.get("PYTHONPATH"),) if p]
                   + [os.path.dirname(os.path.dirname(os.path.dirname(
                       os.path.dirname(os.path.abspath(__file__)))))]))
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=150)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ARMED ['donate', 'recompile'] raise" in proc.stdout


# -------------------------------------------------------------- RECOMPILE
def test_recompile_names_the_offending_field():
    san.arm("recompile", mode="raise")
    h = san.register_cache("seeded", kind="fused_fit", warmup=1)
    h.miss({"optimizer": "SGD", "num_update": 0})
    with pytest.raises(san.SanitizerError) as ei:
        h.miss({"optimizer": "SGD", "num_update": 50})
    msg = str(ei.value)
    assert "seeded" in msg and "fused_fit" in msg
    assert "num_update (0 -> 50)" in msg
    assert "optimizer" not in msg.split("field(s):")[1]


def test_recompile_warmup_budget_and_nearest_neighbour():
    san.arm("recompile", mode="raise")
    h = san.register_cache("lad", kind="serving-rung", warmup=3)
    for b in (1, 2, 4):                 # one tick per rung: warmup
        h.miss({"bucket": b})
    with pytest.raises(san.SanitizerError) as ei:
        h.miss({"bucket": 4, "stale": True})
    # diffed against the closest warm key (bucket=4), not bucket=1
    assert "stale (None -> True)" in str(ei.value)
    assert "bucket" not in str(ei.value).split("field(s):")[1]


def test_recompile_warn_mode_counts_and_warns():
    san.arm("recompile", mode="warn")
    h = san.register_cache("warncache", kind="fused_fit", warmup=0)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        h.miss({"k": 1})
    assert len(w) == 1 and issubclass(w[0].category, san.SanitizerWarning)
    assert san.stats()["recompile_violations"] == 1


def test_warmup_env_override():
    os.environ["MXNET_SAN_WARMUP"] = "5"
    san.arm("recompile", mode="raise")
    h = san.register_cache("envbudget", kind="fused_fit", warmup=0)
    for i in range(5):                   # env override beats warmup=0
        h.miss({"i": i})
    with pytest.raises(san.SanitizerError):
        h.miss({"i": 99})


def test_warmup_counts_from_arming():
    h = san.register_cache("anchored", kind="fused_fit", warmup=1)
    for i in range(10):                  # pre-arm misses are warmup
        h.miss({"i": i})
    san.arm("recompile", mode="raise")
    h.miss({"i": 100})                   # one post-arm miss: in budget
    with pytest.raises(san.SanitizerError):
        h.miss({"i": 101})


def test_raw_jit_watcher_flags_recompile_loops():
    """A fresh jax.jit object per call recompiles the SAME (function,
    shapes) signature every time — the raw-jit loop the log watcher
    exists for.  Distinct shapes (bucket warmup) never trip it."""
    import jax
    os.environ["MXNET_SAN_WARMUP"] = "2"
    san.arm("recompile", mode="warn")

    def unstable_fn(a):
        return a * 2
    def fresh():
        # a NEW function object each time: jax.jit over the same object
        # would hit jax's own cache and never recompile
        def unstable_fn(a):
            return a * 2
        return unstable_fn
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for n in (2, 3, 4):              # distinct shapes: legit warmup
            jax.jit(unstable_fn)(np.zeros(n, np.float32))
        assert not [x for x in w
                    if issubclass(x.category, san.SanitizerWarning)]
        for _ in range(3):               # same signature thrice: loop
            jax.jit(fresh())(np.zeros(7, np.float32))
    msgs = [str(x.message) for x in w
            if issubclass(x.category, san.SanitizerWarning)]
    assert any("raw jax.jit 'unstable_fn'" in m for m in msgs), msgs
    assert san.stats()["raw_compiles"] >= 6


# ------------------------------------------------------------------- SYNC
def test_sync_flags_item_in_hot_region():
    import jax.numpy as jnp
    san.arm("sync", mode="raise")
    x = jnp.float32(3.0)
    x + 1                                # materialize outside the region
    with pytest.raises(san.SanitizerError) as ei:
        with san.hot_region("test_step"):
            x.item()
    assert "unplanned host sync (.item())" in str(ei.value)
    assert "'test_step'" in str(ei.value)
    with pytest.raises(san.SanitizerError):
        with san.hot_region("test_step"):
            float(x)


def test_sync_free_outside_regions_and_allow_scoping():
    import jax.numpy as jnp
    san.arm("sync", mode="raise")
    x = jnp.float32(3.0)
    x.item()                             # outside any region: free
    with san.hot_region("step"):
        with san.allow_sync("planned fetch"):
            x.item()                     # scoped escape
        with pytest.raises(san.SanitizerError):
            x.item()                     # scope really ended
    assert san.stats()["sync_allowed"] == 1
    assert san.stats()["sync_violations"] == 1


def test_sync_clean_fused_fit_and_eval():
    """The real hot paths are sync-free under the armed checker in raise
    mode — a false positive here would halt training."""
    san.arm("sync", mode="raise")
    mod = _fit_once(num_epoch=2)
    score = mod.score(mx.io.NDArrayIter(
        np.random.randn(30, 1, 12, 12).astype(np.float32),
        np.random.randint(0, 4, 30).astype(np.float32), batch_size=30),
        mx.metric.Accuracy())
    assert san.stats()["sync_violations"] == 0
    assert score is not None


# ----------------------------------------------------------------- DONATE
def test_donate_flags_reuse_of_donated_params():
    san.arm("donate", mode="raise")
    ts, p, s, a, batch = _train_step()
    p2, s2, a2, _ = ts(p, s, a, batch)
    with pytest.raises(san.SanitizerError) as ei:
        ts(p, s, a2, batch)              # stale params + opt state
    msg = str(ei.value)
    assert "donated" in msg and "params[" in msg
    assert "num_update=1" in msg
    # threading the returned pytrees is clean
    ts(p2, s2, a2, batch)


def test_donate_flags_read_through_sync_hook():
    san.arm("donate", mode="raise")
    ts, p, s, a, batch = _train_step()
    leaf = next(iter(p.values()))
    ts(p, s, a, batch)
    with pytest.raises(san.SanitizerError) as ei:
        leaf.item()      # the donate guard fires before .item() itself
    assert "donated buffer" in str(ei.value)


def test_donate_warn_mode_names_the_buffer_before_the_crash():
    """Warn mode: the NAMED warning lands before XLA's cryptic
    deleted-buffer error (which still fires — XLA:CPU honours donation
    here), so the crash is attributable."""
    san.arm("donate", mode="warn")
    ts, p, s, a, batch = _train_step()
    ts(p, s, a, batch)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        with pytest.raises(Exception) as ei:
            ts(p, s, a, batch)
    assert "deleted or donated" in str(ei.value)
    assert any(issubclass(x.category, san.SanitizerWarning) for x in w)
    assert san.stats()["donate_violations"] >= 1


def test_run_steps_donation_tracked():
    san.arm("donate", mode="raise")
    ts, p, s, a, batch = _train_step()
    p2, s2, a2, _ = ts.run_steps(p, s, a, batch, num_steps=1)
    with pytest.raises(san.SanitizerError) as ei:
        ts.run_steps(p, s, a, batch, num_steps=1)
    assert "run_steps" in str(ei.value)
    ts.run_steps(p2, s2, a2, batch, num_steps=1)


# ------------------------------------------------- PR-7 regression (fused)
def test_recompile_catches_fused_fit_step_state_key(monkeypatch):
    """THE acceptance pin: revert the fused-fit cache key to include step
    state (the PR-7 bug) and assert mxsan names the offending field."""
    from mxnet_tpu.module import module as module_mod
    real = module_mod._fused_fit_key_fields

    def buggy(opt, policy):
        fields = real(opt, policy)
        fields["num_update"] = max(
            getattr(opt, "_index_update_count", {0: 0}).values() or [0])
        return fields
    monkeypatch.setattr(module_mod, "_fused_fit_key_fields", buggy)
    san.arm("recompile", mode="raise")
    mod = _fit_once()                    # warmup: the one legitimate miss
    with pytest.raises(san.SanitizerError) as ei:
        _fit_once(mod)                   # step state changed -> new key
    msg = str(ei.value)
    assert "fused_fit" in msg
    assert "num_update (0 -> " in msg, msg


def test_fused_fit_no_recompile_on_second_fit():
    """The PR-7 fix itself, pinned through the sanitizer's ledger: a
    second fit() must hit the cached TrainStep (zero new misses)."""
    san.arm("recompile", mode="raise")
    mod = _fit_once()
    snap = [c for c in san.caches() if c["name"] == "fused_fit"
            and c["misses"]][-1]
    _fit_once(mod)                       # raise mode: a miss would throw
    snap2 = [c for c in san.caches() if c["name"] == "fused_fit"
             and c["misses"]][-1]
    assert snap2["misses"] == snap["misses"] == 1
    assert mod._fused_ts_cache is not None


def test_fused_fit_trace_env_toggle_lands_on_new_key(monkeypatch):
    """CKEY001 fix pinned dynamically: toggling a TRACE_ENV_DEFAULTS
    lever between fits must build a NEW TrainStep (not reuse the program
    compiled under the old value)."""
    mod = _fit_once()
    ts1 = mod._fused_ts_cache[1]
    monkeypatch.setenv("MXNET_STEM_FUSE", "0")
    _fit_once(mod)
    assert mod._fused_ts_cache[1] is not ts1
    monkeypatch.delenv("MXNET_STEM_FUSE")
    _fit_once(mod)                       # back: cached key again differs
    # and repeating under the SAME env reuses the step
    ts2 = mod._fused_ts_cache[1]
    _fit_once(mod)
    assert mod._fused_ts_cache[1] is ts2


def test_run_steps_trace_env_keying(monkeypatch):
    """run_steps' chunk cache keys on the trace-env snapshot: same env =
    one entry; a lever toggle retraces into a second entry."""
    ts, p, s, a, batch = _train_step()
    p, s, a, _ = ts.run_steps(p, s, a, batch, num_steps=1)
    p, s, a, _ = ts.run_steps(p, s, a, batch, num_steps=1)
    assert len(ts._multi_cache) == 1
    monkeypatch.setenv("MXNET_STEM_FUSE", "0")
    ts.run_steps(p, s, a, batch, num_steps=1)
    assert len(ts._multi_cache) == 2


# ------------------------------------------------------ gauge + telemetry
def test_jit_cache_size_gauge_sourced_from_registry(monkeypatch):
    telemetry.start()
    try:
        mod = _fit_once()                # fused fit registers its caches
        # every miss re-publishes the gauge as the LIVE registry total
        # (dead owners from earlier tests drop out, so probe the
        # contract at a controlled miss rather than across the fit)
        import gc
        gc.collect()
        probe = san.register_cache("gaugeprobe", kind="fused_fit",
                                   sizer=lambda: 1)
        probe.miss({"probe": 1})
        assert telemetry.value("jit_cache_size") == \
            san.total_cache_entries()
        # ops + fused-fit entries all visible, not just executor jits
        names = {c["name"] for c in san.caches() if c["entries"]}
        assert "ops.registry" in names and "fused_fit" in names
        assert mod._fused_ts_cache is not None
    finally:
        telemetry.stop()


def test_serving_rungs_visible_in_registry():
    from mxnet_tpu.serving import ServedModel
    sym = _mlp_symbol(num_hidden=3, num_classes=3)
    params = {"arg:fc_weight":
              mx.nd.array(np.random.randn(3, 5).astype(np.float32)),
              "arg:fc_bias": mx.nd.array(np.zeros(3, np.float32))}
    m = ServedModel(sym.tojson(), params, {"data": (5,)}, name="gsrv",
                    max_batch=4, max_wait_ms=0.5)
    try:
        m.warm()
        snap = [c for c in san.caches() if c["name"] == "serving:gsrv"][0]
        assert snap["entries"] == len(m.buckets)
        assert snap["warmup"] == len(m.buckets)
        assert san.total_cache_entries() >= snap["entries"]
    finally:
        m.close()


def test_violations_and_reset():
    san.arm("recompile", mode="warn")
    h = san.register_cache("vr", kind="fused_fit", warmup=0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        h.miss({"k": 1})
    assert san.violations()
    san.reset()
    assert san.violations() == [] and \
        san.stats()["recompile_violations"] == 0


# -------------------------------------------------- the suite-executes-CI
_SAN_E2E = r"""
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import models, sanitize as san
from mxnet_tpu.serving import ServedModel

assert san.armed() == frozenset({"recompile", "sync"}), san.armed()
assert san._mode == "raise"

# one fused-fit epoch (plus a reuse fit: the PR-7 regression would raise)
np.random.seed(0)
x = np.random.randn(120, 1, 12, 12).astype(np.float32)
y = np.random.randint(0, 4, 120).astype(np.float32)
it = mx.io.NDArrayIter(x, y, batch_size=30)
net = models.get_mlp(num_classes=4) if hasattr(models, "get_mlp") \
    else models.get_lenet(num_classes=4)
mod = mx.Module(net)
mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.01})
it.reset()
mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.01})

# one serving burst across the bucket ladder
data = mx.sym.Variable("data")
fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
out = mx.sym.SoftmaxOutput(fc, name="softmax")
params = {"arg:fc_weight":
          mx.nd.array(np.random.randn(3, 5).astype(np.float32)),
          "arg:fc_bias": mx.nd.array(np.zeros(3, np.float32))}
m = ServedModel(out.tojson(), params, {"data": (5,)}, name="e2e",
                max_batch=4, max_wait_ms=1.0)
m.warm()
futs = [m.submit({"data": np.random.randn(5).astype(np.float32)})
        for _ in range(16)]
rows = [f.result(60) for f in futs]
assert len(rows) == 16
m.close()

s = san.stats()
assert s["recompile_violations"] == 0, s
assert s["sync_violations"] == 0, s
print("SAN_E2E_OK", s["cache_misses"])
"""


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_suite_executes_under_sanitizer_raise_mode():
    """CI satellite: a fused-fit epoch AND a serving burst run to
    completion in a process armed with MXNET_SAN=recompile,sync:raise —
    the repo's hot paths hold the contracts the sanitizer enforces."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("MXNET_", "MXTPU_"))}
    env.update(JAX_PLATFORMS="cpu", MXNET_SAN="recompile,sync:raise",
               PYTHONPATH=os.pathsep.join(
                   [p for p in (os.environ.get("PYTHONPATH"),) if p]
                   + [os.path.dirname(os.path.dirname(os.path.dirname(
                       os.path.dirname(os.path.abspath(__file__)))))]))
    proc = subprocess.run([sys.executable, "-c", _SAN_E2E], env=env,
                          capture_output=True, text=True, timeout=550)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SAN_E2E_OK" in proc.stdout


# ------------------------------------------------------- collective checker
def test_collective_spec_and_all_includes_it():
    assert san.arm("collective:raise")
    assert san.armed() == frozenset({"collective"})
    assert san._collective_on and san._mode == "raise"
    san.disarm()
    san.arm("all")
    assert "collective" in san.armed()


def test_collective_ledger_records_dispatch_identity():
    """Every entry carries (seq, kind, name, sig, axes, thread) — the
    shared model both lint and runtime layers hang off."""
    san.arm("collective")
    san.reset()
    san.note_collective("dist.allreduce", sig=("f32(4,2)", "i32(8,)"),
                        axes="worker")
    with san.collective_dispatch("barrier", name="ep-0"):
        st = san.collective_state()
        assert len(st["inflight"]) == 1   # marked while blocking
    tail = san.ledger_tail()
    assert [e["seq"] for e in tail] == [1, 2]
    assert tail[0]["kind"] == "dist.allreduce"
    assert tail[0]["sig"] == ("f32(4,2)", "i32(8,)")
    assert tail[0]["axes"] == "worker"
    assert tail[1] == dict(tail[1], kind="barrier", name="ep-0")
    assert tail[0]["thread"] == "MainThread"
    st = san.collective_state()
    assert st["seq"] == 2 and st["inflight"] == []


def test_collective_sig_is_metadata_only():
    import jax
    x = jax.numpy.ones((4, 2), dtype="float32")
    assert san.collective_sig([x]) == ("f32(4,2)",)
    import numpy as _np
    assert san.collective_sig([_np.zeros(3, _np.int64)]) == ("i64(3)",)


def test_collective_hash_chain_deterministic_and_order_sensitive():
    """Two ranks issuing the SAME dispatch stream produce the same
    chain; any reorder/extra entry diverges it — the exchangeable
    summary the coordination service carries."""
    san.arm("collective")
    san.reset()
    san.note_collective("dist.allreduce", sig=("f32(4,)",), axes="worker")
    san.note_collective("barrier", name="ep-0")
    c1 = san.collective_state()["chain"]
    san.reset()
    san.note_collective("dist.allreduce", sig=("f32(4,)",), axes="worker")
    san.note_collective("barrier", name="ep-0")
    assert san.collective_state()["chain"] == c1
    san.reset()
    san.note_collective("barrier", name="ep-0")
    san.note_collective("dist.allreduce", sig=("f32(4,)",), axes="worker")
    assert san.collective_state()["chain"] != c1


def _payload(entries, chain):
    return {"seq": max((e["seq"] for e in entries), default=0),
            "chain": chain,
            "tail": [dict({"name": None, "sig": None, "axes": None}, **e)
                     for e in entries]}


def test_collective_divergence_names_seq_and_field_diff():
    """The headline message: first divergent seq, kind/name/sig/axes
    field diff, minority vs majority ranks."""
    mine = _payload([
        {"seq": 40, "kind": "dist.allreduce", "sig": ["f32(4,)"],
         "axes": "worker"},
        {"seq": 41, "kind": "mxtpu_pp_gather", "name": "stage3",
         "sig": ["f32(2048,)"], "axes": "dp"}], "aaa")
    peer = _payload([
        {"seq": 40, "kind": "dist.allreduce", "sig": ["f32(4,)"],
         "axes": "worker"},
        {"seq": 41, "kind": "dist.allreduce", "sig": ["f32(8,)"],
         "axes": "worker"}], "bbb")
    msg = san._divergence_message("barrier:x", 7, 2, mine,
                                  {0: peer, 1: peer, 3: peer})
    assert "rank 2 seq 41" in msg
    assert "mxtpu_pp_gather[name=stage3" in msg
    assert "ranks 0,1,3 dispatched dist.allreduce" in msg
    assert "kind ('dist.allreduce' -> 'mxtpu_pp_gather')" in msg
    assert "sig (['f32(8,)'] -> ['f32(2048,)'])" in msg


def test_collective_divergence_names_stopped_rank():
    """A rank missing an entry at a seq (it stopped dispatching) is
    named with where it stopped."""
    mine = _payload([{"seq": 5, "kind": "barrier", "name": "ep-1"}], "aa")
    peer = _payload([], "bb")
    msg = san._divergence_message("epoch1", 2, 0, mine, {1: peer})
    assert "dispatched nothing at seq 5" in msg
    assert "barrier[name=ep-1]" in msg


def test_collective_agreement_is_silent():
    mine = _payload([{"seq": 1, "kind": "barrier", "name": "x"}], "same")
    assert san._divergence_message("p", 1, 0, mine,
                                   {1: dict(mine)}) is None


def test_collective_off_main_thread_named_and_escape_scoped():
    """THR002's dynamic twin: a device collective noted from a side
    thread is a named violation; allow_thread_collective scopes the one
    sanctioned probe; coordination_barrier (device=False) is free."""
    import threading
    san.arm("collective")
    san.reset()
    caught = []

    def t_bad():
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            san.note_collective("barrier", name="x")
            caught.extend(str(x.message) for x in w
                          if issubclass(x.category, san.SanitizerWarning))

    th = threading.Thread(target=t_bad)
    th.start()
    th.join()
    assert len(caught) == 1
    assert "from thread" in caught[0] and "allow_thread_collective" \
        in caught[0]

    clean = []

    def t_ok():
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            with san.allow_thread_collective("bounded probe"):
                san.note_collective("barrier", name="y")
            san.note_collective("coordination_barrier", name="z",
                                device=False)
            clean.extend(str(x.message) for x in w)

    th = threading.Thread(target=t_ok)
    th.start()
    th.join()
    assert clean == [], clean
    s = san.stats()
    assert s["collective_violations"] == 1
    assert s["collective_thread_allowed"] == 1


def test_collective_sync_noop_single_process():
    """One process, nothing to exchange — and no exchange counter
    drift."""
    san.arm("collective")
    san.reset()
    san.collective_sync("epoch0")
    assert san.collective_state()["exchanges"] == 0


def test_collective_telemetry_signals_and_strict_noop_off():
    """collective_dispatches counter + collective_ledger_seq gauge under
    telemetry; zero events with telemetry off."""
    san.arm("collective")
    san.reset()
    telemetry.start()
    try:
        san.note_collective("dist.allreduce", sig=("f32(2,)",),
                            axes="worker")
        san.note_collective("barrier", name="b-1")
        c = telemetry.counters()
        assert c.get("collective_dispatches") == 2
        assert telemetry.gauges().get("collective_ledger_seq") == 2
    finally:
        telemetry.stop()
    before = telemetry.counters()
    san.note_collective("barrier", name="b-2")
    assert telemetry.counters() == before     # telemetry off: no events


def test_collective_disarm_is_strict_noop_and_stops_watchdog(tmp_path):
    """Disarm restores the no-op state: guard off, watchdog joined,
    in-flight cleared — and the entry points return the shared no-op."""
    os.environ["MXNET_SAN_COLL_TIMEOUT"] = "30"
    try:
        san.arm("collective")
        assert san._coll_watch_thread is not None
        assert san._coll_watch_thread.is_alive()
        san.disarm()
        assert san._collective_on is False
        assert san._coll_watch_thread is None
        assert san.collective_dispatch("barrier") is san.hot_region("x")
        assert san.allow_thread_collective("r") is san.hot_region("x")
    finally:
        os.environ.pop("MXNET_SAN_COLL_TIMEOUT", None)


def test_collective_watchdog_dumps_ledger_on_stuck_dispatch(tmp_path):
    """A dispatch in flight past MXNET_SAN_COLL_TIMEOUT writes ONE
    diagnostics bundle embedding the ledger tail and the stuck entry —
    the hung-fleet post-mortem."""
    import glob
    import json
    import time
    os.environ["MXNET_SAN_COLL_TIMEOUT"] = "0.3"
    os.environ["MXNET_DIAG_DIR"] = str(tmp_path)
    try:
        san.arm("collective")
        san.reset()
        san.note_collective("dist.allreduce", sig=("f32(4,)",),
                            axes="worker")
        with san.collective_dispatch("barrier", name="hung-1"):
            deadline = time.time() + 15
            bundles = []
            while time.time() < deadline and not bundles:
                bundles = glob.glob(
                    str(tmp_path / "mxtpu_diag.collective_stall*"))
                time.sleep(0.05)
        assert bundles, "watchdog never dumped"
        with open(bundles[0]) as f:
            b = json.load(f)
        stall = b["extra"]["collective_stall"]
        assert stall["entry"]["kind"] == "barrier"
        assert stall["entry"]["name"] == "hung-1"
        kinds = [e["kind"] for e in b["extra"]["collective_ledger"]]
        assert kinds == ["dist.allreduce", "barrier"]
        # one bundle per stall (the incident set dedupes)
        time.sleep(0.8)
        assert len(glob.glob(
            str(tmp_path / "mxtpu_diag.collective_stall*"))) == 1
    finally:
        os.environ.pop("MXNET_SAN_COLL_TIMEOUT", None)
        os.environ.pop("MXNET_DIAG_DIR", None)


def test_diagnostics_bundle_embeds_ledger_while_armed(tmp_path):
    """Any diagnostics bundle (crash/stall) carries the collective
    ledger while the checker is armed — and tools/diagnose.py renders
    it."""
    import io
    import json
    from mxnet_tpu import diagnostics as diag
    san.arm("collective")
    san.reset()
    san.note_collective("mxtpu_pp_gather", name="stage1",
                        sig=("f32(64,)",), axes="dp")
    os.environ["MXNET_DIAG_DIR"] = str(tmp_path)
    try:
        path = diag.write_snapshot("probe")
    finally:
        os.environ.pop("MXNET_DIAG_DIR", None)
    with open(path) as f:
        b = json.load(f)
    assert b["collective"]["seq"] == 1
    assert b["collective_ledger"][0]["kind"] == "mxtpu_pp_gather"
    if ROOT_DIR not in sys.path:
        sys.path.insert(0, ROOT_DIR)
    from tools.diagnose import render, load_bundle
    out = io.StringIO()
    render(load_bundle(path), out=out)
    text = out.getvalue()
    assert "Collective ledger" in text
    assert "mxtpu_pp_gather" in text and "stage1" in text


ROOT_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def test_collective_chain_immune_to_side_thread_interleave():
    """THE false-divergence regression pin: two ranks with identical
    MAIN-thread dispatch streams must hash identically even when their
    async-writer (side-thread) service barriers land at different
    points — side threads pair by barrier id, not order, so they stay
    out of the chain and out of the chained (mseq) numbering."""
    import threading
    san.arm("collective")

    def side_barrier(n):
        def _b():
            san.note_collective("coordination_barrier", name="ckpt-%d" % n,
                                device=False)
        t = threading.Thread(target=_b)
        t.start()
        t.join()

    # "rank 0": writer barrier between the two main dispatches
    san.reset()
    san.note_collective("dist.allreduce", sig=("f32(4,)",), axes="worker")
    side_barrier(1)
    san.note_collective("barrier", name="ep-0")
    st0 = san.collective_state()
    # "rank 1": writer barrier after both main dispatches
    san.reset()
    san.note_collective("dist.allreduce", sig=("f32(4,)",), axes="worker")
    san.note_collective("barrier", name="ep-0")
    side_barrier(1)
    st1 = san.collective_state()
    assert st0["chain"] == st1["chain"]
    assert st0["mseq"] == st1["mseq"] == 2
    assert st0["seq"] == st1["seq"] == 3      # ledger still sees all 3
    # and the exchanged payload aligns on the chained numbering
    p = san._coll_payload()
    assert [e["seq"] for e in p["tail"]] == [1, 2]
    assert all(e["kind"] != "coordination_barrier" or True
               for e in p["tail"])
    assert len(p["tail"]) == 2                # side entry not published


def test_collective_divergence_skips_slid_window_edges():
    """Window-edge regression pin: when both ranks' published tails are
    FULL and seq-offset (one rank dispatched an extra entry long ago),
    the seqs below a tail's minimum are not evidence — the diff must
    come from the overlapping range (a field diff), never a
    self-contradictory 'rank N dispatched nothing / stopped at a LATER
    seq' blaming the rank that is ahead."""
    # rank 2 (mine) is one ahead: window 3..5; peer's window 2..4
    mine = _payload([
        {"seq": 3, "kind": "dist.allreduce", "sig": ["f32(8,)"]},
        {"seq": 4, "kind": "barrier", "name": "ep-1"},
        {"seq": 5, "kind": "dist.allreduce", "sig": ["f32(4,)"]}], "aaa")
    peer = _payload([
        {"seq": 2, "kind": "dist.allreduce", "sig": ["f32(4,)"]},
        {"seq": 3, "kind": "dist.allreduce", "sig": ["f32(4,)"]},
        {"seq": 4, "kind": "dist.allreduce", "sig": ["f32(4,)"]}], "bbb")
    msg = san._divergence_message("epoch2", 9, 2, mine, {0: peer})
    assert "dispatched nothing at seq 2" not in msg
    assert "seq 3" in msg and "field diff" in msg
    assert "sig (['f32(8,)'] -> ['f32(4,)'])" in msg
