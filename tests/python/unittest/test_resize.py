"""Live world resize — elasticity v3 (mxnet_tpu/parallel/resize.py).

Pins, on the virtual 8-device CPU mesh (tests/conftest.py):

- world-plan protocol: atomic write/read round trip, missing fields
  named, generation-bump detection from one ``os.stat`` per gate;
- in-place re-shard parity: ``reshard_train_step`` (device→host→device,
  no disk) is BITWISE equal to a sharded save + ``restore_into`` of the
  same state at the same target topology — held across the
  test_checkpoint matrix (ZeRO levels 1/2/3 dp8→dp4, pp4→pp2, the
  loss-scale automaton) and as an f64 @1e-9 slow twin;
- gate semantics: cadence (``MXNET_RESIZE_GATE_EVERY``), the general
  (non-fused) path warns once and never gates, a SHRINK plan skips the
  membership barrier, a GROW plan is adopted only through the
  gate-then-re-poll order, a spurious gate failure (no newer plan)
  continues training;
- join hand-off codec round trip (params + optimizer leaves + aux,
  with and without optimizer state);
- telemetry/diagnostics: resize bookkeeping lands in
  ``diagnostics.snapshot`` bundles and tools/diagnose.py renders the
  world trajectory;
- tools/launch.py ``--elastic MIN:MAX``: bound validation, plan-file
  compatibility, CLI parse errors;
- the preemption drill (slow): a 2-process ``--elastic 1:2`` world
  under ``MXNET_SAN=all:raise``, rank 1 SIGKILLed mid-epoch — the
  survivor resizes dp2→dp1 IN PLACE (process never exits), the dead
  slot rejoins live with its state handed over through the
  coordination service, and ``tools/run_compare.py --check`` holds the
  survivor's training curve on the fixed-world trajectory.
"""
import io
import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ckpt
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import dist
from mxnet_tpu.parallel import resize
from mxnet_tpu.parallel.mesh import make_mesh, make_pp_mesh
from mxnet_tpu.train import TrainStep, PipelineTrainStep

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))
BATCH = 8


def _mlp(classes=8):
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, name="fc1", num_hidden=16)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, name="fc2", num_hidden=16)
    h = mx.sym.Activation(h, act_type="tanh")
    h = mx.sym.FullyConnected(h, name="fc3", num_hidden=classes)
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _batch(seed=0, classes=8):
    rs = np.random.RandomState(seed)
    return {"data": rs.uniform(-1, 1, (BATCH, 32)).astype(np.float32),
            "softmax_label": rs.randint(0, classes,
                                        (BATCH,)).astype(np.float32)}


SHAPES = ({"data": (BATCH, 32)}, {"softmax_label": (BATCH,)})


def _opt():
    return mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                            rescale_grad=1.0 / BATCH)


def _zero_ts(level, dp=8):
    mesh = make_mesh({"dp": dp}, devices=jax.devices()[:dp])
    ts = TrainStep(_mlp(), _opt(), mesh=mesh, zero=level)
    p, s, a = ts.init(*SHAPES, seed=3)
    return ts, p, s, a


def _pp_ts(pp, M=2):
    mesh = make_pp_mesh(pp, dp=1, devices=jax.devices()[:pp])
    ts = PipelineTrainStep(_mlp(), _opt(), mesh=mesh, num_microbatches=M)
    p, s, a = ts.init(*SHAPES, seed=3)
    return ts, p, s, a


def _steps(ts, p, s, a, batch, n, key=7):
    rng = jax.random.PRNGKey(key)
    b = ts.shard_batch(batch)
    for _ in range(n):
        p, s, a, o = ts(p, s, a, b, rng=rng)
    return p, s, a


def _bitwise(got, want, what=""):
    assert sorted(got) == sorted(want), what
    for n in sorted(want):
        assert np.asarray(got[n]).tobytes() == \
            np.asarray(want[n]).tobytes(), "%s: %s" % (what, n)


def _bitwise_opt(got, want, what=""):
    assert (got is None) == (want is None), what
    if want is None:
        return
    assert sorted(got) == sorted(want), what
    for n in sorted(want):
        assert len(got[n]) == len(want[n]), "%s: %s" % (what, n)
        for i, (g, w) in enumerate(zip(got[n], want[n])):
            assert np.asarray(g).tobytes() == np.asarray(w).tobytes(), \
                "%s: %s[%d]" % (what, n, i)


def _oracle_restore(tmp_path, old_ts, p, s, a, new_ts, epoch=1, nbatch=2):
    """The disk route the live re-shard must match bitwise: sharded save
    from the OLD step, restore_into the NEW one."""
    cp = ckpt.Checkpointer(str(tmp_path / "oracle"), async_=False)
    path = cp.save(old_ts, p, s, a, epoch=epoch, nbatch=nbatch)
    return ckpt.restore_into(new_ts, path)


# --------------------------------------------------------------- plan file
def test_plan_roundtrip(tmp_path):
    path = str(tmp_path / "plan.json")
    written = resize.write_plan(path, gen=3, world=2,
                                coordinator="localhost:41207",
                                assign={"0": 0, "1": 1}, join=["1"])
    plan = resize.read_plan(path)
    assert plan == written
    assert plan["gen"] == 3 and plan["world"] == 2
    assert plan["assign"] == {"0": 0, "1": 1} and plan["join"] == ["1"]
    # join defaults to empty
    resize.write_plan(path, gen=4, world=1, coordinator="localhost:1",
                      assign={"0": 0})
    assert resize.read_plan(path)["join"] == []


def test_plan_missing_field_named(tmp_path):
    path = str(tmp_path / "plan.json")
    with open(path, "w") as f:
        json.dump({"gen": 1, "coordinator": "x", "assign": {}}, f)
    with pytest.raises(MXNetError, match="'world'"):
        resize.read_plan(path)


def test_poll_generation_bump_and_same_gen_refresh(tmp_path):
    path = str(tmp_path / "plan.json")
    resize.write_plan(path, gen=1, world=2, coordinator="localhost:1000",
                      assign={"0": 0, "1": 1})
    c = resize.ResizeController(path)
    assert c._poll() is None                      # unchanged file
    # same generation rewritten (content differs): adopted silently,
    # never reported as a transition
    resize.write_plan(path, gen=1, world=2,
                      coordinator="localhost:2000200",
                      assign={"0": 0, "1": 1})
    assert c._poll() is None
    assert c.plan["coordinator"] == "localhost:2000200"
    # a generation bump is returned exactly once
    resize.write_plan(path, gen=2, world=1, coordinator="localhost:3000",
                      assign={"0": 0})
    plan = c._poll()
    assert plan is not None and plan["gen"] == 2
    assert c._poll() is None


# ------------------------------------------------------------- state codec
def test_state_codec_roundtrip():
    man = {"epoch": 1, "nbatch": 2, "step": 5,
           "opt_state": {"fc1_weight": 2, "fc1_bias": 1}}
    params = {"fc1_weight": np.arange(12, dtype=np.float32).reshape(3, 4),
              "fc1_bias": np.ones((3,), np.float32)}
    aux = {"bn_mean": np.full((3,), 0.5, np.float32)}
    opt = {"fc1_weight": [np.zeros((3, 4), np.float32),
                          np.full((3, 4), 2.0, np.float32)],
           "fc1_bias": [np.full((3,), -1.0, np.float32)]}
    man2, p2, s2, a2 = resize._decode_state(
        resize._encode_state(man, params, opt, aux))
    assert man2 == man
    _bitwise(p2, params, "params")
    _bitwise(a2, aux, "aux")
    _bitwise_opt(s2, opt, "opt")


def test_state_codec_without_optimizer_state():
    man = {"epoch": 0, "nbatch": 0, "step": 0, "opt_state": None}
    params = {"w": np.eye(3, dtype=np.float32)}
    man2, p2, s2, a2 = resize._decode_state(
        resize._encode_state(man, params, None, {}))
    assert man2 == man and s2 is None and a2 == {}
    _bitwise(p2, params, "params")


# -------------------------------------------------- in-place re-shard parity
@pytest.mark.parametrize("level", [1, 2, 3])
def test_reshard_zero_dp8_to_dp4_bitwise_vs_checkpoint(tmp_path, level):
    """The acceptance pin: the live device→device re-shard is bitwise
    identical to the checkpoint save/restore route at the same target
    topology — params, every optimizer leaf, aux, and the update count —
    and stays bitwise through continued steps on the new mesh."""
    batch = _batch()
    ts, p, s, a = _zero_ts(level, dp=8)
    p, s, a = _steps(ts, p, s, a, batch, 2)

    live_ts = _zero_ts(level, dp=4)[0]
    lp, ls, la, lman = resize.reshard_train_step(ts, p, s, a, live_ts)

    disk_ts = _zero_ts(level, dp=4)[0]
    dp_, ds, da, dman = _oracle_restore(tmp_path, ts, p, s, a, disk_ts)

    assert live_ts.num_update == disk_ts.num_update == 2
    assert lman["step"] == dman["step"] == 2
    _bitwise(lp, dp_, "zero%d params" % level)
    _bitwise_opt(ls, ds, "zero%d opt" % level)
    _bitwise(la, da, "zero%d aux" % level)

    lp, ls, la = _steps(live_ts, lp, ls, la, batch, 2)
    dp_, ds, da = _steps(disk_ts, dp_, ds, da, batch, 2)
    _bitwise(lp, dp_, "zero%d params +2 steps" % level)
    _bitwise_opt(ls, ds, "zero%d opt +2 steps" % level)


def test_reshard_pp4_to_pp2_bitwise_vs_checkpoint(tmp_path):
    batch = _batch()
    ts, p, s, a = _pp_ts(4, M=2)
    rng = jax.random.PRNGKey(7)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, batch, rng=rng)

    live_ts = _pp_ts(2, M=2)[0]
    lp, ls, la, lman = resize.reshard_train_step(ts, p, s, a, live_ts)

    disk_ts = _pp_ts(2, M=2)[0]
    dp_, ds, da, dman = _oracle_restore(tmp_path, ts, p, s, a, disk_ts)

    assert lman["topology"]["pp"] == 4 and live_ts.num_update == 2
    assert disk_ts.num_update == 2
    _bitwise(lp, dp_, "pp4->pp2 params")
    _bitwise_opt(ls, ds, "pp4->pp2 opt")
    _bitwise(la, da, "pp4->pp2 aux")

    for _ in range(2):
        lp, ls, la, _ = live_ts(lp, ls, la, batch, rng=rng)
        dp_, ds, da, _ = disk_ts(dp_, ds, da, batch, rng=rng)
    _bitwise(lp, dp_, "pp4->pp2 params +2 steps")


def test_reshard_preserves_loss_scale_automaton(tmp_path):
    from mxnet_tpu import amp

    def _amp_ts():
        ts = TrainStep(_mlp(), _opt(), policy=amp.Policy(
            compute_dtype="float32", loss_scale=2048.0))
        p, s, a = ts.init(*SHAPES, seed=3)
        return ts, p, s, a

    batch = _batch()
    ts, p, s, a = _amp_ts()
    p, s, a = _steps(ts, p, s, a, batch, 2)
    assert ts.scale_state_host()["good"] == 2

    live_ts = _amp_ts()[0]
    lp, ls, la, _ = resize.reshard_train_step(ts, p, s, a, live_ts)
    disk_ts = _amp_ts()[0]
    dp_, ds, da, _ = _oracle_restore(tmp_path, ts, p, s, a, disk_ts)
    assert live_ts.scale_state_host() == disk_ts.scale_state_host()
    assert live_ts.scale_state_host()["scale"] == 2048.0
    assert live_ts.scale_state_host()["good"] == 2

    # the automaton keeps counting from where it was, on both routes
    lp, ls, la = _steps(live_ts, lp, ls, la, batch, 1)
    dp_, ds, da = _steps(disk_ts, dp_, ds, da, batch, 1)
    assert live_ts.scale_state_host() == disk_ts.scale_state_host()


@pytest.mark.slow
def test_reshard_zero3_dp8_to_dp4_f64(tmp_path):
    """f64 twin at 1e-9: the live re-shard continues on the dp4 mesh to
    within float64 tolerance of the UNINTERRUPTED dp8 run (this bounds
    real numerics drift, not just route parity)."""
    import jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)
    try:
        batch = {k: v.astype(np.float64) for k, v in _batch().items()}
        ts, p, s, a = _zero_ts(3, dp=8)
        p = {k: v.astype(jnp.float64) for k, v in p.items()}
        s = {k: tuple(x.astype(jnp.float64) for x in st)
             for k, st in s.items()}
        a = {k: v.astype(jnp.float64) for k, v in a.items()}
        p, s, a = _steps(ts, p, s, a, batch, 2)

        live_ts = _zero_ts(3, dp=4)[0]
        lp, ls, la, _ = resize.reshard_train_step(ts, p, s, a, live_ts)
        assert np.asarray(lp[live_ts.param_names[0]]).dtype == np.float64
        lp, ls, la = _steps(live_ts, lp, ls, la, batch, 2)

        p, s, a = _steps(ts, p, s, a, batch, 2)   # uninterrupted reference
        for n in sorted(p):
            np.testing.assert_allclose(
                np.asarray(live_ts.unflatten_host(n, np.asarray(lp[n]))),
                np.asarray(ts.unflatten_host(n, np.asarray(p[n]))),
                rtol=1e-9, atol=1e-10, err_msg=n)
    finally:
        jax.config.update("jax_enable_x64", False)


# --------------------------------------------------------------- controller
class _FakeFast(object):
    """Stands in for _FusedFit: real checkpoint math, recorded rebuild."""

    def __init__(self, ts, p, s, a):
        self.ts, self.p, self.s, self.a = ts, p, s, a
        self.applied = None

    def export_state(self, epoch=0, nbatch=0):
        return ckpt.reassemble(ckpt.snapshot(self.ts, self.p, self.s,
                                             self.a, epoch=epoch,
                                             nbatch=nbatch))

    def apply_resize(self, man, params, opt_state, aux):
        self.applied = (man, params, opt_state, aux)


def _plan1(tmp_path, world=1, assign=None, gen=1):
    path = str(tmp_path / "plan.json")
    resize.write_plan(path, gen=gen, world=world,
                      coordinator="localhost:1000",
                      assign=assign or {"0": 0})
    return path


def test_controller_none_without_env(monkeypatch):
    monkeypatch.delenv("MXNET_ELASTIC_PLAN", raising=False)
    assert resize.controller() is None


def test_controller_reads_plan_and_slot(tmp_path, monkeypatch):
    path = _plan1(tmp_path, world=2, assign={"0": 0, "1": 1})
    monkeypatch.setenv("MXNET_ELASTIC_PLAN", path)
    monkeypatch.setenv("MXTPU_SLOT", "1")
    c = resize.controller()
    assert c is not None and c.gen == 1 and c.slot == "1"


def test_gate_cadence(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_RESIZE_GATE_EVERY", "3")
    c = resize.ResizeController(_plan1(tmp_path))
    polls = []
    monkeypatch.setattr(c, "_poll", lambda: polls.append(1))
    for _ in range(7):
        assert c.step_gate(object(), epoch=0, nbatch=0) is False
    assert len(polls) == 2                        # gates 3 and 6 only


def test_gate_general_path_warns_once(tmp_path, caplog):
    c = resize.ResizeController(_plan1(tmp_path))
    with caplog.at_level("WARNING", logger="mxnet_tpu.parallel.resize"):
        for _ in range(3):
            assert c.step_gate(None, epoch=0, nbatch=0) is False
    warned = [r for r in caplog.records if "fused fit path" in r.message]
    assert len(warned) == 1


def test_shrink_plan_skips_membership_barrier(tmp_path, monkeypatch):
    path = _plan1(tmp_path, world=2, assign={"0": 0, "1": 1})
    monkeypatch.setenv("MXTPU_SLOT", "0")
    c = resize.ResizeController(path)

    def _no_barrier(name, timeout_ms=0):
        raise AssertionError("shrink gate must not run a barrier")
    monkeypatch.setattr(dist, "membership_barrier", _no_barrier)
    seen = []
    monkeypatch.setattr(
        c, "_transition",
        lambda plan, fast, epoch, nbatch: seen.append(plan["gen"]))
    resize.write_plan(path, gen=2, world=1, coordinator="localhost:2000",
                      assign={"0": 0})
    assert c.step_gate(object(), epoch=0, nbatch=5) is True
    assert seen == [2]


def test_grow_plan_adopted_via_post_gate_repoll(tmp_path, monkeypatch):
    """A grow plan written while this rank was already inside the gate is
    picked up by the re-poll AFTER the successful barrier — the ordering
    that keeps every member transitioning at the same step boundary."""
    path = _plan1(tmp_path, world=2, assign={"0": 0, "1": 1})
    monkeypatch.setenv("MXTPU_SLOT", "0")
    c = resize.ResizeController(path)

    def _barrier_then_plan(name, timeout_ms=0):
        assert name.startswith("resize-gate-g1-")
        resize.write_plan(path, gen=2, world=2,
                          coordinator="localhost:2000",
                          assign={"0": 0, "1": 1}, join=["1"])
        return True
    monkeypatch.setattr(dist, "membership_barrier", _barrier_then_plan)
    seen = []
    monkeypatch.setattr(
        c, "_transition",
        lambda plan, fast, epoch, nbatch: seen.append(plan["gen"]))
    assert c.step_gate(object(), epoch=0, nbatch=5) is True
    assert seen == [2]


def test_gate_timeout_without_plan_continues(tmp_path, monkeypatch):
    path = _plan1(tmp_path, world=2, assign={"0": 0, "1": 1})
    monkeypatch.setenv("MXNET_RESIZE_GATE_SEC", "0.2")
    c = resize.ResizeController(path)
    monkeypatch.setattr(dist, "membership_barrier",
                        lambda name, timeout_ms=0: False)
    assert c.step_gate(object(), epoch=0, nbatch=5) is False
    assert c.gen == 1                              # nothing adopted


def test_transition_in_process_single_world(tmp_path, monkeypatch):
    """A full _transition without a coupled runtime (world 1 → 1): the
    exported manifest carries the TRUE in-epoch batch index (resume
    offset applied), the MXTPU env contract is rewritten to the plan,
    and the fast object is rebuilt with bitwise-preserved state."""
    resize._reset_stats()
    batch = _batch()
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    ts = TrainStep(_mlp(), _opt(), mesh=mesh, zero=2)
    p, s, a = ts.init(*SHAPES, seed=3)
    p, s, a = _steps(ts, p, s, a, batch, 2)
    fake = _FakeFast(ts, p, s, a)

    path = _plan1(tmp_path, world=1, assign={"0": 0})
    monkeypatch.setenv("MXTPU_SLOT", "0")
    monkeypatch.setenv("MXTPU_NUM_PROCESSES", "1")
    monkeypatch.setenv("MXTPU_PROCESS_ID", "0")
    c = resize.ResizeController(path)
    c.resume_epoch, c.nbatch_offset = 1, 5
    resize.write_plan(path, gen=2, world=1, coordinator="localhost:7777",
                      assign={"0": 0})
    assert c.step_gate(fake, epoch=1, nbatch=3) is True
    assert c.gen == 2 and c._seq == 0

    man, params, opt_state, aux = fake.applied
    assert man["epoch"] == 1 and man["nbatch"] == 8   # 3 + offset 5
    assert man["step"] == 2
    # the hand-off pytrees ARE the exported state (no disk in between)
    eman, ep, es, ea = fake.export_state(epoch=1, nbatch=8)
    _bitwise({n: np.asarray(v) for n, v in params.items()},
             {n: np.asarray(v) for n, v in ep.items()}, "params")
    assert os.environ["MXTPU_COORDINATOR"] == "localhost:7777"
    assert os.environ["MXTPU_NUM_PROCESSES"] == "1"
    assert os.environ["MXTPU_PROCESS_ID"] == "0"

    st = resize.stats()
    assert st["resizes"] == 1 and st["lost_steps"] == 0
    assert st["last"]["gen"] == 2 and st["last"]["world"] == 1
    resize._reset_stats()


def test_transition_refuses_unassigned_slot(tmp_path, monkeypatch):
    path = _plan1(tmp_path, world=2, assign={"0": 0, "1": 1})
    monkeypatch.setenv("MXTPU_SLOT", "1")
    c = resize.ResizeController(path)
    plan = {"gen": 2, "world": 1, "coordinator": "localhost:1",
            "assign": {"0": 0}, "join": []}
    with pytest.raises(MXNetError, match="slot 1"):
        c._transition(plan, _FakeFast(None, None, None, None),
                      epoch=0, nbatch=0)


# ------------------------------------------------------- stats/diagnostics
def test_stats_record_and_reset():
    resize._reset_stats()
    assert resize.stats() == {"resizes": 0, "lost_steps": 0, "world": None,
                              "history": [], "last": None}
    resize._record({"kind": "shrink", "gen": 2, "world": 1,
                    "from_world": 2, "lost_steps": 0})
    resize._record({"kind": "grow", "gen": 3, "world": 2,
                    "from_world": 1, "lost_steps": 3})
    st = resize.stats()
    assert st["resizes"] == 2 and st["lost_steps"] == 3
    assert st["world"] == 2 and len(st["history"]) == 2
    st["history"][0]["kind"] = "mutated"           # copies, not views
    assert resize.stats()["history"][0]["kind"] == "shrink"
    resize._reset_stats()
    assert resize.stats()["resizes"] == 0


def test_diagnostics_bundle_carries_resize_section():
    from mxnet_tpu import diagnostics
    resize._reset_stats()
    bundle = diagnostics.snapshot("test")
    assert "resize" not in bundle                  # quiet until a resize
    resize._record({"kind": "shrink", "gen": 2, "world": 1,
                    "from_world": 2, "epoch": 1, "nbatch": 3, "step": 7,
                    "seconds": 0.5, "lost_steps": 0, "time": 1.0})
    bundle = diagnostics.snapshot("test")
    assert bundle["resize"]["resizes"] == 1
    assert bundle["resize"]["last"]["kind"] == "shrink"
    resize._reset_stats()


def test_diagnose_renders_resize_section():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import diagnose
    finally:
        sys.path.pop(0)
    bundle = {
        "type": "mxtpu_diagnostics", "reason": "crash", "time": 1.0,
        "pid": 1,
        "resize": {
            "resizes": 2, "lost_steps": 0, "world": 2,
            "history": [
                {"kind": "shrink", "gen": 2, "world": 1, "from_world": 2,
                 "epoch": 1, "nbatch": 3, "step": 7, "seconds": 0.4,
                 "time": 2.0},
                {"kind": "grow", "gen": 3, "world": 2, "from_world": 1,
                 "epoch": 1, "nbatch": 5, "step": 9, "seconds": 0.6,
                 "time": 3.0}],
            "last": {"kind": "grow", "gen": 3, "world": 2,
                     "from_world": 1, "epoch": 1, "nbatch": 5, "step": 9,
                     "seconds": 0.6, "time": 3.0}}}
    buf = io.StringIO()
    diagnose.render(bundle, out=buf)
    text = buf.getvalue()
    assert "Live resize (elasticity v3)" in text
    assert "2 -> 1 -> 2" in text
    assert "grow gen 3" in text


# -------------------------------------------------------- launch --elastic
def _launch_mod():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import launch
    finally:
        sys.path.pop(0)
    return launch


def test_launch_elastic_bounds_validated():
    launch = _launch_mod()
    for wmin, wmax in ((0, 2), (3, 3), (1, 1), (2, 1)):
        with pytest.raises(ValueError, match="elastic"):
            launch.launch_elastic(2, ["true"], wmin, wmax)


def test_launch_write_plan_matches_worker_reader(tmp_path):
    launch = _launch_mod()
    path = str(tmp_path / "plan.json")
    launch._write_plan(path, gen=2, world=2, coordinator="localhost:9",
                       assign={"0": 0, "1": 1}, join=["1"])
    plan = resize.read_plan(path)
    assert plan["gen"] == 2 and plan["world"] == 2
    assert plan["assign"] == {"0": 0, "1": 1} and plan["join"] == ["1"]
    # field-for-field the same schema the worker-side writer produces
    resize.write_plan(str(tmp_path / "w.json"), gen=2, world=2,
                      coordinator="localhost:9",
                      assign={"0": 0, "1": 1}, join=["1"])
    assert plan == resize.read_plan(str(tmp_path / "w.json"))


def test_launch_elastic_cli_rejects_bad_spec(monkeypatch):
    launch = _launch_mod()
    monkeypatch.setattr(sys, "argv",
                        ["launch.py", "-n", "2", "--elastic", "nope",
                         "true"])
    with pytest.raises(SystemExit):
        launch.main()


# --------------------------------------------------------- preemption drill
_DRILL_CHILD = """
import os, signal, sys, time
sys.path.insert(0, %(root)r)
import numpy as np
import jax
# coordination-only world: the single-process device backend must exist
# BEFORE the coordination service couples the ranks (docs/elastic.md)
jax.devices()
import mxnet_tpu as mx
from mxnet_tpu.parallel import elastic, resize

slot = os.environ.get("MXTPU_SLOT", "0")
join = os.environ.get("MXTPU_ELASTIC_JOIN") == "1"
prefix = os.environ["MXNET_DRILL_PREFIX"]

rs = np.random.RandomState(0)
centers = rs.randn(4, 16) * 3
yid = rs.randint(0, 4, 120)
x = (centers[yid] + rs.randn(120, 16)).astype(np.float32)
y = yid.astype(np.float32)
it = mx.io.NDArrayIter(x, y, batch_size=30)

data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
net = mx.sym.SoftmaxOutput(net, name="softmax")

state = {"n": 0}
def pace_then_maybe_die(param):
    # the victim (slot 1, original attempt) SIGKILLs itself mid-epoch-1,
    # BEFORE its membership gate for this batch ran; everyone else paces
    # so the supervisor's shrink->grow plans land mid-run, not post-run
    state["n"] += 1
    if slot == "1" and not join and state["n"] == 6:
        os.kill(os.getpid(), signal.SIGKILL)
    time.sleep(0.3)

mx.random.seed(11)
mod = mx.Module(net, context=mx.cpu())
elastic.fit_elastic(mod, it, prefix, num_epoch=4,
                    batch_end_callback=pace_then_maybe_die,
                    optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1,
                                      "momentum": 0.9})
st = resize.stats()
print("RESIZE slot", slot, "resizes", st["resizes"],
      "lost", st["lost_steps"],
      "worlds", "/".join(str(h["world"]) for h in st["history"]),
      "kinds", "/".join(h["kind"] for h in st["history"]), flush=True)
acc = mod.score(mx.io.NDArrayIter(x, y, batch_size=30), "acc")[0][1]
print("DRILL-DONE slot", slot, "acc %%.3f" %% acc, flush=True)
"""


def _counter_total(tel_path, name):
    total = 0
    if not os.path.exists(tel_path):
        return None
    with open(tel_path) as f:
        for line in f:
            try:
                ev = json.loads(line)
            except ValueError:
                continue
            if ev.get("type") == "counter" and ev.get("name") == name:
                total = ev.get("total", ev.get("value", 0))
    return total


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_live_resize_preemption_drill_e2e(tmp_path):
    """The acceptance drill: ``launch.py -n 2 --elastic 1:2`` under
    ``MXNET_SAN=all:raise``; rank 1 SIGKILLed mid-epoch.  The survivor
    must resize dp2→dp1 IN PLACE (its process never exits — one
    DRILL-DONE line per slot), the dead slot rejoins live (join event,
    state handed over through the coordination service, no disk resume),
    zero sanitizer violations, and the survivor's training curve stays
    on the fixed-world trajectory (run_compare --check)."""
    child = tmp_path / "child.py"
    child.write_text(_DRILL_CHILD % {"root": ROOT})

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_ELASTIC_PLAN", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_SAN"] = "all:raise"
    env["MXNET_RESIZE_GATE_SEC"] = "5"

    # fixed-world reference: the same training, one uncoupled process
    ref_tel = str(tmp_path / "ref.jsonl")
    ref_env = dict(env)
    ref_env["MXNET_TELEMETRY"] = ref_tel
    ref_env["MXNET_DRILL_PREFIX"] = str(tmp_path / "ref-el")
    ref = subprocess.run([sys.executable, "-u", str(child)],
                         env=ref_env, cwd=ROOT, capture_output=True,
                         text=True, timeout=300)
    assert ref.returncode == 0, (ref.stdout + ref.stderr)[-4000:]

    drill_tel = str(tmp_path / "drill.jsonl")
    env["MXNET_TELEMETRY"] = drill_tel
    env["MXNET_DRILL_PREFIX"] = str(tmp_path / "drill-el")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", "2", "--elastic", "1:2", "--max-restarts", "1",
         "--respawn-delay", "1.0",
         sys.executable, "-u", str(child)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=540)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-8000:]
    assert "SanitizerError" not in out, out[-8000:]

    # the survivor resized twice IN PLACE: shrink to world 1 when the
    # victim died, grow back to world 2 when the supervisor re-added it
    assert "RESIZE slot 0 resizes 2 lost 0 worlds 1/2 kinds shrink/grow" \
        in out, out[-8000:]
    # the re-added slot joined LIVE: state over the wire, not from disk
    assert "RESIZE slot 1 resizes 1 lost 0 worlds 2 kinds join" in out, \
        out[-8000:]
    # both members of the final world finished training
    assert out.count("DRILL-DONE slot 0") == 1, out[-8000:]
    assert out.count("DRILL-DONE slot 1") == 1, out[-8000:]

    # telemetry: the survivor's counter says two transitions, zero lost
    assert _counter_total(drill_tel + ".rank0", "elastic_resizes") == 2
    assert _counter_total(drill_tel + ".rank0", "resize_lost_steps") == 0

    # the survivor's training curve never left the fixed-world
    # trajectory: run_compare --check exits 0 (no REGRESSION verdict)
    cmp_ = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "run_compare.py"),
         ref_tel, drill_tel + ".rank0", "--check"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert cmp_.returncode == 0, cmp_.stdout + cmp_.stderr
    assert "REGRESSION" not in cmp_.stdout, cmp_.stdout
