"""Elastic training v2: sharded async checkpointing + any-topology restore.

Pins, on the virtual 8-device CPU mesh (tests/conftest.py):

- format: shard-per-ownership-group layout, manifest written last,
  checksums, ``latest_sharded`` sees only complete checkpoints;
- crash consistency: an async save is byte-identical to a synchronous
  save of the same step; a SIGKILL mid-write leaves the previous
  checkpoint as the newest (subprocess, real SIGKILL);
- fault injection: a writer-thread failure (full-disk class) fails the
  NEXT save()/wait() loudly and never corrupts the previous checkpoint;
  a missing shard is named (shard, group, rank); a manifest version
  mismatch raises with both versions;
- any-topology restore: save under pp=4 / ZeRO dp=8, restore under
  pp=2 / single-program / dp=4 and continue to parity with the
  uninterrupted run (f32 rtol 2e-5 across topologies — microbatch
  summation order, same bound as test_pipeline; BITWISE at the same
  topology); sharded→monolithic export loads as legacy params;
- elastic resume v2: ``MXNET_CKPT_EVERY_N_STEPS`` writes mid-epoch
  sharded checkpoints from ``fit_elastic``; a crash resumes from the
  last interval (params + optimizer state + update count) to parity
  with the uninterrupted run, including at a DIFFERENT topology
  (MXNET_PP toggled between save and resume);
- telemetry: ckpt.save/ckpt.wait/ckpt.write spans + ckpt_bytes/
  ckpt_pending gauges, strict no-op with telemetry off;
- tools/ckpt.py: render, --json, --verify exit codes.
"""
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
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import elastic
from mxnet_tpu.parallel.mesh import make_mesh, make_pp_mesh
from mxnet_tpu.train import TrainStep, PipelineTrainStep

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))
BATCH = 8
RTOL, ATOL = 2e-5, 1e-6


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


def _plain_ts(policy=None):
    ts = TrainStep(_mlp(), _opt(), policy=policy)
    p, s, a = ts.init(*SHAPES, seed=3)
    return ts, p, s, a


def _pp_ts(pp, dp=1, M=2, zero=False):
    mesh = make_pp_mesh(pp, dp=dp, devices=jax.devices()[:pp * dp])
    ts = PipelineTrainStep(_mlp(), _opt(), mesh=mesh, num_microbatches=M,
                           zero=zero)
    p, s, a = ts.init(*SHAPES, seed=3)
    return ts, p, s, a


def _steps(ts, p, s, a, batch, n, key=7):
    rng = jax.random.PRNGKey(key)
    b = ts.shard_batch(batch)
    for _ in range(n):
        p, s, a, o = ts(p, s, a, b, rng=rng)
    return p, s, a, o


def _close(got, want, rtol=RTOL, atol=ATOL, what=""):
    for n in sorted(want):
        np.testing.assert_allclose(np.asarray(got[n]), np.asarray(want[n]),
                                   rtol=rtol, atol=atol,
                                   err_msg="%s: %s" % (what, n))


# ----------------------------------------------------------- format basics
def test_save_layout_and_manifest(tmp_path):
    ts, p, s, a = _plain_ts()
    p, s, a, _ = _steps(ts, p, s, a, _batch(), 2)
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a, epoch=1, nbatch=3)
    assert path.endswith("-step00000002.ckpt")
    files = sorted(os.listdir(path))
    assert files == ["manifest.json", "stage0-opt.params", "stage0.params"]
    man = ckpt.load_manifest(path)
    assert man["step"] == 2 and man["epoch"] == 1 and man["nbatch"] == 3
    assert man["topology"] == {"pp": 1, "dp": 1, "zero": False,
                               "microbatches": None, "world": 1}
    assert set(man["stage_of"]) == set(ts.param_names + ts.aux_names)
    assert man["params"]["fc1_weight"]["shape"] == [16, 32]
    for meta in man["shards"].values():
        full = os.path.join(path, meta["group"] + ".params")
        assert os.path.getsize(full) == meta["bytes"]
    assert ckpt.latest_sharded(str(tmp_path / "m")) == path


def test_latest_sharded_ignores_incomplete(tmp_path):
    ts, p, s, a = _plain_ts()
    p, s, a, _ = _steps(ts, p, s, a, _batch(), 1)
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    first = cp.save(ts, p, s, a)
    # a later save interrupted before its manifest landed: invisible
    half = ckpt.checkpoint_dir(str(tmp_path / "m"), 9)
    os.makedirs(half)
    with open(os.path.join(half, "stage0.params"), "wb") as f:
        f.write(b"partial")
    assert ckpt.latest_sharded(str(tmp_path / "m")) == first
    with pytest.raises(MXNetError, match="manifest"):
        ckpt.load_manifest(half)


def test_manifest_version_mismatch_names_both(tmp_path):
    ts, p, s, a = _plain_ts()
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    mpath = os.path.join(path, "manifest.json")
    man = json.load(open(mpath))
    man["version"] = 99
    with open(mpath, "w") as f:
        json.dump(man, f)
    with pytest.raises(MXNetError, match=r"version 99.*version %d"
                       % ckpt.VERSION):
        ckpt.load_sharded(path)


def test_missing_shard_names_shard_and_rank(tmp_path):
    ts, p, s, a = _plain_ts()
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    os.remove(os.path.join(path, "stage0-opt.params"))
    with pytest.raises(MXNetError, match=r"stage0-opt\.params.*group "
                       r"stage0-opt.*rank 0"):
        ckpt.load_sharded(path)


def test_corrupt_shard_checksum_named(tmp_path):
    ts, p, s, a = _plain_ts()
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    target = os.path.join(path, "stage0.params")
    blob = bytearray(open(target, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(target, "wb") as f:
        f.write(bytes(blob))
    with pytest.raises(MXNetError, match="corrupt"):
        ckpt.load_sharded(path)
    # verification is opt-out for trusted/local reads
    man, params, opt, aux = ckpt.load_sharded(path, verify=False)
    assert "fc1_weight" in params


def test_latest_sharded_orders_by_position_not_filename(tmp_path):
    """A resumed run whose update counter restarted (mono-epoch resume)
    writes LOWER step numbers than stale pre-crash checkpoints — the
    manifest's (epoch, nbatch, step) position decides newest, not the
    filename."""
    ts, p, s, a = _plain_ts()
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    cp.save(ts, p, s, a, step=9, epoch=0, nbatch=3)       # pre-crash
    fresh = cp.save(ts, p, s, a, step=3, epoch=2, nbatch=0)  # post-resume
    assert ckpt.latest_sharded(str(tmp_path / "m")) == fresh


def test_rewrite_same_step_stays_consistent(tmp_path):
    """Re-writing an existing checkpoint dir (step-number collision after
    a counter restart) drops the stale manifest FIRST: the final state is
    fully consistent (new manifest over new shards, crc-verifiable) and a
    kill mid-rewrite could only ever leave a manifest-less dir."""
    ts, p, s, a = _plain_ts()
    prefix = str(tmp_path / "m")
    cp = ckpt.Checkpointer(prefix, async_=False)
    first = cp.save(ts, p, s, a, step=2, epoch=0, nbatch=1)
    p, s, a, _ = _steps(ts, p, s, a, _batch(), 1)   # different content
    second = cp.save(ts, p, s, a, step=2, epoch=1, nbatch=1)
    assert first == second
    man = ckpt.verify_checkpoint(second)            # crc table matches
    assert man["epoch"] == 1


# ------------------------------------------------------------------- async
def test_async_byte_identical_to_sync(tmp_path):
    ts, p, s, a = _plain_ts()
    p, s, a, _ = _steps(ts, p, s, a, _batch(), 2)
    sync = ckpt.Checkpointer(str(tmp_path / "sync"), async_=False)
    path_s = sync.save(ts, p, s, a, epoch=1, nbatch=1)
    anc = ckpt.Checkpointer(str(tmp_path / "anc"), async_=True)
    path_a = anc.save(ts, p, s, a, epoch=1, nbatch=1)
    anc.wait()
    anc.close()
    assert sorted(os.listdir(path_s)) == sorted(os.listdir(path_a))
    for f in os.listdir(path_s):
        assert open(os.path.join(path_s, f), "rb").read() == \
            open(os.path.join(path_a, f), "rb").read(), f


def test_async_env_default_and_no_thread_before_save(monkeypatch,
                                                     tmp_path):
    monkeypatch.delenv("MXNET_CKPT_ASYNC", raising=False)
    cp = ckpt.Checkpointer(str(tmp_path / "m"))
    assert cp._async and cp._thread is None
    monkeypatch.setenv("MXNET_CKPT_ASYNC", "0")
    cp2 = ckpt.Checkpointer(str(tmp_path / "m2"))
    assert not cp2._async
    ts, p, s, a = _plain_ts()
    cp2.save(ts, p, s, a)
    assert cp2._thread is None          # sync mode never starts a thread


def test_writer_failure_fails_next_save_loudly(tmp_path, monkeypatch):
    """The full-disk class: the writer thread's failure surfaces on the
    NEXT save()/wait() as an MXNetError naming the cause — and the
    previously completed checkpoint is untouched."""
    ts, p, s, a = _plain_ts()
    prefix = str(tmp_path / "m")
    cp = ckpt.Checkpointer(prefix, async_=True)
    good = cp.save(ts, p, s, a, step=1)
    cp.wait()
    real = ckpt.write_snapshot

    def full_disk(dirname, job):
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(ckpt, "write_snapshot", full_disk)
    _steps(ts, p, s, a, _batch(), 1)
    cp.save(ts, p, s, a, step=2)
    with pytest.raises(MXNetError, match="No space left"):
        cp.wait()
    monkeypatch.setattr(ckpt, "write_snapshot", real)
    # previous checkpoint intact and still the newest
    assert ckpt.latest_sharded(prefix) == good
    man = ckpt.verify_checkpoint(good)
    assert man["step"] == 1
    cp.close()


@pytest.mark.timeout(180)
def test_sigkill_mid_write_keeps_previous_latest(tmp_path):
    """Real SIGKILL between the second save's shards and its manifest:
    the first checkpoint must remain the newest complete one."""
    script = tmp_path / "child.py"
    script.write_text("""
import os, signal, sys
sys.path.insert(0, %r)
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ckpt
from mxnet_tpu.train import TrainStep
import mxnet_tpu.base as base

d = mx.sym.Variable("data")
h = mx.sym.FullyConnected(d, name="fc1", num_hidden=8)
net = mx.sym.SoftmaxOutput(h, name="softmax")
ts = TrainStep(net, mx.optimizer.SGD(learning_rate=0.1))
p, s, a = ts.init({"data": (4, 6)}, {"softmax_label": (4,)})
cp = ckpt.Checkpointer(%r, async_=False)
ts.num_update = 1
cp.save(ts, p, s, a)
print("FIRST OK", flush=True)

real = ckpt.atomic_write
class kill_at_manifest(object):
    def __init__(self, fname, *a, **k):
        if fname.endswith("manifest.json"):
            os.kill(os.getpid(), signal.SIGKILL)
        self._w = real(fname, *a, **k)
    def __enter__(self):
        return self._w.__enter__()
    def __exit__(self, *exc):
        return self._w.__exit__(*exc)
ckpt.atomic_write = kill_at_manifest
ts.num_update = 2
cp.save(ts, p, s, a)
print("UNREACHABLE", flush=True)
""" % (ROOT, str(tmp_path / "m")))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=150)
    assert "FIRST OK" in proc.stdout
    assert "UNREACHABLE" not in proc.stdout
    assert proc.returncode == -signal.SIGKILL
    latest = ckpt.latest_sharded(str(tmp_path / "m"))
    assert latest is not None and latest.endswith("-step00000001.ckpt")
    # the interrupted step-2 dir exists but is invisible (no manifest)
    half = ckpt.checkpoint_dir(str(tmp_path / "m"), 2)
    assert os.path.isdir(half)
    assert not os.path.exists(os.path.join(half, "manifest.json"))
    ckpt.verify_checkpoint(latest)


# -------------------------------------------------- any-topology restore
def test_restore_pp4_to_pp2_and_single_parity(tmp_path):
    batch = _batch()
    ts, p, s, a = _pp_ts(4, M=2)
    rng = jax.random.PRNGKey(7)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, batch, rng=rng)
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, batch, rng=rng)
    ref = {n: np.asarray(v) for n, v in p.items()}

    ts2, p2, s2, a2 = _pp_ts(2, M=2)
    p2, s2, a2, man = ckpt.restore_into(ts2, path)
    assert ts2.num_update == 2 and man["topology"]["pp"] == 4
    for _ in range(2):
        p2, s2, a2, _ = ts2(p2, s2, a2, batch, rng=rng)
    _close(p2, ref, what="pp4->pp2")

    ts3 = TrainStep(_mlp(), _opt())
    p3, s3, a3, _ = ckpt.restore_into(ts3, path)
    b3 = ts3.shard_batch(batch)
    for _ in range(2):
        p3, s3, a3, _ = ts3(p3, s3, a3, b3, rng=rng)
    _close(p3, ref, what="pp4->single")


def test_restore_single_to_pp_parity(tmp_path):
    """The opposite direction: a single-program (monolithic-topology)
    sharded save restores onto a pipeline mesh."""
    batch = _batch()
    ts, p, s, a = _plain_ts()
    rng = jax.random.PRNGKey(7)
    b = ts.shard_batch(batch)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, b, rng=rng)
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, b, rng=rng)
    ref = {n: np.asarray(v) for n, v in p.items()}
    ts2, p2, s2, a2 = _pp_ts(2, M=2)
    p2, s2, a2, _ = ckpt.restore_into(ts2, path)
    for _ in range(2):
        p2, s2, a2, _ = ts2(p2, s2, a2, batch, rng=rng)
    _close(p2, ref, what="single->pp2")


def test_restore_same_topology_bitwise(tmp_path):
    """No resharding, no reordering: restore at the SAVING topology and
    continue — bitwise equal to the uninterrupted run."""
    batch = _batch()
    ts, p, s, a = _plain_ts()
    rng = jax.random.PRNGKey(9)
    b = ts.shard_batch(batch)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, b, rng=rng)
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    for _ in range(3):
        p, s, a, _ = ts(p, s, a, b, rng=rng)
    ts2 = TrainStep(_mlp(), _opt())
    p2, s2, a2, _ = ckpt.restore_into(ts2, path)
    b2 = ts2.shard_batch(batch)
    for _ in range(3):
        p2, s2, a2, _ = ts2(p2, s2, a2, b2, rng=rng)
    for n in p:
        assert np.asarray(p[n]).tobytes() == np.asarray(p2[n]).tobytes(), n


def test_restore_zero_dp8_to_dp4_and_replicated(tmp_path):
    batch = _batch()
    mesh8 = make_mesh({"dp": 8})
    ts = TrainStep(_mlp(), _opt(), mesh=mesh8, zero=True)
    p, s, a = ts.init(*SHAPES, seed=3)
    rng = jax.random.PRNGKey(7)
    b = ts.shard_batch(batch)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, b, rng=rng)
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    man = ckpt.load_manifest(path)
    assert man["topology"]["zero"] and man["topology"]["dp"] == 8
    # one zero shard file per dp row
    zrows = [f for f in man["shards"] if "-zero" in f]
    assert len(zrows) == 8
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, b, rng=rng)
    ref = {n: np.asarray(v) for n, v in p.items()}

    mesh4 = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    ts2 = TrainStep(_mlp(), _opt(), mesh=mesh4, zero=True)
    p2, s2, a2, _ = ckpt.restore_into(ts2, path)
    b2 = ts2.shard_batch(batch)
    for _ in range(2):
        p2, s2, a2, _ = ts2(p2, s2, a2, b2, rng=rng)
    _close(p2, ref, what="zero dp8->dp4")

    # sharded ZeRO state restores into a REPLICATED optimizer too
    ts3 = TrainStep(_mlp(), _opt())
    p3, s3, a3, _ = ckpt.restore_into(ts3, path)
    b3 = ts3.shard_batch(batch)
    for _ in range(2):
        p3, s3, a3, _ = ts3(p3, s3, a3, b3, rng=rng)
    _close(p3, ref, what="zero->replicated")


@pytest.mark.parametrize("dp_new", [2, 3])
def test_restore_zero1_dp4_to_another_dp(tmp_path, dp_new):
    """Saved on ``dp`` = 4, where every leaf's state lies in the leaf's
    own shape (4 divides the leading axes 16, 16 and 8), resumed on
    ``dp`` = 2 (in shape again, cut in halves) and on ``dp`` = 3 (which
    divides none of them: the flat ``(dp, chunk)`` view): the checkpoint
    holds logical tensors, the plan of the resuming step cuts them anew,
    and training continues to the uninterrupted run's parameters."""
    rows = 12                               # divisible by 4, 2 and 3
    rs = np.random.RandomState(0)
    batch = {"data": rs.uniform(-1, 1, (rows, 32)).astype(np.float32),
             "softmax_label": rs.randint(0, 8, (rows,)).astype(np.float32)}
    shapes = ({"data": (rows, 32)}, {"softmax_label": (rows,)})

    def step_on(dp):
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                               rescale_grad=1.0 / rows)
        return TrainStep(_mlp(), opt, zero=1, mesh=make_mesh(
            {"dp": dp}, devices=jax.devices()[:dp]))

    ts = step_on(4)
    p, s, a = ts.init(*shapes, seed=3)
    rng = jax.random.PRNGKey(7)
    b = ts.shard_batch(batch)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, b, rng=rng)
    for n, st in s.items():
        assert all(leaf.shape == p[n].shape for leaf in st), n
    path = ckpt.Checkpointer(str(tmp_path / "m"), async_=False).save(
        ts, p, s, a)
    man = ckpt.load_manifest(path)
    assert man["topology"]["zero"] == 1 and man["topology"]["dp"] == 4
    # one shard file a dp index, each leaf's part in it as one flat row
    assert len([f for f in man["shards"] if "-zero" in f]) == 4
    _man, lp, ls, _la = ckpt.load_sharded(path)
    for n, st in s.items():
        for got, live in zip(ls[n], st):
            assert got.shape == lp[n].shape
            np.testing.assert_array_equal(got, np.asarray(live), err_msg=n)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, b, rng=rng)
    ref = {n: np.asarray(v) for n, v in p.items()}

    ts2 = step_on(dp_new)
    p2, s2, a2, _ = ckpt.restore_into(ts2, path)
    for n, st in s2.items():
        kept = p2[n].shape[0] % dp_new == 0
        assert kept is (dp_new == 2), n
        want = tuple(p2[n].shape) if kept \
            else (dp_new, -(-p2[n].size // dp_new))
        assert all(tuple(leaf.shape) == want for leaf in st), n
    b2 = ts2.shard_batch(batch)
    for _ in range(2):
        p2, s2, a2, _ = ts2(p2, s2, a2, b2, rng=rng)
    _close(p2, ref, what="zero1 dp4->dp%d" % dp_new)


def _zero_ts(level, dp=8, pp=0, M=2):
    if pp:
        mesh = make_pp_mesh(pp, dp=dp, devices=jax.devices()[:pp * dp])
        ts = PipelineTrainStep(_mlp(), _opt(), mesh=mesh,
                               num_microbatches=M, zero=level)
    else:
        mesh = make_mesh({"dp": dp}, devices=jax.devices()[:dp])
        ts = TrainStep(_mlp(), _opt(), mesh=mesh, zero=level)
    p, s, a = ts.init(*SHAPES, seed=3)
    return ts, p, s, a


def _logical(ts, p):
    if getattr(ts, "zero", 0) >= 3:
        return {n: ts.unflatten_host(n, np.asarray(v))
                for n, v in p.items()}
    return {n: np.asarray(v) for n, v in p.items()}


@pytest.mark.parametrize("level", [2, 3])
def test_restore_zero2_zero3_to_replicated(tmp_path, level):
    """A zero2/zero3 save (manifest carries the LEVEL; level-3 params
    live as per-row argz entries) restores into a plain replicated step
    and continues at parity."""
    batch = _batch()
    ts, p, s, a = _zero_ts(level)
    rng = jax.random.PRNGKey(7)
    b = ts.shard_batch(batch)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, b, rng=rng)
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    man = ckpt.load_manifest(path)
    assert man["topology"]["zero"] == level
    if level >= 3:
        # params are flat rows, but the manifest shapes stay LOGICAL
        assert man["params"]["fc1_weight"]["shape"] == [16, 32]
        assert len([f for f in man["shards"] if "-zero" in f]) == 8
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, b, rng=rng)
    ref = _logical(ts, p)

    ts2 = TrainStep(_mlp(), _opt())
    p2, s2, a2, _ = ckpt.restore_into(ts2, path)
    b2 = ts2.shard_batch(batch)
    for _ in range(2):
        p2, s2, a2, _ = ts2(p2, s2, a2, b2, rng=rng)
    _close(p2, ref, what="zero%d->replicated" % level)


def test_restore_zero3_dp8_to_dp4(tmp_path):
    """zero3 dp=8 -> zero3 dp=4: the flat param/state rows re-chunk to
    the restoring mesh's dp."""
    batch = _batch()
    ts, p, s, a = _zero_ts(3, dp=8)
    rng = jax.random.PRNGKey(7)
    b = ts.shard_batch(batch)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, b, rng=rng)
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, b, rng=rng)
    ref = _logical(ts, p)

    ts2, _p, _s, _a = _zero_ts(3, dp=4)
    p2, s2, a2, _ = ckpt.restore_into(ts2, path)
    assert all(v.shape[0] == 4 for v in p2.values())
    b2 = ts2.shard_batch(batch)
    for _ in range(2):
        p2, s2, a2, _ = ts2(p2, s2, a2, b2, rng=rng)
    _close(_logical(ts2, p2), ref, what="zero3 dp8->dp4")


def test_restore_zero3_pp_to_single(tmp_path):
    """A zero3 x pp=2 save (per-stage flat rows) restores into one
    single-program replicated step and continues at parity."""
    batch = _batch()
    ts, p, s, a = _zero_ts(3, dp=2, pp=2)
    rng = jax.random.PRNGKey(7)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, batch, rng=rng)
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    man = ckpt.load_manifest(path)
    assert man["topology"]["zero"] == 3 and man["topology"]["pp"] == 2
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, batch, rng=rng)
    ref = _logical(ts, p)

    ts2 = TrainStep(_mlp(), _opt())
    p2, s2, a2, _ = ckpt.restore_into(ts2, path)
    b2 = ts2.shard_batch(batch)
    for _ in range(2):
        p2, s2, a2, _ = ts2(p2, s2, a2, b2, rng=rng)
    _close(p2, ref, rtol=2e-5, atol=1e-6, what="zero3xpp2->single")


def test_export_monolithic_roundtrip(tmp_path):
    ts, p, s, a = _pp_ts(2, M=1)
    rng = jax.random.PRNGKey(7)
    batch = _batch()
    p, s, a, _ = ts(p, s, a, batch, rng=rng)
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    mono = str(tmp_path / "legacy-0001.params")
    ckpt.export_monolithic(path, mono)
    loaded = mx.nd.load(mono)
    for n in ts.param_names:
        np.testing.assert_array_equal(np.asarray(loaded["arg:%s" % n].value),
                                      np.asarray(p[n]))


def test_restore_amp_scale_state(tmp_path):
    from mxnet_tpu import amp
    pol = amp.Policy(compute_dtype="float32", loss_scale=2048.0)
    ts, p, s, a = _plain_ts(policy=pol)
    batch = _batch()
    b = ts.shard_batch(batch)
    rng = jax.random.PRNGKey(7)
    for _ in range(2):
        p, s, a, _ = ts(p, s, a, b, rng=rng)
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    man = ckpt.load_manifest(path)
    assert man["extra"]["loss_scale"]["scale"] == 2048.0
    assert man["extra"]["loss_scale"]["good"] == 2
    ts2, p2, s2, a2 = _plain_ts(policy=amp.Policy(
        compute_dtype="float32", loss_scale=2048.0))
    p2, s2, a2, _ = ckpt.restore_into(ts2, path)
    got = ts2.scale_state_host()
    assert got["scale"] == 2048.0 and got["good"] == 2
    # the automaton continues: next finite step increments good
    p2, s2, a2, _ = ts2(p2, s2, a2, ts2.shard_batch(batch), rng=rng)
    assert ts2.scale_state_host()["good"] == 3


def test_restore_missing_param_named(tmp_path):
    ts, p, s, a = _plain_ts()
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    path = cp.save(ts, p, s, a)
    other = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), name="zz",
                              num_hidden=4), name="softmax")
    ts2 = TrainStep(other, _opt())
    with pytest.raises(MXNetError, match="zz_bias, zz_weight"):
        ckpt.restore_into(ts2, path)
    # aux coverage is checked with the same curated error (a bare
    # KeyError from placement would hide the checkpoint path): save a
    # checkpoint that covers the params but carries no aux, restore into
    # an aux-bearing model
    bn = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.BatchNorm(
            mx.sym.Variable("data"), name="bn1", fix_gamma=False),
            name="fc", num_hidden=4), name="softmax")
    ts3 = TrainStep(bn, _opt())
    p3, s3, a3 = ts3.init(({"data": (4, 6)}, {"softmax_label": (4,)})[0],
                          {"softmax_label": (4,)})
    cp3 = ckpt.Checkpointer(str(tmp_path / "noaux"), async_=False)
    path3 = cp3.save(ts3, p3, s3, {})
    ts4 = TrainStep(bn, _opt())
    with pytest.raises(MXNetError, match="aux state.*bn1_moving"):
        ckpt.restore_into(ts4, path3)


# --------------------------------------------------------- elastic resume
def _blob_data(n=120, nc=4, dim=16, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(nc, dim) * 3
    y = rng.randint(0, nc, n)
    x = (centers[y] + rng.randn(n, dim)).astype(np.float32)
    return x, y.astype(np.float32)


def _elastic_mlp(nc=4):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=nc, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


class _Boom(Exception):
    pass


def _crash_after(n):
    state = {"n": 0}

    def cb(param):
        state["n"] += 1
        if state["n"] == n:
            raise _Boom()
    return cb


def test_fit_elastic_step_interval_and_midepoch_resume(tmp_path,
                                                       monkeypatch):
    """The headline: MXNET_CKPT_EVERY_N_STEPS writes sharded async
    checkpoints mid-epoch; after a crash the respawn resumes from the
    newest interval — optimizer state, update count and data position
    included — and finishes at parity with the uninterrupted run."""
    monkeypatch.setenv("MXNET_CKPT_EVERY_N_STEPS", "3")
    x, y = _blob_data()
    kw = dict(optimizer="sgd",
              optimizer_params={"learning_rate": 0.1, "momentum": 0.9})

    def iter_():
        return mx.io.NDArrayIter(x, y, batch_size=30)

    mx.random.seed(11)
    ref = mx.Module(_elastic_mlp(), context=mx.cpu())
    elastic.fit_elastic(ref, iter_(), str(tmp_path / "ref"), num_epoch=3,
                        **kw)
    ref_params = {k: v.asnumpy() for k, v in ref.get_params()[0].items()}
    # interval checkpoints exist: 4 batches/epoch * 3 epochs = steps 3,6,9,12
    steps = sorted(int(p[-13:-5]) for p in
                   [f for f in os.listdir(tmp_path)
                    if f.startswith("ref-step")])
    assert steps == [3, 6, 9, 12]

    prefix = str(tmp_path / "el")
    mx.random.seed(11)
    m1 = mx.Module(_elastic_mlp(), context=mx.cpu())
    with pytest.raises(_Boom):
        # crash at epoch 1, batch 2 — after the step-6 interval save
        elastic.fit_elastic(m1, iter_(), prefix, num_epoch=3,
                            batch_end_callback=_crash_after(7), **kw)
    latest = ckpt.latest_sharded(prefix)
    man = ckpt.load_manifest(latest)
    # at most one interval lost: the newest checkpoint is within
    # every_n_steps of the crash step (crash at update 7, ckpt at 6)
    assert man["step"] == 6 and (man["epoch"], man["nbatch"]) == (1, 1)

    mx.random.seed(11)
    m2 = mx.Module(_elastic_mlp(), context=mx.cpu())
    elastic.fit_elastic(m2, iter_(), prefix, num_epoch=3, **kw)
    got = {k: v.asnumpy() for k, v in m2.get_params()[0].items()}
    for k in ref_params:
        np.testing.assert_allclose(got[k], ref_params[k], rtol=1e-4,
                                   atol=1e-5, err_msg=k)


def test_interval_save_and_resume_sanitizer_all_raise(tmp_path,
                                                      monkeypatch):
    """Acceptance leg: the checkpoint save (async writer, batched
    device_get) and the sharded resume run CLEAN under the FULL
    sanitizer — MXNET_SAN=all:raise now includes the collective checker,
    so the writer path must hold the ledger/thread contracts too."""
    from mxnet_tpu import sanitize as san
    monkeypatch.setenv("MXNET_CKPT_EVERY_N_STEPS", "3")
    x, y = _blob_data()
    kw = dict(optimizer="sgd",
              optimizer_params={"learning_rate": 0.1, "momentum": 0.9})

    def iter_():
        return mx.io.NDArrayIter(x, y, batch_size=30)

    san.arm("all", mode="raise")
    san.reset()
    try:
        prefix = str(tmp_path / "sanck")
        mx.random.seed(11)
        m1 = mx.Module(_elastic_mlp(), context=mx.cpu())
        elastic.fit_elastic(m1, iter_(), prefix, num_epoch=2, **kw)
        assert ckpt.latest_sharded(prefix) is not None
        # a rerun resumes from the newest checkpoint — load, crc verify,
        # re-place, continue training — still fully sanitized
        mx.random.seed(11)
        m2 = mx.Module(_elastic_mlp(), context=mx.cpu())
        elastic.fit_elastic(m2, iter_(), prefix, num_epoch=3, **kw)
        s = san.stats()
        for k in ("collective_violations", "sync_violations",
                  "donate_violations", "recompile_violations"):
            assert s[k] == 0, (k, s, san.violations())
    finally:
        san.disarm()
        san.reset()


def test_fit_elastic_resume_at_different_topology(tmp_path, monkeypatch):
    """Preemption-safe world resize: checkpoints written under MXNET_PP=2
    restore into a respawn WITHOUT pipeline stages (a shrunk world) —
    the mesh is rebuilt and the sharded state re-placed, not refused."""
    monkeypatch.setenv("MXNET_CKPT_EVERY_N_STEPS", "3")
    x, y = _blob_data()
    kw = dict(optimizer="sgd",
              optimizer_params={"learning_rate": 0.1, "momentum": 0.9})

    def iter_():
        # batch 24: microbatch 12 divides the dp=4 of the 8-device
        # dp4 x pp2 mesh MXNET_PP=2 builds
        return mx.io.NDArrayIter(x, y, batch_size=24)

    prefix = str(tmp_path / "el")
    monkeypatch.setenv("MXNET_PP", "2")
    mx.random.seed(11)
    m1 = mx.Module(_elastic_mlp(), context=mx.cpu())
    with pytest.raises(_Boom):
        elastic.fit_elastic(m1, iter_(), prefix, num_epoch=3,
                            batch_end_callback=_crash_after(7), **kw)
    man = ckpt.load_manifest(ckpt.latest_sharded(prefix))
    assert man["topology"]["pp"] == 2

    monkeypatch.delenv("MXNET_PP")
    mx.random.seed(11)
    m2 = mx.Module(_elastic_mlp(), context=mx.cpu())
    elastic.fit_elastic(m2, iter_(), prefix, num_epoch=3, **kw)
    # parity bound is loose: pp2 and single-program steps sum gradients
    # in different orders, and the difference compounds over the tail
    mx.random.seed(11)
    ref = mx.Module(_elastic_mlp(), context=mx.cpu())
    monkeypatch.setenv("MXNET_CKPT_EVERY_N_STEPS", "3")
    elastic.fit_elastic(ref, iter_(), str(tmp_path / "ref"), num_epoch=3,
                        **kw)
    got = {k: v.asnumpy() for k, v in m2.get_params()[0].items()}
    refp = {k: v.asnumpy() for k, v in ref.get_params()[0].items()}
    for k in refp:
        np.testing.assert_allclose(got[k], refp[k], rtol=5e-3, atol=1e-4,
                                   err_msg=k)


def test_resume_point_prefers_newest(tmp_path):
    """Monolithic epoch checkpoints and sharded step checkpoints compose:
    the later data position wins."""
    prefix = str(tmp_path / "m")
    # monolithic: epoch 2 complete
    mx.nd.save("%s-0002.params" % prefix,
               {"arg:w": mx.nd.array(np.ones((2, 2), np.float32))})
    ts, p, s, a = _plain_ts()
    cp = ckpt.Checkpointer(prefix, async_=False)
    # sharded at (epoch 1, nbatch 3) -> position (1, 4) < (2, 0): mono wins
    cp.save(ts, p, s, a, step=5, epoch=1, nbatch=3)
    kind = elastic._resume_point(prefix)
    assert kind[0] == "mono" and kind[1] == (2, 0)
    # sharded at (epoch 2, nbatch 0) -> position (2, 1) > (2, 0): sharded
    cp.save(ts, p, s, a, step=9, epoch=2, nbatch=0)
    kind = elastic._resume_point(prefix)
    assert kind[0] == "sharded" and kind[1] == (2, 1)


def test_fit_elastic_no_env_no_sharded_ckpt(tmp_path, monkeypatch):
    """Unset interval env => pure v1 behaviour: per-epoch monolithic
    checkpoints only, no Checkpointer, no writer thread."""
    monkeypatch.delenv("MXNET_CKPT_EVERY_N_STEPS", raising=False)
    import threading
    before = {t.name for t in threading.enumerate()}
    x, y = _blob_data(n=60)
    mod = mx.Module(_elastic_mlp(), context=mx.cpu())
    elastic.fit_elastic(mod, mx.io.NDArrayIter(x, y, batch_size=30),
                        str(tmp_path / "m"), num_epoch=1,
                        optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1})
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".ckpt")]
    after = {t.name for t in threading.enumerate()}
    assert "mxtpu-ckpt-writer" not in after - before


# -------------------------------------------------------------- telemetry
def test_ckpt_telemetry_signals(tmp_path):
    tel.start()
    try:
        ts, p, s, a = _plain_ts()
        cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=True)
        cp.save(ts, p, s, a, step=1)
        cp.wait()
        cp.close()
        names = {e["name"] for e in tel.events() if e["type"] == "span"}
        assert {"ckpt.save", "ckpt.wait", "ckpt.write"} <= names
        assert tel.counters().get("ckpt_saves") == 1
        gauges = tel.gauges()
        assert gauges.get("ckpt_bytes", 0) > 0
        assert "ckpt_pending" in gauges
    finally:
        tel.stop()


def test_ckpt_telemetry_strict_noop(tmp_path):
    assert not tel.enabled()
    # delta-based: the registry keeps the LAST session's events after
    # stop(), so assert the disabled save adds nothing
    n_events = len(tel.events())
    counters = dict(tel.counters())
    ts, p, s, a = _plain_ts()
    cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
    cp.save(ts, p, s, a)
    assert len(tel.events()) == n_events
    assert tel.counters() == counters


# ------------------------------------------------------------ tools/ckpt.py
def _load_ckpt_tool():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ckpt_tool", os.path.join(ROOT, "tools", "ckpt.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ckpt_tool_render_verify_json(tmp_path, capsys):
    tool = _load_ckpt_tool()
    ts, p, s, a = _pp_ts(2, M=1)
    batch = _batch()
    p, s, a, _ = ts(p, s, a, batch, rng=jax.random.PRNGKey(1))
    prefix = str(tmp_path / "m")
    cp = ckpt.Checkpointer(prefix, async_=False)
    path = cp.save(ts, p, s, a, epoch=2, nbatch=1)
    # prefix resolution + render + verify ok
    assert tool.main([prefix, "--verify"]) == 0
    out = capsys.readouterr().out
    assert "pp=2" in out and "Stage partition" in out \
        and "all shards ok" in out
    # --json carries the topology and shard table
    assert tool.main([path, "--json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["topology"]["pp"] == 2 and data["step"] == 1
    # corrupt a shard: --verify exits 2 naming it
    shard = sorted(f for f in os.listdir(path) if f.endswith(".params"))[0]
    with open(os.path.join(path, shard), "ab") as f:
        f.write(b"x")
    assert tool.main([path, "--verify"]) == 2
    assert shard in capsys.readouterr().out


@pytest.mark.slow
@pytest.mark.timeout(300)
def test_restore_matrix_f64_parity(tmp_path):
    """The dryrun-grade pin: the whole restore matrix in f64 at 1e-9 —
    reduction-order noise cannot mask (or fake) a real resharding bug.
    Mirrors __graft_entry__'s f64 idiom (enable x64, cast the pytrees,
    restore the flag in a finally)."""
    import jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)
    try:
        batch = {k: v.astype(np.float64) for k, v in _batch().items()}
        rng = jax.random.PRNGKey(7)

        def to64(p, s, a):
            return ({k: v.astype(jnp.float64) for k, v in p.items()},
                    {k: tuple(x.astype(jnp.float64) for x in st)
                     for k, st in s.items()},
                    {k: v.astype(jnp.float64) for k, v in a.items()})

        ts, p, s, a = _pp_ts(4, M=2)
        p, s, a = to64(p, s, a)
        for _ in range(2):
            p, s, a, _o = ts(p, s, a, batch, rng=rng)
        cp = ckpt.Checkpointer(str(tmp_path / "m"), async_=False)
        path = cp.save(ts, p, s, a)
        for _ in range(2):
            p, s, a, _o = ts(p, s, a, batch, rng=rng)
        ref = {n: np.asarray(v) for n, v in p.items()}

        for make in (lambda: _pp_ts(2, M=2)[0],
                     lambda: TrainStep(_mlp(), _opt())):
            ts2 = make()
            p2, s2, a2, _man = ckpt.restore_into(ts2, path)
            assert np.asarray(p2[ts2.param_names[0]]).dtype == np.float64
            b2 = ts2.shard_batch(batch)
            for _ in range(2):
                p2, s2, a2, _o = ts2(p2, s2, a2, b2, rng=rng)
            _close(p2, ref, rtol=1e-9, atol=1e-10,
                   what="f64 restore %s" % type(ts2).__name__)
    finally:
        jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------- fault-injection e2e
_E2E_CHILD = """
import os, signal, sys, time
sys.path.insert(0, %(root)r)
import numpy as np
import threading
import mxnet_tpu as mx
from mxnet_tpu.parallel import elastic
from mxnet_tpu import checkpoint as ckpt

rank = int(os.environ["MXTPU_PROCESS_ID"])
attempt = int(os.environ["MXTPU_RESTART_COUNT"])
prefix = %(prefix)r

# failure-detection signals up front: the barrier-bounded health check
# passes on a live world, and the hang watchdog is armed
assert elastic.health_check(timeout=120), "world unhealthy at start"
print("HEALTH OK rank", rank, "attempt", attempt, flush=True)
assert any(t.name == "mxtpu-watchdog" for t in threading.enumerate()), \\
    "watchdog not armed"

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

resume = elastic._resume_point(prefix)
if resume is not None:
    print("RESUME kind=%%s pos=%%s" %% (resume[0], resume[1]), flush=True)
    if resume[0] == "sharded":
        man = ckpt.load_manifest(resume[2])
        print("RESUME step=%%d" %% man["step"], flush=True)

from mxnet_tpu.parallel import dist

state = {"n": 0}
def lockstep_then_maybe_die(param):
    # per-batch lockstep (coordination-service barrier, like a real
    # data-parallel world's gradient collective): without it the
    # surviving rank races whole epochs ahead of the victim before the
    # supervisor tears the world down, and the epoch checkpoint would
    # mask the mid-epoch sharded one this test pins
    state["n"] += 1
    dist.coordination_barrier("a%%d-b%%d" %% (attempt, state["n"]))
    # rank 1, first attempt: SIGKILL mid-epoch-1, one batch after the
    # step-6 interval checkpoint was enqueued (slack for the async writer)
    if rank == 1 and attempt == 0 and state["n"] == 7:
        time.sleep(0.8)
        os.kill(os.getpid(), signal.SIGKILL)

mx.random.seed(11)
mod = mx.Module(net, context=mx.cpu())
elastic.fit_elastic(mod, it, prefix, num_epoch=3,
                    batch_end_callback=lockstep_then_maybe_die,
                    optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1,
                                      "momentum": 0.9})
acc = mod.score(mx.io.NDArrayIter(x, y, batch_size=30), "acc")[0][1]
print("OK rank", rank, "acc %%.3f" %% acc, flush=True)
"""


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_sigkill_respawn_resume_e2e(tmp_path):
    """The acceptance path: a 2-process ``launch_local --max-restarts``
    world, rank 1 SIGKILLed mid-epoch; the supervisor tears down and
    respawns the world, which resumes from the last step-interval sharded
    checkpoint (at most one interval lost) and finishes.  The merged
    fleet telemetry shows the ckpt.* signals from both ranks."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import telemetry_agg as agg
    finally:
        sys.path.pop(0)
    prefix = str(tmp_path / "el")
    child = tmp_path / "child.py"
    child.write_text(_E2E_CHILD % {"root": ROOT, "prefix": prefix})
    tfile = str(tmp_path / "telemetry.jsonl")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_CKPT_EVERY_N_STEPS"] = "3"
    env["MXNET_TELEMETRY"] = tfile
    env["MXNET_WATCHDOG_SEC"] = "300"
    env["MXNET_DIAG_DIR"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", "2", "--max-restarts", "2",
         sys.executable, "-u", str(child)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=540)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-6000:]
    # the killed attempt triggered exactly the elastic supervisor path
    assert "elastic restart 1/2" in out
    # the respawn resumed from the last step-interval sharded checkpoint:
    # 4 batches/epoch, kill at global batch 8 (epoch 1, nbatch 3), saves
    # at steps 3 and 6 — at most one interval (steps 7-8) replayed
    assert "RESUME kind=sharded" in out
    assert "RESUME step=6" in out
    # both ranks of the respawn finished, trained to separable-blob acc
    # (the two ranks' prints can interleave on one line — match tokens)
    import re
    accs = re.findall(r"acc (\d\.\d+)", out)
    assert len(accs) == 2, out[-4000:]
    for acc in accs:
        assert float(acc) > 0.9, accs
    # health check + watchdog signals fired on every attempt
    assert out.count("HEALTH OK") >= 4
    # attempt-1 interval checkpoints landed after the resume
    latest = ckpt.latest_sharded(prefix)
    man = ckpt.load_manifest(latest)
    assert man["step"] in (9, 12)
    ckpt.verify_checkpoint(latest)
    # monolithic epoch checkpoints were rank-0-only and atomic: the
    # newest validates (no torn interleaving from concurrent writers)
    assert elastic.latest_checkpoint(prefix) == 3
    # merged fleet view: both ranks' ckpt.* signals visible
    files = agg.rank_files(tfile)
    assert len(files) == 2
    merged = agg.aggregate(files)
    assert merged["counters"].get("ckpt_saves", 0) >= 2
    assert "ckpt.save" in merged["histograms"]
    assert "ckpt.write" in merged["histograms"]
