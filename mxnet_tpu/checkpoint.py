"""Sharded, asynchronous, any-topology checkpointing — elastic training v2.

The reference's fault story (PAPER.md §5.3) is ps-lite heartbeats plus a
whole-world restart from a monolithic per-epoch ``prefix-NNNN.params``
(``--load-epoch``).  PR 3/4 modernised *detection* (watchdog, barrier-bounded
``health_check``) and tools/launch.py ``--max-restarts`` supervises respawn —
but recovery still cost a monolithic save and a whole epoch of lost work, and
the monolithic format cannot even represent what the runtime already shards
(pipeline stages partition parameters, ZeRO-1 shards optimizer state over dp).
This module replaces it:

* **Sharded format** — a checkpoint is a DIRECTORY ``<prefix>-stepNNNNNNNN.ckpt``
  of per-ownership-group shard files in the ``.params`` byte format
  (``ndarray.serialize_arrays``) plus a ``manifest.json``:

  - ``stage<k>.params``       parameters + aux of pipeline stage ``k``
                              (single-program = everything in stage 0);
  - ``stage<k>-opt.params``   stage ``k``'s optimizer state (replicated mode);
  - ``stage<k>-zero<j>.params``  part ``j`` of stage ``k``'s ZeRO
                              shards, each as one flat row (row ``j`` of
                              a flat ``(dp, chunk)`` view; of a level-1
                              leaf kept in its shape, part ``j`` of its
                              leading axis): optimizer state
                              (``opt:`` entries, level >= 1) and, at
                              ZeRO level 3, the parameters themselves
                              (``argz:`` entries — logical shapes ride
                              the manifest);
  - ``manifest.json``         mesh/stage topology (incl. the ZeRO level),
                              the stage partition map, per-shard
                              checksums, logical shapes, global
                              step/epoch, format version — written LAST.

  Under a multi-process world the groups are distributed round-robin over
  ranks so no two ranks ever write one file, and rank 0 writes the manifest
  after a barrier.  (Every rank holds a full replica in this runtime's
  process model, so each rank can serialise every group for the checksum
  table while writing only its own to disk.)

* **Async writer** — :meth:`Checkpointer.save` snapshots the device pytrees
  (ONE batched device→host fetch: the live arrays are donated into the next
  step, so holding bare references would read deleted buffers) and hands the
  host snapshot to a lazily-created daemon writer thread through a bounded
  queue; training continues while serialisation, fsync and rename happen off
  the hot path.  :meth:`Checkpointer.wait` is the durability barrier.  A
  writer failure (full disk, dead mount) is re-raised loudly by the NEXT
  ``save()``/``wait()`` — and can never corrupt the previous checkpoint.

* **Crash consistency** — every shard and the manifest are written via
  write-to-temp + fsync + atomic rename (``base.atomic_write``), and the
  manifest is written last: a checkpoint either fully exists (manifest
  present, checksums verifiable) or is invisible to :func:`latest_sharded`.

* **Any-topology restore** — :func:`load_sharded` reassembles LOGICAL host
  tensors from the shards (ZeRO rows are concatenated, un-padded and
  reshaped; stage files are merged), and ``place_checkpoint`` on the
  restoring TrainStep/PipelineTrainStep re-shards them onto the CURRENT
  mesh: pp4→pp2, dp8→dp6, pp→single-program and sharded→monolithic all
  restore to parity with the saving run (docs/elastic.md has the matrix).

Telemetry (strict no-op when telemetry is off): ``ckpt.save`` /
``ckpt.wait`` / ``ckpt.write`` spans, ``ckpt_bytes`` / ``ckpt_pending``
gauges, ``ckpt_saves`` counter.
"""
from __future__ import annotations

import glob
import json
import logging
import os
import re
import threading
import time
import zlib

import numpy as _np

from .base import MXNetError, atomic_write, get_env
from . import telemetry as _tel

_LOG = logging.getLogger(__name__)

__all__ = ["Checkpointer", "snapshot", "write_snapshot", "load_manifest",
           "load_sharded", "reassemble", "restore_into", "latest_sharded",
           "export_monolithic", "verify_checkpoint", "FORMAT", "VERSION"]

FORMAT = "mxtpu-sharded-checkpoint"
VERSION = 1
SUFFIX = ".ckpt"
MANIFEST = "manifest.json"

_STEP_RE = re.compile(r"-step(\d{8,})" + re.escape(SUFFIX) + r"$")


def checkpoint_dir(prefix, step):
    """Directory path of the sharded checkpoint for ``step``."""
    return "%s-step%08d%s" % (prefix, int(step), SUFFIX)


def _world():
    return max(1, int(get_env("MXTPU_NUM_PROCESSES", "1") or 1))


def _rank():
    return int(get_env("MXTPU_PROCESS_ID", "0") or 0)


# process-global save counter: the multi-process writer barrier id must be
# unique per use within one coordination-service lifetime, ACROSS
# Checkpointer instances (two elastic fits in one process both start
# their own writer); saves are collective, so the counter agrees
# world-wide as long as every rank saves the same sequence
_seq_lock = threading.Lock()
_save_seq = [0]


def _next_seq():
    with _seq_lock:
        _save_seq[0] += 1
        return _save_seq[0]


# ----------------------------------------------------------------- snapshot
def snapshot(ts, params, opt_state, aux, *, step=None, epoch=0, nbatch=0,
             extra=None):
    """Host-side snapshot of a training state: ONE batched device→host
    fetch of the pytrees plus the ownership topology and manifest fields.
    The returned job dict is what the (possibly asynchronous) writer
    consumes — it holds host numpy only, never device buffers (the live
    arrays are donated into the next step; a reference set would read
    deleted buffers by the time an async writer serialises it)."""
    import jax
    topo = ts.checkpoint_topology()
    if step is None:
        step = ts.num_update
    host_params, host_state, host_aux = jax.device_get(
        (params, opt_state if opt_state is not None else {}, aux))
    stage_of = topo["stage_of"]
    # topo["zero"] is the ZeRO LEVEL (int; historical bools read as 0/1):
    # level >= 1 shards optimizer state into dp parts, level 3
    # additionally stores the parameters themselves as flat rows
    # ("argz:" entries) — their logical shapes ride topo["param_shapes"]
    zlevel = int(topo["zero"])
    dp = int(topo["dp"])
    pshapes = topo.get("param_shapes") or {}
    groups = {}

    def grp(name):
        return groups.setdefault(name, {})

    for n, v in host_params.items():
        v = _np.asarray(v)
        if zlevel >= 3:
            # row j belongs to dp index j, like the optimizer-state rows
            for j in range(v.shape[0]):
                grp("stage%d-zero%d" % (stage_of[n], j))[
                    "argz:%s" % n] = v[j]
        else:
            grp("stage%d" % stage_of[n])["arg:%s" % n] = v
    for n, v in host_aux.items():
        grp("stage%d" % stage_of[n])["aux:%s" % n] = _np.asarray(v)
    has_opt = opt_state is not None
    if has_opt:
        for n, st in host_state.items():
            s = stage_of[n]
            for i, leaf in enumerate(st):
                leaf = _np.asarray(leaf)
                if zlevel:
                    # part j of the leaf belongs to dp index j: row j of
                    # a flat (dp, chunk) view, or the j-th part of the
                    # leading axis of a leaf kept in its shape (level 1,
                    # dp divides it) — as one flat row the same bytes
                    for j, row in enumerate(leaf.reshape(dp, -1)):
                        grp("stage%d-zero%d" % (s, j))[
                            "opt:%s:%d" % (n, i)] = row
                else:
                    grp("stage%d-opt" % s)["opt:%s:%d" % (n, i)] = leaf
    manifest = {
        "format": FORMAT,
        "version": VERSION,
        "step": int(step),
        "epoch": int(epoch),
        "nbatch": int(nbatch),
        "topology": {"pp": int(topo["pp"]), "dp": int(topo["dp"]),
                     "zero": zlevel,
                     "microbatches": topo["microbatches"],
                     "world": _world()},
        "stage_of": {n: int(s) for n, s in stage_of.items()},
        # manifest shapes are LOGICAL — for level-3 flat rows they come
        # from the step's plan, and load_sharded unpads against them
        "params": {n: {"shape": list(pshapes[n]) if zlevel >= 3
                       else list(_np.asarray(v).shape),
                       "dtype": str(_np.asarray(v).dtype)}
                   for n, v in host_params.items()},
        "aux": {n: {"shape": list(_np.asarray(v).shape),
                    "dtype": str(_np.asarray(v).dtype)}
                for n, v in host_aux.items()},
        "opt_state": {n: len(st) for n, st in host_state.items()}
        if has_opt else None,
        "extra": dict(extra or {}),
    }
    scale = ts.scale_state_host()
    if scale is not None:
        manifest["extra"]["loss_scale"] = scale
    return {"manifest": manifest, "groups": groups,
            "world": _world(), "rank": _rank()}


# ------------------------------------------------------------------- writer
def _fsync_dir(path):
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def write_snapshot(dirname, job):
    """Write a snapshot job as a sharded checkpoint directory — the
    synchronous core both the async writer thread and ``async_=False``
    saves run.  Per-shard write-to-temp + fsync + atomic rename; the
    manifest (with the full checksum table) lands LAST, so a kill at any
    point leaves either the complete checkpoint or one that
    :func:`latest_sharded` cannot see.  Returns total payload bytes."""
    from . import ndarray as nd
    wall0 = time.time()
    t0 = time.perf_counter()
    os.makedirs(dirname, exist_ok=True)
    world, rank = job["world"], job["rank"]
    stale = os.path.join(dirname, MANIFEST)
    if os.path.exists(stale):
        # re-writing an existing checkpoint dir (a resumed run whose
        # update counter restarted can reuse a step number): drop the
        # stale manifest BEFORE any shard rename, so a kill mid-rewrite
        # leaves an invisible dir — never old-manifest-over-new-shards,
        # which would pass latest_sharded's size check and fail crc at
        # restore time
        try:
            os.remove(stale)
        except OSError:
            pass
        _fsync_dir(dirname)
    manifest = dict(job["manifest"])
    shards = {}
    total = 0
    for i, g in enumerate(sorted(job["groups"])):
        owner = i % world
        fname = "%s.params" % g
        if owner != rank and rank != 0:
            # only the owner writes the shard, and only rank 0 needs the
            # full checksum table (it writes the manifest) — every other
            # rank skips serialising its peers' groups entirely
            continue
        blob = nd.serialize_arrays(job["groups"][g])
        shards[fname] = {"group": g, "rank": owner,
                         "crc32": zlib.crc32(blob) & 0xFFFFFFFF,
                         "bytes": len(blob)}
        total += len(blob)
        if owner == rank:
            with atomic_write(os.path.join(dirname, fname)) as f:
                f.write(blob)
    manifest["shards"] = shards
    if world > 1:
        # every rank's shards must be durable before the manifest makes
        # the checkpoint visible.  The writer threads of all ranks meet at
        # a coordination-SERVICE barrier (coordination_barrier): a device
        # collective here would race the training collectives in flight on
        # the main thread.  Checkpoint saves are collective: every rank
        # must save the same sequence of steps.
        from .parallel import dist
        # bounded: a peer that died mid-epoch surfaces as a loud writer
        # error on the next save()/wait() (and the launch supervisor is
        # already tearing the world down), not an indefinite hang.
        # COLL002 contract: the id carries BOTH the step and the
        # process-global save sequence — a resumed run whose update
        # counter restarted can reuse a step number, and barrier ids are
        # single-use within a coordination-service lifetime.
        dist.coordination_barrier(
            "ckpt-%d-%d" % (manifest["step"], job.get("_seq", 0)),
            timeout_ms=300000)
    if rank == 0:
        with atomic_write(os.path.join(dirname, MANIFEST)) as f:
            f.write(json.dumps(manifest, sort_keys=True,
                               indent=1).encode("utf-8"))
    _fsync_dir(dirname)
    # the checkpoint DIRECTORY's creation is an entry in its parent —
    # fsync that too or a power cut can drop the whole .ckpt dir
    _fsync_dir(os.path.dirname(os.path.abspath(dirname)))
    if _tel._enabled:
        _tel.record_span("ckpt.write", wall0, time.perf_counter() - t0,
                         cat="checkpoint", step=manifest["step"])
        _tel.gauge("ckpt_bytes", total)
        _tel.counter("ckpt_saves")
    return total


class Checkpointer(object):
    """Sharded checkpoint writer with an optional async daemon thread.

    ``async_=None`` (default) consults ``MXNET_CKPT_ASYNC`` (on unless
    ``0``).  The writer thread is created lazily on the first async
    ``save()`` — constructing a Checkpointer (or merely importing this
    module) starts nothing (import-hygiene contract, test_import_noop).
    The queue is bounded (depth 2): if serialisation cannot keep up,
    ``save()`` applies backpressure instead of accumulating unbounded
    host snapshots.  A writer exception is re-raised by the next
    ``save()``/``wait()`` — never swallowed, and never able to damage the
    previously completed checkpoint (each checkpoint is its own
    directory, made visible only by its manifest)."""

    def __init__(self, prefix, async_=None, queue_depth=2):
        if async_ is None:
            async_ = get_env("MXNET_CKPT_ASYNC", "1") != "0"
        self._prefix = prefix
        self._async = bool(async_)
        self._depth = int(queue_depth)
        self._lock = threading.Lock()
        self._queue = None
        self._thread = None
        self._error = None
        self._stop = object()

    # -- error forwarding
    def _raise_pending(self):
        with self._lock:
            err, self._error = self._error, None
        if err is not None:
            raise MXNetError(
                "checkpoint writer failed (the PREVIOUS completed "
                "checkpoint is intact; this one was discarded): %s: %s"
                % (type(err).__name__, err)) from err

    # -- thread plumbing
    def _ensure_thread(self):
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            import queue as _queue
            self._queue = _queue.Queue(maxsize=self._depth)
            self._thread = threading.Thread(
                target=self._drain, name="mxtpu-ckpt-writer", daemon=True)
            self._thread.start()

    def _drain(self):
        q = self._queue
        while True:
            job = q.get()
            try:
                if job is self._stop:
                    return
                write_snapshot(job["_dir"], job)
            except BaseException as exc:   # forwarded to the training loop
                with self._lock:
                    self._error = exc
            finally:
                q.task_done()
                if _tel._enabled:
                    _tel.gauge("ckpt_pending", q.qsize())

    # -- public API
    def save(self, ts, params, opt_state, aux, *, step=None, epoch=0,
             nbatch=0, extra=None):
        """Checkpoint one training state.  Synchronous part: the host
        snapshot (``ckpt.save`` span).  Asynchronous part: serialisation
        + fsync + rename on the writer thread.  Returns the checkpoint
        directory path (complete only after :meth:`wait` in async
        mode)."""
        self._raise_pending()
        wall0 = time.time()
        t0 = time.perf_counter()
        job = snapshot(ts, params, opt_state, aux, step=step, epoch=epoch,
                       nbatch=nbatch, extra=extra)
        path = checkpoint_dir(self._prefix, job["manifest"]["step"])
        job["_dir"] = path
        # unique multi-process barrier id per save (same-step re-saves —
        # and a second Checkpointer in the same process — must not
        # collide at the coordination service)
        job["_seq"] = _next_seq()
        if _tel._enabled:
            _tel.record_span("ckpt.save", wall0,
                             time.perf_counter() - t0, cat="checkpoint",
                             step=job["manifest"]["step"],
                             mode="async" if self._async else "sync")
        if not self._async:
            write_snapshot(path, job)
            return path
        self._ensure_thread()
        self._queue.put(job)
        if _tel._enabled:
            _tel.gauge("ckpt_pending", self._queue.qsize())
        return path

    def wait(self):
        """Durability barrier: block until every queued checkpoint is on
        disk (``ckpt.wait`` span), then surface any writer failure."""
        q = self._queue
        if q is not None:
            if _tel._enabled:
                wall0 = time.time()
                t0 = time.perf_counter()
                q.join()
                _tel.record_span("ckpt.wait", wall0,
                                 time.perf_counter() - t0, cat="checkpoint")
            else:
                q.join()
        self._raise_pending()

    def close(self):
        """Flush pending saves and stop the writer thread."""
        with self._lock:
            thread, q = self._thread, self._queue
            self._thread = None
        if thread is not None and thread.is_alive():
            q.put(self._stop)
            thread.join()
        self._raise_pending()


# -------------------------------------------------------------------- load
def load_manifest(path):
    """Read + validate a checkpoint directory's manifest.  A version (or
    format) mismatch names both sides so the operator knows which runtime
    wrote the file and what this one can read."""
    mpath = os.path.join(path, MANIFEST)
    if not os.path.isfile(mpath):
        raise MXNetError(
            "not a complete sharded checkpoint (no %s): %s — an "
            "interrupted save leaves shards without a manifest and is "
            "invisible to latest_sharded()" % (MANIFEST, path))
    with open(mpath) as f:
        man = json.load(f)
    if man.get("format") != FORMAT:
        raise MXNetError("not an mxtpu sharded checkpoint: %s (format=%r)"
                         % (path, man.get("format")))
    if int(man.get("version", -1)) != VERSION:
        raise MXNetError(
            "checkpoint format version mismatch: %s was written as "
            "version %s, this runtime reads version %d — re-save with a "
            "matching runtime or convert with tools/ckpt.py"
            % (path, man.get("version"), VERSION))
    return man


def _iter_shards(path, man, verify=True, parse=True):
    """Yield (meta, entries) per shard, checking presence + checksums.
    One disk read per shard: the checksum and the parse share the same
    in-memory bytes.  ``parse=False`` (verify-only callers) skips
    deserialisation and yields ``entries=None``."""
    from . import ndarray as nd
    for fname in sorted(man["shards"]):
        meta = man["shards"][fname]
        full = os.path.join(path, fname)
        if not os.path.isfile(full):
            raise MXNetError(
                "checkpoint %s is missing shard %s (group %s, written by "
                "rank %d) — partial copy or a lost rank filesystem"
                % (path, fname, meta["group"], meta["rank"]))
        with open(full, "rb") as f:
            blob = f.read()
        if verify:
            crc = zlib.crc32(blob) & 0xFFFFFFFF
            if crc != meta["crc32"] or len(blob) != meta["bytes"]:
                raise MXNetError(
                    "checkpoint %s shard %s (group %s, rank %d) is "
                    "corrupt: crc32 %08x / %d bytes on disk vs %08x / %d "
                    "in the manifest" % (path, fname, meta["group"],
                                         meta["rank"], crc, len(blob),
                                         meta["crc32"], meta["bytes"]))
        yield meta, nd.deserialize_arrays(blob) if parse else None


_ZERO_RE = re.compile(r"^stage(\d+)-zero(\d+)$")


def _reassemble(man, group_entries, where):
    """Merge per-ownership-group entry dicts into LOGICAL host pytrees
    ``(params, opt_state, aux)`` — ZeRO ``(dp, chunk)`` rows concatenated,
    un-padded against the manifest's logical shapes and reshaped, stage
    groups merged.  ``group_entries`` yields ``(group_name, entries)``
    pairs; the two producers are :func:`_iter_shards` (checkpoint files,
    via :func:`load_sharded`) and a live :func:`snapshot` job's
    ``groups`` dict (:func:`reassemble` — the no-disk live-resize path),
    so both routes share ONE copy of the layout math by construction.
    ``where`` names the source in errors."""
    params, aux = {}, {}
    flat_leaves = {}                    # (name, i) -> leaf | {row: chunk}
    zparams = {}                        # name -> {row: chunk} (ZeRO-3)
    for group, entries in group_entries:
        m = _ZERO_RE.match(group)
        zrow = int(m.group(2)) if m else None
        for ename, arr in entries.items():
            kind, rest = ename.split(":", 1)
            if kind == "arg":
                params[rest] = arr
            elif kind == "argz":
                # ZeRO-3 flat parameter rows (row j = dp index j)
                zparams.setdefault(rest, {})[zrow] = arr
            elif kind == "aux":
                aux[rest] = arr
            elif kind == "opt":
                n, i = rest.rsplit(":", 1)
                key = (n, int(i))
                if zrow is None:
                    flat_leaves[key] = arr
                else:
                    flat_leaves.setdefault(key, {})[zrow] = arr
    for n, rows in zparams.items():
        if sorted(rows) != list(range(len(rows))):
            raise MXNetError(
                "checkpoint %s: ZeRO-3 parameter rows of %s are not "
                "contiguous (%s)" % (where, n, sorted(rows)))
        shape = tuple(man["params"][n]["shape"])
        size = 1
        for d in shape:
            size *= d
        flat = _np.concatenate([rows[j].reshape(-1)
                                for j in sorted(rows)])
        params[n] = flat[:size].reshape(shape)
    if man["opt_state"] is None:
        return params, None, aux
    opt_state = {}
    for n, count in man["opt_state"].items():
        leaves = []
        shape = tuple(man["params"][n]["shape"])
        size = 1
        for d in shape:
            size *= d
        for i in range(count):
            leaf = flat_leaves.get((n, i))
            if leaf is None:
                raise MXNetError(
                    "checkpoint %s: optimizer-state leaf %d of %s is "
                    "absent from every shard" % (where, i, n))
            if isinstance(leaf, dict):
                rows = [leaf[j] for j in sorted(leaf)]
                if sorted(leaf) != list(range(len(rows))):
                    raise MXNetError(
                        "checkpoint %s: ZeRO rows of %s[%d] are not "
                        "contiguous (%s)" % (where, n, i, sorted(leaf)))
                flat = _np.concatenate([r.reshape(-1) for r in rows])
                leaf = flat[:size].reshape(shape)
            leaves.append(leaf)
        opt_state[n] = tuple(leaves)
    return params, opt_state, aux


def load_sharded(path, verify=True):
    """Load a sharded checkpoint into LOGICAL host pytrees:
    ``(manifest, params, opt_state, aux)`` with every tensor reassembled
    to its logical (unsharded, unpadded) shape — ZeRO ``(dp, chunk)``
    rows concatenated and reshaped, stage files merged.  This is the
    topology-free half of any-topology restore; placement back onto a
    (possibly different) mesh is ``place_checkpoint`` on the restoring
    step (:func:`restore_into` does both)."""
    man = load_manifest(path)
    pairs = ((meta["group"], entries)
             for meta, entries in _iter_shards(path, man, verify=verify))
    params, opt_state, aux = _reassemble(man, pairs, path)
    return man, params, opt_state, aux


def reassemble(job):
    """LOGICAL host pytrees from an in-memory :func:`snapshot` job — a
    save + :func:`load_sharded` round trip without the disk in between.
    ``snapshot`` → ``reassemble`` → :func:`restore_loaded` re-shards a
    LIVE training state onto a new topology (the live-resize path,
    parallel/resize.py): the job's ``groups`` dict is byte-for-byte what
    the shard writer would serialise, reassembled here through the SAME
    group math the file loader uses, so the re-shard is bitwise equal to
    the checkpoint-restore path by construction.  Returns ``(manifest,
    params, opt_state, aux)``."""
    man = job["manifest"]
    params, opt_state, aux = _reassemble(man, sorted(job["groups"].items()),
                                         "<live snapshot>")
    return man, params, opt_state, aux


def restore_loaded(ts, man, params, opt_state, aux, device=None,
                   where="<loaded checkpoint>"):
    """Place already-loaded LOGICAL host pytrees onto ``ts``'s CURRENT
    topology and resume its update count + loss-scale automaton — the
    placement half of :func:`restore_into`, callable with the result of
    one :func:`load_sharded` (the elastic resume loads once and restores
    through here instead of re-reading every shard)."""
    missing = [n for n in ts.param_names if n not in params]
    if missing:
        raise MXNetError(
            "checkpoint %s does not cover parameter(s) %s of this model"
            % (where, ", ".join(sorted(missing))))
    missing_aux = [n for n in ts.aux_names if n not in aux]
    if missing_aux:
        raise MXNetError(
            "checkpoint %s does not cover aux state %s of this model "
            "(was it saved by a model without these layers?)"
            % (where, ", ".join(sorted(missing_aux))))
    if opt_state is None:
        opt_state = ts.fopt.init_state(
            {n: _np.asarray(params[n]) for n in ts.param_names})
    p, s, a = ts.place_checkpoint(params, opt_state, aux, device=device)
    ts.num_update = int(man["step"])
    ts.load_scale_state((man.get("extra") or {}).get("loss_scale"))
    return p, s, a, man


def restore_into(ts, path, verify=True, device=None):
    """Restore a sharded checkpoint onto ``ts``'s CURRENT topology —
    whatever topology saved it.  Returns ``(params, opt_state, aux,
    manifest)`` placed per the step's mesh/stage plan (``device`` pins a
    no-mesh TrainStep's placement); the step's update count and
    loss-scale automaton resume from the manifest.  Absent optimizer
    state (a params-only save) restores fresh state."""
    man, params, opt_state, aux = load_sharded(path, verify=verify)
    return restore_loaded(ts, man, params, opt_state, aux, device=device,
                          where=path)


# ------------------------------------------------------------------ listing
def latest_sharded(prefix):
    """Path of the newest COMPLETE sharded checkpoint for ``prefix``, or
    None.  Completeness = the manifest exists and parses (it is written
    last, atomically): a save interrupted at any earlier point never
    surfaces here.  "Newest" orders by the manifest's DATA POSITION
    ``(epoch, nbatch, step)``, not the filename's step number — a resumed
    run whose update counter restarted (a monolithic-epoch resume) writes
    lower step numbers than stale pre-crash checkpoints, and those must
    not shadow the real progress.  Unreadable / incomplete candidates are
    skipped with a warning (silent fallback to a much older checkpoint is
    undiagnosable)."""
    best = None
    for d in glob.glob("%s-step*%s" % (prefix, SUFFIX)):
        m = _STEP_RE.search(d)
        if m is None or not os.path.isdir(d):
            continue
        try:
            man = load_manifest(d)
        except (MXNetError, ValueError, OSError) as e:
            _LOG.warning("latest_sharded: skipping unreadable candidate "
                         "%s (%s)", d, e)
            continue
        # belt-and-braces beyond manifest-written-last: every shard the
        # manifest names must be present at its recorded size (a rank's
        # lost filesystem, a partial copy) — resume falls back to the
        # previous complete checkpoint instead of failing mid-restore
        complete = True
        for fname, meta in man.get("shards", {}).items():
            full = os.path.join(d, fname)
            if not os.path.isfile(full) \
                    or os.path.getsize(full) != meta["bytes"]:
                complete = False
                break
        if not complete:
            _LOG.warning("latest_sharded: skipping incomplete candidate "
                         "%s (missing/short shard)", d)
            continue
        pos = (int(man.get("epoch", 0)), int(man.get("nbatch", 0)),
               int(man["step"]))
        if best is None or pos > best[0]:
            best = (pos, d)
    return best[1] if best else None


def verify_checkpoint(path):
    """Walk every shard of a checkpoint, checking presence, sizes and
    checksums; returns the manifest.  (tools/ckpt.py --verify.)"""
    man = load_manifest(path)
    for _meta, _entries in _iter_shards(path, man, verify=True,
                                        parse=False):
        pass
    return man


def export_monolithic(path, fname):
    """Reassemble a sharded checkpoint into one legacy monolithic
    ``.params`` file (``arg:``/``aux:`` entries — loadable by
    ``model.load_checkpoint`` / ``Module.load_params``): the
    sharded→monolithic corner of the restore matrix."""
    from . import ndarray as nd
    man, params, _opt, aux = load_sharded(path)
    # nd.save owns the scheme dispatch: local paths go temp+fsync+rename,
    # remote URIs (s3://…) stream through smart_open
    nd.save(fname,
            dict([("arg:%s" % n, v) for n, v in sorted(params.items())]
                 + [("aux:%s" % n, v) for n, v in sorted(aux.items())]))
    return man
