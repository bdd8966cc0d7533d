"""Multi-host distributed runtime (parity: ps-lite + dmlc tracker roles,
SURVEY.md §2.6; replaced by jax.distributed + XLA collectives over ICI/DCN).

Environment contract (replaces DMLC_ROLE/DMLC_PS_ROOT_URI):
- ``MXTPU_COORDINATOR``   address of process 0 (host:port)
- ``MXTPU_NUM_PROCESSES`` world size
- ``MXTPU_PROCESS_ID``    this process's rank
A single process with no env vars set runs standalone (rank 0 of 1) — the same
code path the reference's `local` tracker exercises.

Collective design (TPU-native replacement for KVStoreDist::Push/Pull,
reference src/kvstore/kvstore_dist.h:28-318): instead of copying gradients to
pinned host buffers and shipping them to parameter-server processes over ZMQ,
each worker contributes its already-on-device gradient as one shard of a
global jax.Array laid out along a ``worker`` mesh axis; a jitted ``sum`` over
that axis is compiled by XLA into an all-reduce that rides ICI (single slice)
or DCN (multi-slice).  No per-step host transfer, no server processes.  All
keys pushed in one step are reduced in ONE fused XLA computation
(``allreduce_tree``) — the analogue of the reference's per-key ZPush batching.

Worker-death detection (parity: KVStore::get_num_dead_node via ps heartbeats)
is delegated to the JAX coordination service: a missing host fails the
collective, and recovery is checkpoint-resume (SURVEY.md §5.3 notes the PS
hot-state model is intentionally replaced by checkpointing).
"""
from __future__ import annotations

import logging
import os
import threading

import numpy as _np

from ..base import MXNetError, get_env

_initialized = False


def _connect(coord, nproc, pid):
    """Bring up the coordination service/client for the (coord, nproc,
    pid) world, with bounded retry-with-backoff around the connect.

    A rank that boots a few seconds before the coordinator used to fail
    the whole world on one transient connect error; a live resize
    (parallel/resize.py) re-runs this path on every membership change,
    which makes the race hot.  ``MXNET_DIST_CONNECT_RETRIES`` attempts
    (default 3), sleeping ``MXNET_DIST_CONNECT_BACKOFF_SEC`` (default
    0.5, doubling) between them; the curated error names the attempt
    count and the last cause.  A double-initialize programming error is
    never retried — backoff cannot fix it.

    Two entry modes, picked by backend state:

    - backend NOT yet created: the standard ``jax.distributed.initialize``
      — the device plane spans the world (multi-process ``jax.devices()``,
      gloo collectives on CPU);
    - backend ALREADY created (a live resize re-init, or a
      coordination-only world that touched devices first):
      ``jax.distributed.initialize`` refuses to run, so the coordination
      service/client is brought up directly through jax's internal
      ``global_state.initialize`` — the backend stays single-process
      while barriers/KV/membership ride the service.  This is the ONE
      sanctioned use of that internal (same ownership rule as
      ``coordination_client``)."""
    import jax
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        # The env var alone can be ignored when an accelerator plugin is
        # installed; pin the platform programmatically (must precede any
        # backend-initialising call).  The CPU backend also needs an
        # explicit cross-process collectives implementation (TPU rides
        # ICI natively).
        try:
            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception:
            pass
    from jax._src import xla_bridge as _xb
    coordination_only = _xb.backends_are_initialized()
    attempts = max(1, get_env("MXNET_DIST_CONNECT_RETRIES", 3, typ=int))
    backoff = get_env("MXNET_DIST_CONNECT_BACKOFF_SEC", 0.5, typ=float)
    last = None
    for attempt in range(1, attempts + 1):
        try:
            if coordination_only:
                _coordination_connect(coord, nproc, pid)
            else:
                jax.distributed.initialize(coordinator_address=coord,
                                           num_processes=nproc,
                                           process_id=pid)
            return
        except Exception as e:   # noqa: BLE001 — classified below
            if "should only be called once" in str(e):
                raise           # double-init: a caller bug, not transient
            last = e
            if attempt < attempts:
                import time as _time
                _time.sleep(backoff * (2 ** (attempt - 1)))
    raise MXNetError(
        "init_process_group: cannot connect to the coordination service "
        "at %s after %d attempt(s) (world %d, rank %d): %s — transient "
        "startup races retry with backoff (MXNET_DIST_CONNECT_RETRIES / "
        "MXNET_DIST_CONNECT_BACKOFF_SEC); a persistent failure means the "
        "coordinator address is wrong or rank 0 died during startup"
        % (coord, attempts, nproc, pid, last))


def _nonfatal_peer_error(status):
    """Replacement for jax's default distributed-client error callback.

    The default (xla client.h) TERMINATES THE PROCESS when the
    coordination service reports a peer failure or a heartbeat lapses —
    exactly the signal a live resize (parallel/resize.py) handles in
    Python: the membership gate times out, the supervisor publishes a
    shrink plan, and the survivor transitions IN PLACE.  An abandoned
    generation's zombie client (see ``_zombies``) eventually polls the
    dead peer's heartbeat error too; letting it abort the survivor would
    turn every recoverable membership change into a fleet loss.  So:
    log, remember, never terminate."""
    global _peer_error
    _peer_error = str(status)
    logging.getLogger(__name__).warning(
        "coordination service reported a peer error (world membership "
        "change?): %s — continuing; the membership gate/elastic "
        "supervisor decides what happens next", status)


_peer_error = None


def _coordination_connect(coord, nproc, pid):
    """Coordination-ONLY world bring-up (backend already initialized):
    the service on rank 0 plus a client per rank, wired into jax's
    ``global_state`` so ``coordination_client()`` and jax's own users
    find them.  Mirrors ``jax._src.distributed.State.initialize`` minus
    backend coupling, with one deliberate difference: the client gets
    :func:`_nonfatal_peer_error` instead of the default
    terminate-the-process callback, and never shuts down on destruction
    (a zombie generation's destructor must not run a blocking handshake
    with a dead world)."""
    from jax._src import distributed as _jdist
    from jax._src.lib import _jax as _xe
    state = _jdist.global_state
    if state.client is not None:
        # same message class as jax.distributed.initialize — _connect
        # classifies double-init as a caller bug, never retried
        raise RuntimeError("jax.distributed.initialize should only be "
                           "called once")
    if pid == 0 and state.service is None:
        bind = "[::]:%s" % coord.rsplit(":", 1)[1]
        state.service = _xe.get_distributed_runtime_service(bind, nproc)
    client = _xe.get_distributed_runtime_client(
        coord, pid, missed_heartbeat_callback=_nonfatal_peer_error,
        shutdown_on_destruction=False)
    client.connect()
    state.client = client
    state.process_id = pid
    state.num_processes = nproc
    if hasattr(state, "coordinator_address"):
        state.coordinator_address = coord


def init_process_group():
    """Initialize jax.distributed from the MXTPU_* env contract (idempotent)."""
    global _initialized
    if _initialized:
        return
    coord = get_env("MXTPU_COORDINATOR")
    nproc = get_env("MXTPU_NUM_PROCESSES", typ=int)
    pid = get_env("MXTPU_PROCESS_ID", typ=int)
    if coord and nproc and nproc > 1:
        _connect(coord, nproc, pid or 0)
        # jax.distributed puts its preemption notifier on SIGTERM,
        # displacing the flight recorder's import-time hook — re-assert
        # it (chaining the notifier) so a killed rank still leaves its
        # ring in a bundle.  No-op unless MXNET_FLIGHT_RECORDER armed.
        try:
            from .. import diagnostics as _diag
            _diag.fr_rewire_sigterm()
        except Exception:
            pass
    _initialized = True
    from .. import telemetry as _tel
    if _tel._enabled:
        # one-shot world-identity gauges: the fleet merge and the metrics
        # endpoint can label this process without re-deriving the contract
        _tel.gauge("dist_world_size", nproc if (coord and nproc) else 1)
        _tel.gauge("dist_rank", pid or 0)


# coordination clients/services of torn-down worlds, kept referenced ON
# PURPOSE: their C++ destructors run the graceful shutdown handshake
# (blocking RPCs a world that lost a member can never complete), so
# dropping the last reference inside a resize would hang the survivor
# inside a destructor.  Bounded by the number of resizes in one process
# lifetime; each entry is two small RPC endpoints, not device state.
_zombies = []


def shutdown_process_group(graceful=False):
    """Tear down the distributed runtime so :func:`init_process_group`
    can bring up a NEW world (the live-resize transition).

    ``graceful=True`` runs jax's full shutdown handshake — every peer
    must still be alive to meet the shutdown barrier.  ``graceful=False``
    (the resize default) ABANDONS the old client/service without the
    handshake: the old world has lost a member by definition, and the
    handshake would block on the dead rank forever.  Abandoned endpoints
    are stashed in ``_zombies`` (see above) rather than dropped.

    Also resets this module's world-derived state — the worker mesh and
    the fused allreduce programs hold the OLD world's device topology —
    and re-arms the idempotence latch so the next collective re-reads
    the (rewritten) MXTPU env contract."""
    global _initialized, _worker_mesh
    state = None
    try:
        from jax._src import distributed as _jdist
        state = _jdist.global_state
    except Exception:            # internal layout moved
        pass
    if state is not None and (getattr(state, "client", None) is not None
                              or getattr(state, "service", None) is not None):
        if graceful:
            import jax
            jax.distributed.shutdown()
        else:
            _zombies.append((state.client, state.service,
                             getattr(state, "preemption_sync_manager",
                                     None)))
            state.client = None
            state.service = None
            if hasattr(state, "preemption_sync_manager"):
                state.preemption_sync_manager = None
            if hasattr(state, "coordinator_address"):
                state.coordinator_address = None
            if hasattr(state, "process_id"):
                state.process_id = 0
            if hasattr(state, "num_processes"):
                state.num_processes = None
    _initialized = False
    _worker_mesh = None
    _sum_cache.clear()
    # clock offsets and straggler verdicts are world-relative: the next
    # world re-estimates / re-exchanges from scratch
    _clock_reset()
    _sentinel_reset()


def rank():
    init_process_group()
    import jax
    return jax.process_index()


def num_workers():
    init_process_group()
    import jax
    return jax.process_count()


# default-barrier-id sequence: sync_global_devices tolerates a repeated
# name, but a *distinct* id per use keeps the COLL002 contract uniform
# across every barrier flavour (coordination-service ids are single-use)
# and makes a hung barrier's ledger entry unambiguous.  Process-local,
# but barriers are collective — every rank reaches the same call count,
# so the generated names agree world-wide (the health_check idiom).
_barrier_seq_lock = threading.Lock()
_barrier_seq = [0]


def barrier(name=None):
    """Global DEVICE barrier (psum over all global devices; parity: ps
    barrier).  ``name=None`` auto-derives a sequenced id so repeated
    calls (the kvstore epoch barrier) never reuse one.  Main-thread
    only by contract — see :func:`coordination_barrier` for the
    thread-safe service barrier."""
    init_process_group()
    import jax
    if jax.process_count() <= 1:
        return
    if name is None:
        with _barrier_seq_lock:
            _barrier_seq[0] += 1
            name = "kvstore-%d" % _barrier_seq[0]
    from jax.experimental import multihost_utils
    from .. import sanitize as _san
    _clock_exchange()
    _sentinel_exchange()
    with _san.collective_dispatch("barrier", name=name):
        # exchange BEFORE waiting: two ranks arriving with different
        # barrier names (or divergent dispatch histories) are named here
        # instead of deadlocking inside the mismatched collective
        _san.collective_sync("barrier:%s" % name)
        multihost_utils.sync_global_devices(name)


def coordination_client():
    """jax's coordination-service client, or None (single process, or a
    jax upgrade moved the internal layout).  The ONE owner of this
    fragile lookup — ``coordination_barrier`` and mxsan's hash-chain
    exchange both ride it, so a breakage surfaces in both at once
    instead of silently disabling one."""
    try:
        from jax._src import distributed as _jdist
        return getattr(_jdist.global_state, "client", None)
    except Exception:            # internal layout moved
        return None


def peer_world():
    """``(world, rank)`` of this process's coordination-service peer
    group.  The device backend's own world when it is multi-process;
    otherwise — the coordination-only coupling a live resize runs in,
    where the backend stays single-process but the service still couples
    the ranks — the MXTPU env contract, provided a coordination client is
    actually connected.  Standalone: ``(1, 0)``."""
    init_process_group()
    import jax
    if jax.process_count() > 1:
        return jax.process_count(), jax.process_index()
    if coordination_client() is not None:
        from .. import checkpoint as _ckpt
        return _ckpt._world(), _ckpt._rank()
    return 1, 0


def membership_barrier(name, timeout_ms=30000):
    """Bounded liveness/membership gate over the coordination service —
    a barrier EXPECTED to fail when the world changed.  True when every
    peer arrived within ``timeout_ms``; False on timeout or service
    error (a missing peer, a dead coordinator).  Standalone (no service):
    trivially True.

    Unlike :func:`coordination_barrier` this skips mxsan's hash-chain
    exchange: the exchange would block on the dead peer's payload and
    record a divergence violation before the probe could report — a
    probe whose JOB is to observe membership loss must not trip the
    checker that assumes membership is fixed.  The dispatch still lands
    in the collective ledger (``device=False``) so a post-mortem names
    the gate in flight.  Service barrier ids are single-use: callers
    suffix a generation/sequence (the ``health_check`` idiom)."""
    init_process_group()
    import jax
    client = coordination_client()
    if client is None:
        if jax.process_count() <= 1:
            return True
        # multi-process device world but no client lookup: probing via a
        # device collective could hang forever on the very peer loss the
        # probe exists to detect — fail loudly instead
        raise MXNetError(
            "membership_barrier: jax's coordination-service client is "
            "unavailable in this jax version — membership cannot be "
            "probed without a device collective (fix "
            "dist.coordination_client)")
    from .. import sanitize as _san
    with _san.collective_dispatch("membership_barrier", name=name,
                                  device=False):
        try:
            client.wait_at_barrier(name, timeout_ms)
            return True
        except Exception:
            return False


def kv_set(key, value):
    """Publish ``value`` (str) under ``key`` on the coordination service
    (single writer per key within one service lifetime — the live-resize
    state hand-off publishes under a generation-suffixed key)."""
    init_process_group()
    client = coordination_client()
    if client is None:
        raise MXNetError(
            "kv_set: no coordination-service client (single-process "
            "world, or a jax upgrade moved the internal lookup)")
    client.key_value_set(key, value)


def kv_get(key, timeout_ms=600000):
    """Blocking read of ``key`` from the coordination service (bounded;
    raises on timeout).  The receive side of :func:`kv_set`."""
    init_process_group()
    client = coordination_client()
    if client is None:
        raise MXNetError(
            "kv_get: no coordination-service client (single-process "
            "world, or a jax upgrade moved the internal lookup)")
    return client.blocking_key_value_get(key, timeout_ms)


def coordination_barrier(name, timeout_ms=600000):
    """Process barrier over the coordination SERVICE (key-value RPC, no
    device collectives).  ``barrier``/``sync_global_devices`` launches a
    psum over all global devices, so calling it off the main thread can
    interleave with in-flight training collectives and deadlock the world
    — this variant is safe from any thread (the async checkpoint writer
    meets its peers here).  ``name`` must be unique per use within one
    coordination-service lifetime."""
    init_process_group()
    import jax
    client = coordination_client()
    if jax.process_count() <= 1 and client is None:
        # truly standalone.  A single-process BACKEND with a live client
        # is the coordination-only world a live resize runs in — those
        # ranks still meet each other here, through the service.
        return
    from .. import sanitize as _san
    _clock_exchange()
    _sentinel_exchange()
    # device=False: the service barrier is thread-safe by design — the
    # checkpoint writer thread meeting its peers here is the sanctioned
    # pattern, not an off-main-thread violation
    with _san.collective_dispatch("coordination_barrier", name=name,
                                  device=False):
        _san.collective_sync("coordination_barrier:%s" % name)
        if client is not None:
            client.wait_at_barrier(name, timeout_ms)
            return
        if threading.current_thread() is not threading.main_thread():
            # falling back to sync_global_devices would launch a device
            # collective from a side thread, interleaving with in-flight
            # training collectives — the exact deadlock this function
            # exists to avoid.  Fail loudly instead (a jax upgrade moved
            # the coordination client; fix the lookup above).
            raise MXNetError(
                "coordination_barrier: jax's coordination-service client "
                "is unavailable in this jax version and the device-"
                "collective fallback is unsafe off the main thread")
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)


# --------------------------------------------------------------------------
# Cross-rank clock exchange (the fleet-timeline substrate)
# --------------------------------------------------------------------------
# Per-rank telemetry streams timestamp with the LOCAL wall clock; merging
# them into one fleet timeline (tools/trace_merge.py) needs each rank's
# offset against a reference.  At every barrier entry — a point all ranks
# reach together, so the true arrival spread bounds the error — each rank
# publishes ``(monotonic, wall)`` under a seq-numbered key on the
# coordination service (key-value RPC ONLY: no device collective, so the
# COLL rules and the mxsan ledger stay silent) and estimates its offset
# against rank 0 as the running median of the wall-clock deltas.  The
# estimate rides the event stream as the ``clock_offset_sec`` gauge, so a
# telemetry JSONL or a flight-recorder bundle is self-describing for the
# merge.  Gated on ``_tel._enabled`` (full telemetry OR an armed flight
# recorder): with both off, nothing is published and no state accrues —
# the zero-overhead contract, pinned in test_import_noop.  Main-thread
# only, like mxsan's hash-chain exchange: the seq numbering must advance
# identically on every rank.
_clock_lock = threading.Lock()
_clock_seq = 0
_clock_samples = []       # wall-delta samples vs rank 0 (bounded)
_clock_offset = None      # current median estimate (seconds)
_CLOCK_SAMPLES_KEEP = 64
_CLOCK_TIMEOUT_MS = 5000


def clock_offset():
    """Latest estimated wall-clock offset of this rank against rank 0
    (seconds; positive = this rank's clock runs ahead), or None before
    the first exchange.  Rank 0 reports 0.0."""
    return _clock_offset


def _clock_reset():
    global _clock_seq, _clock_samples, _clock_offset
    with _clock_lock:
        _clock_seq = 0
        _clock_samples = []
        _clock_offset = None


def _clock_exchange():
    """One clock sample exchange at a barrier entry (see above).  Must
    never fail or stall the barrier: every service error degrades to a
    lost sample."""
    global _clock_seq, _clock_offset
    from .. import telemetry as _tel
    if not _tel._enabled:
        return
    if threading.current_thread() is not threading.main_thread():
        # seq numbering must advance in the same order on every rank;
        # side-thread barriers (the async checkpoint writer) interleave
        # nondeterministically — same rule as mxsan's exchange
        return
    client = coordination_client()
    if client is None:
        return
    try:
        world, myrank = peer_world()
    except Exception:
        return
    if world <= 1:
        return
    import time as _time
    with _clock_lock:
        _clock_seq += 1
        n = _clock_seq
    mono = _time.monotonic()
    wall = _time.time()
    try:
        client.key_value_set("mxtpu-clock/%d/%d" % (n, myrank),
                             "%.9f,%.9f" % (mono, wall))
        if n > 2:
            # reclaim this rank's round-(n-2) key (the mxsan-coll delete
            # argument: anyone who published n-1 has finished reading
            # n-2, and barriers order the rounds)
            try:
                client.key_value_delete("mxtpu-clock/%d/%d"
                                        % (n - 2, myrank))
            except Exception:
                pass
        if myrank == 0:
            offset = 0.0
        else:
            raw = client.blocking_key_value_get("mxtpu-clock/%d/0" % n,
                                                _CLOCK_TIMEOUT_MS)
            _mono0, wall0 = (float(x) for x in str(raw).split(","))
            offset = wall - wall0
    except Exception:
        return   # a lost sample must never fail the barrier
    with _clock_lock:
        _clock_samples.append(offset)
        if len(_clock_samples) > _CLOCK_SAMPLES_KEEP:
            del _clock_samples[0]
        s = sorted(_clock_samples)
        m = len(s) // 2
        _clock_offset = s[m] if len(s) % 2 else 0.5 * (s[m - 1] + s[m])
        est, nsamp = _clock_offset, len(s)
    _tel.gauge("clock_offset_sec", est, rank=myrank, samples=nsamp)


def wire_bytes():
    """Cumulative collective payload bytes by ``"kind/axes"`` — folded
    out of each dispatch's shape/dtype signature (metadata only, no
    device syncs) while mxsan's collective checker OR telemetry records.
    The same totals ride ``/metrics`` as ``coll_wire_bytes[kind/axes]``
    counters; ROADMAP item 5's wire-efficiency work gates against the
    ``dryrun_multichip`` wire ladder built on this accounting."""
    from .. import sanitize as _san
    return _san.wire_bytes()


# --------------------------------------------------------------------------
# Cross-rank sentinel digest exchange (live straggler naming)
# --------------------------------------------------------------------------
# The clock exchange's perf twin: at every barrier entry each rank
# publishes its sentinel step-summary digest (per-phase EWMA means — a
# few hundred bytes of JSON) under a seq-numbered key on the
# coordination service and reads every peer's, so ALL ranks can answer
# "who is slow, and in which phase" mid-run — not just rank 0.
# Key-value RPC only: the collective ledger and hash chain stay quiet,
# exactly like the clock exchange above.  Gated on the sentinel being
# armed AND detecting (MXNET_SENTINEL=step:<k>sigma...); unset, nothing
# is published and no state accrues (import-noop pinned).  Main-thread
# only for the same seq-agreement reason as the clock.
_sent_lock = threading.Lock()
_sent_seq = 0
_straggler = None         # latest (rank, phase, slowdown) verdict
_SENT_TIMEOUT_MS = 5000


def straggler():
    """Latest cross-rank straggler verdict ``(rank, phase, slowdown)``
    — the slowest rank's id, its dominant divergent phase (data_wait /
    compute / stall) and its mean-step-time ratio over the median of the
    other ranks — or None before the first digest exchange (or with the
    sentinel disarmed).  Every rank holds the same verdict, refreshed at
    each barrier/epoch exchange point."""
    return _straggler


def _sentinel_reset():
    global _sent_seq, _straggler
    with _sent_lock:
        _sent_seq = 0
        _straggler = None


def _sentinel_exchange():
    """One digest exchange at a barrier entry (see above).  Must never
    fail or stall the barrier: every service error degrades to a lost
    round."""
    global _sent_seq, _straggler
    from .. import sentinel as _sen
    if not _sen._on or not _sen._detect:
        return
    if threading.current_thread() is not threading.main_thread():
        return
    client = coordination_client()
    if client is None:
        return
    try:
        world, myrank = peer_world()
    except Exception:
        return
    if world <= 1:
        return
    mine = _sen.digest()
    if mine is None:
        return   # no baseline yet (pre-first-step barrier)
    import json as _json
    with _sent_lock:
        _sent_seq += 1
        n = _sent_seq
    try:
        client.key_value_set("mxtpu-sent/%d/%d" % (n, myrank),
                             _json.dumps(mine))
        if n > 2:
            try:
                client.key_value_delete("mxtpu-sent/%d/%d"
                                        % (n - 2, myrank))
            except Exception:
                pass
        digests = {myrank: mine}
        for r in range(world):
            if r == myrank:
                continue
            raw = client.blocking_key_value_get(
                "mxtpu-sent/%d/%d" % (n, r), _SENT_TIMEOUT_MS)
            digests[r] = _json.loads(str(raw))
    except Exception:
        return   # a lost round must never fail the barrier
    verdict = _sen.name_straggler(digests)
    if verdict is None:
        return
    with _sent_lock:
        _straggler = verdict
    from .. import telemetry as _tel
    if _tel._enabled:
        srank, phase, slowdown = verdict
        _tel.gauge("straggler_rank", srank, phase=phase)
        _tel.gauge("straggler_slowdown", round(slowdown, 4))


# --------------------------------------------------------------------------
# On-device cross-process allreduce
# --------------------------------------------------------------------------
_worker_mesh = None
_sum_cache = {}


def worker_mesh():
    """1-D mesh with one leader device per process (axis name ``worker``).

    The global array built over this mesh has one shard per worker; summing
    its leading axis is the cross-worker gradient reduction, and XLA lowers
    it to an all-reduce collective between the leader devices.
    """
    global _worker_mesh
    if _worker_mesh is None:
        import jax
        from jax.sharding import Mesh
        leaders = {}
        for d in jax.devices():
            leaders.setdefault(d.process_index, d)
        devs = [leaders[p] for p in sorted(leaders)]
        _worker_mesh = Mesh(_np.asarray(devs), ("worker",))
    return _worker_mesh


def _sum_fn(nshapes_key):
    """Jitted per-pytree sum over the worker axis, replicated output."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    fn = _sum_cache.get(nshapes_key)
    if fn is None:
        mesh = worker_mesh()
        rep = NamedSharding(mesh, PartitionSpec())

        def reduce_all(stacked):
            return [x.sum(axis=0) for x in stacked]

        fn = jax.jit(reduce_all, out_shardings=rep)
        _sum_cache[nshapes_key] = fn
    return fn


def _to_global(x):
    """Wrap this process's array as its shard of a (W, *shape) global array."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = worker_mesh()
    my_leader = jax.local_devices()[0]
    local = jax.device_put(_np.asarray(x)[None]
                           if isinstance(x, _np.ndarray) else x[None],
                           my_leader)
    W = mesh.devices.size
    spec = PartitionSpec("worker", *([None] * (local.ndim - 1)))
    return jax.make_array_from_single_device_arrays(
        (W,) + tuple(local.shape[1:]), NamedSharding(mesh, spec), [local])


def allreduce_arrays(arrays):
    """Sum a list of jax arrays across worker processes in ONE fused XLA
    computation (the dist kvstore's merge; no host round-trip)."""
    init_process_group()
    import jax
    if jax.process_count() <= 1:
        return list(arrays)
    def reduce():
        stacked = [_to_global(a) for a in arrays]
        key = tuple((tuple(a.shape), str(a.dtype)) for a in stacked)
        return _sum_fn(key)(stacked)

    from .. import diagnostics as _diag
    if _diag._armed:
        # beat BEFORE entering the collective: a worker hanging inside it
        # stops beating, so the watchdog dump's stacks show the allreduce
        _diag.heartbeat(comm="dist.allreduce", narrays=len(arrays))
    from .. import sanitize as _san
    from .. import telemetry as _tel
    # ledger entry from shape metadata only (the mxsan no-sync
    # discipline); the in-flight mark feeds the MXNET_SAN_COLL_TIMEOUT
    # deadlock watchdog while the collective blocks
    sig = None
    if _san._collective_on or _tel._enabled:
        sig = _san.collective_sig(arrays)
        # wire-bytes ledger: payload bytes from the sig metadata (no
        # device sync), per (kind, axes) — dist.wire_bytes() / /metrics
        _san.record_wire_bytes("dist.allreduce", sig, axes="worker")
    with _san.collective_dispatch("dist.allreduce", sig=sig,
                                  axes="worker"):
        if _tel._enabled:
            # the rank tag lets a merged event stream (not just per-rank
            # files) attribute collective latency to its worker
            with _tel.span("dist.allreduce", cat="comm",
                           narrays=len(arrays), rank=jax.process_index()):
                outs = reduce()
                _tel.counter("dist_allreduce")
                _tel.counter("dist_allreduce_bytes",
                             sum(_tel.nbytes_of(a) for a in arrays))
                jax.block_until_ready(outs)  # span reads collective time
        else:
            outs = reduce()
    # outputs are replicated over the worker mesh; hand back this process's
    # shard so results compose with process-local arrays (stays on device)
    return [o.addressable_shards[0].data for o in outs]


def allreduce(value):
    """Sum one NDArray across worker processes (XLA all-reduce over the
    worker mesh; parity: the dist kvstore server-side merge)."""
    import jax
    init_process_group()
    if jax.process_count() <= 1:
        return value
    from .. import ndarray as nd
    out = allreduce_arrays([value.value])[0]
    return nd.NDArray(out, ctx=value.context)


def allreduce_tree(values):
    """Sum a dict {key: NDArray} across workers in one fused computation."""
    import jax
    init_process_group()
    if jax.process_count() <= 1:
        return dict(values)
    from .. import ndarray as nd
    keys = sorted(values)
    outs = allreduce_arrays([values[k].value for k in keys])
    return {k: nd.NDArray(o, ctx=values[k].context)
            for k, o in zip(keys, outs)}
