"""mxsan — opt-in runtime sanitizer for the invariants mxlint can only
check statically.

The repo's perf story rests on two runtime contracts: every jit cache
stays *warm* in steady state (a recompile is seconds of silent stall —
the PR-7 fused-fit cache keyed on ``num_update`` and recompiled on every
``fit()`` after the first), and the hot path never syncs to host unless
an observability lever asked for it.  mxlint's JIT001/SYNC001 police the
source; this module polices the *running process* — the dynamic twin,
the way ``test_import_noop.py`` is NOOP001's dynamic twin.

Arm with ``MXNET_SAN=recompile,sync,donate`` (any subset; append
``:raise`` to fail fast instead of warning).  With the variable unset
this module is a strict no-op: no thread, no hook, no patched function,
no logging handler — every entry point degrades to one module-global
bool check (the telemetry/diagnostics autostart discipline).

Three checkers:

* **RECOMPILE** — every jit cache in the repo registers itself through
  :func:`register_cache` (the executor's per-instance ``_jit_cache``,
  the imperative op cache ``ops/registry._JIT_CACHE``, the fused-fit
  TrainStep cache, ``TrainStep._multi_cache``, ``serving.ServedModel``'s
  bucket-rung ladder — and any future pp/elastic cache that merely
  calls ``register_cache``).  Each cache-miss reports its key as a dict
  of named fields; after a per-cache warmup budget (``MXNET_SAN_WARMUP``
  overrides every budget; the per-cache defaults correspond to one
  warmup epoch / one tick per serving rung) any further miss
  warns-or-raises naming the cache, its kind tag, and a field diff of
  the new key against its nearest warm neighbour — so the PR-7 class
  surfaces as ``key differs in field(s): num_update (0 -> 50)`` instead
  of a mysteriously slow second epoch.  Raw ``jax.jit`` sites outside
  any registered cache are watched through jax's compile-logging hook
  (a handler on the ``jax._src.interpreters.pxla`` logger): a function
  name that keeps compiling past its budget is reported too (warn-only
  — the logging layer swallows exceptions raised from handlers).

* **SYNC** — SYNC001's dynamic twin.  The hot-path regions (the fused
  TrainStep call, executor forward/backward, the serving batcher's
  coalesced forward) run inside :func:`hot_region`, which arms jax's
  ``transfer_guard_device_to_host`` (``disallow`` in raise mode,
  ``log`` otherwise — the guard fires on real accelerator transfers)
  plus Python-level sync hooks (``jax.device_get``,
  ``jax.block_until_ready``, and the jax array's ``item``/``__float__``
  /``__int__``/``__bool__``/``__array__`` — installed only while
  armed, restored on :func:`disarm`).  An unplanned device->host sync
  inside a region is a named violation; the legitimately-gated sites
  (telemetry span timing, ``amp_stats``, the numerics sentinel, the
  monitor) wrap themselves in :func:`allow_sync` with a reason, which
  also counts how often the escape hatch was used.

* **DONATE** — the donated-jit entry points (``TrainStep.__call__`` /
  ``run_steps``: params, optimizer state, aux, the loss-scale state)
  note every leaf they donate; passing such a buffer back into a step
  (or reading it through a sync hook) is flagged as a named contract
  violation — ``params['fc1_weight'] was donated at num_update=3`` —
  BEFORE XLA's cryptic "buffer has been deleted or donated" crash, and
  independently of whether the backend actually donated (a backend that
  silently ignores donation would ship the bug latent until the first
  run on one that honours it).

* **COLLECTIVE** — the SPMD twin of the COLL lint family
  (docs/static_analysis.md): every collective dispatch through the
  ``parallel.dist`` wrappers (allreduce, ``barrier``,
  ``coordination_barrier``) and the pipeline gradient gather records a
  ledger entry ``(seq, kind, name, shape/dtype signature, mesh axes,
  thread)`` — built from shape METADATA at dispatch, zero host syncs —
  and folds it into a per-rank rolling hash chain.  The chains are
  exchanged through the jax coordination service (key-value RPC, no
  device collectives) at every barrier entry and every fit epoch
  boundary; a mismatch names the FIRST divergent entry with a field
  diff against the majority rank ("rank 2 seq 41: mxtpu_pp_gather[...]
  where ranks 0,1,3 dispatched dist.allreduce[...]") *before* the world
  hangs in the mismatched collective.  A device collective dispatched
  off the main thread (the writer-thread deadlock
  ``dist.coordination_barrier`` exists to avoid; THR002's dynamic twin)
  is a named violation unless scoped by
  :func:`allow_thread_collective`.  With ``MXNET_SAN_COLL_TIMEOUT=<s>``
  set, a watchdog thread (the diagnostics armed-thread idiom) notices a
  dispatch that stays in flight past the budget and dumps the ledger
  tail into a diagnostics bundle — a hung fleet leaves a post-mortem
  naming which rank stopped at which seq.

One part is always on: the **set-up account** (:func:`setup_account`), a
``jax.monitoring`` feed installed once by the package's import, which
times each program's trace, lowering and compile and counts the
persistent cache's answers.  jax fires it only when it traces, lowers or
compiles, so a cached dispatch pays nothing; it feeds the per-cache
``compile_seconds`` and, while the telemetry registry records, the
``xla_compile`` / ``compile.seconds`` spans.

``stats()`` / ``violations()`` expose counters and the recent violation
messages; under telemetry every cache miss also refreshes the
``jit_cache_size`` gauge from the registry (the sum of live entries
across ALL registered caches — executor, imperative ops, fused-fit,
serving rungs), replacing the old executor-only ever-growing counter.

See docs/static_analysis.md "Runtime sanitizers".
"""
from __future__ import annotations

import threading
import time
import warnings
import weakref
from collections import deque
from contextlib import nullcontext

from .base import MXNetError, get_env
from . import telemetry as _tel

__all__ = ["SanitizerError", "SanitizerWarning", "arm", "disarm", "armed",
           "register_cache", "hot_region", "allow_sync", "note_donated",
           "check_donated", "donated_entry", "total_cache_entries",
           "caches", "stats", "violations", "reset", "note_collective",
           "collective_dispatch", "collective_sync", "collective_sig",
           "allow_thread_collective", "ledger_tail", "collective_state",
           "expect_recompile", "sig_nbytes", "record_wire_bytes",
           "wire_bytes", "hbm_arm", "hbm_disarm", "hbm_ledger",
           "hbm_note", "hbm_capture", "hbm_wrap", "cost_arm",
           "cost_disarm", "cost_ledger", "cost_note", "program_capture",
           "program_wrap", "compile_seconds", "setup_account",
           "SetupAccount", "install_setup_feed", "program_name"]

CHECKERS = ("recompile", "sync", "donate", "collective")

# per-kind default warmup budgets: the number of cache misses that count
# as legitimate warmup (one epoch of compiles for the train-side caches,
# one tick per rung for serving).  MXNET_SAN_WARMUP overrides all of
# them with one integer.
DEFAULT_WARMUPS = {
    "executor": 16,       # jit kinds x mon variants x trace-env retraces
    "op": 256,            # imperative dispatch: one key per (op, attrs)
    "fused_fit": 1,       # one TrainStep per (optimizer, policy, env)
    "train_multi": 4,     # run_steps chunk shapes
    "serving-rung": 8,    # overridden per model with len(buckets)
    "jax.jit": 16,        # raw-jit watcher: per function name
}
_WARM_KEEP = 512          # warm keys remembered per cache (FIFO)
_WARN_QUOTA = 10          # per-cache warn cap (counters keep counting)


class SanitizerError(MXNetError):
    """A sanitizer contract violation in ``:raise`` mode."""


class SanitizerWarning(UserWarning):
    """A sanitizer contract violation in warn mode (the default)."""


_lock = threading.RLock()
# arm/disarm serialization: NEVER hold ``_lock`` while joining the
# collective watchdog thread (it takes ``_lock`` in its scan loop);
# concurrent arm() calls serialize here instead so handler/patch
# installs still cannot double-install
_arm_lock = threading.RLock()
_armed = frozenset()      # subset of CHECKERS
_mode = "warn"
# hot-path guards: one module-global bool read while disarmed
_recompile_on = False
_sync_on = False
_donate_on = False
_collective_on = False

_CACHES = []              # list[_CacheHandle]
_DONATED = {}             # id(leaf) -> (label, where, step, ref)
_RAW_COMPILES = {}        # (jit fun name, shapes signature) -> count
# inner-function names registered caches jit (declared via
# register_cache(jit_names=...)), each to the newest handle declaring it:
# their compiles are those caches' OWN misses — the raw-jit watcher must
# not double-count them (many executors re-binding the same shapes
# legitimately recompile 'fwd'), and the set-up feed charges their trace,
# lowering and compile seconds to that handle
_REGISTERED_JIT_NAMES = {}
_stats = {"recompile_violations": 0, "sync_violations": 0,
          "donate_violations": 0, "collective_violations": 0,
          "sync_allowed": 0, "cache_misses": 0, "raw_compiles": 0,
          "collective_dispatches": 0, "collective_thread_allowed": 0}
_violations = deque(maxlen=200)
_wire_bytes = {}          # (kind, axes) -> cumulative payload bytes folded
                          # out of dispatch signatures (record_wire_bytes)
_hbm_on = False           # per-program HBM attribution armed (sentinel)
_hbm_ledger = {}          # program name -> memory_analysis byte breakdown
_cost_on = False          # per-program cost attribution armed
_cost_ledger = {}         # program name -> cost_analysis flop/byte row
_tls = threading.local()
_log_handler = None       # compile-log watcher state
_log_prev_level = None
_log_prev_propagate = None
_patches = []             # (obj, attr, original) for sync/donate hooks


# ----------------------------------------------------------------- helpers
def _state():
    st = getattr(_tls, "st", None)
    if st is None:
        st = _tls.st = type("_TlsState", (), {})()
        st.regions = []
        st.allow = 0
        st.coll_ok = 0
    return st


def _short(v, limit=64):
    r = repr(v)
    return r if len(r) <= limit else r[:limit - 3] + "..."


def _violation(checker, message, raise_ok=True, quiet=False):
    """Record one violation; warn or raise per the armed mode.  ``quiet``
    suppresses the warning (counters and the violation log still record —
    used to cap per-cache warn spam)."""
    with _lock:
        _stats[checker + "_violations"] += 1
        _violations.append(message)
    if _tel._enabled:
        _tel.counter("san_violations", checker=checker)
    if _mode == "raise" and raise_ok:
        if _tel.flight_recorder_armed():
            # the raise is about to unwind the run: leave the crash ring
            # behind first (MXNET_FLIGHT_RECORDER contract — every fatal
            # path flushes the last-N-events timeline into a bundle)
            try:
                from . import diagnostics as _diag
                _diag.write_snapshot("sanitizer_violation",
                                     extra={"checker": checker,
                                            "violation": message})
            except Exception:   # noqa: BLE001 — never mask the violation
                pass
        raise SanitizerError(message)
    if not quiet:
        warnings.warn(message, SanitizerWarning, stacklevel=3)


# ------------------------------------------------------------ cache registry
class _CacheHandle(object):
    """One registered jit cache: warm-key memory for the RECOMPILE
    checker plus a live-entry sizer for the ``jit_cache_size`` gauge."""

    def __init__(self, name, kind, owner, sizer, warmup, jit_names=()):
        self.name = name
        self.kind = kind or name
        self.warmup = warmup
        self.jit_names = tuple(jit_names)
        if jit_names:
            with _lock:
                _REGISTERED_JIT_NAMES.update(dict.fromkeys(jit_names, self))
        self._sizer = sizer
        self._owner_ref = None
        if owner is not None:
            try:
                self._owner_ref = weakref.ref(owner)
            except TypeError:       # un-weakref-able owner: pin it
                self._owner_ref = lambda o=owner: o
        self._warm = deque(maxlen=_WARM_KEEP)
        self._misses = 0
        self._miss_anchor = 0       # miss count when the checker was armed
        self._warned = 0
        self._compile_s = 0.0       # cumulative trace + lower + compile s

    # -- registry plumbing
    def alive(self):
        return self._owner_ref is None or self._owner_ref() is not None

    def entries(self):
        if not self.alive():
            return 0
        try:
            if self._owner_ref is not None:
                return int(self._sizer(self._owner_ref()))
            return int(self._sizer()) if self._sizer is not None else 0
        except Exception:
            return 0

    def _budget(self):
        env = get_env("MXNET_SAN_WARMUP", None, typ=int)
        if env is not None:
            return max(0, env)
        return self.warmup if self.warmup is not None \
            else DEFAULT_WARMUPS.get(self.kind, 16)

    # -- the RECOMPILE entry point (call on every cache MISS; a miss is
    #    about to pay an XLA compile, so the dict build costs nothing)
    def miss(self, fields):
        fields = dict(fields)
        violation = None
        with _lock:
            self._misses += 1
            _stats["cache_misses"] += 1
            if _recompile_on and \
                    (self._misses - self._miss_anchor) > self._budget():
                violation = self._diff_message(fields)
            else:
                self._warm.append(fields)
        if _tel._enabled:
            _tel.gauge("jit_cache_size", total_cache_entries())
        if violation is not None:
            with _lock:
                self._warned += 1
                quiet = self._warned > _WARN_QUOTA
            _violation("recompile", violation, quiet=quiet)

    def _diff_message(self, fields):
        head = ("mxsan RECOMPILE: jit cache '%s' (kind=%s) missed after "
                "its warmup budget (%d)" % (self.name, self.kind,
                                            self._budget()))
        best, best_score = None, -1
        for w in self._warm:
            score = sum(1 for k in fields if k in w and w[k] == fields[k])
            if score > best_score:
                best, best_score = w, score
        if best is None:
            return head + " with no warm keys recorded — an always-cold " \
                "cache on the hot path"
        diffs = sorted(set(fields) | set(best))
        parts = ["%s (%s -> %s)" % (k, _short(best.get(k)),
                                    _short(fields.get(k)))
                 for k in diffs if best.get(k) != fields.get(k)]
        return head + "; key differs from its nearest warm neighbour in " \
            "field(s): %s — an unstable cache key (step state or an " \
            "unkeyed lever leaking into the key; the PR-7 num_update " \
            "class)" % ("; ".join(parts) or "<none — duplicate key, "
                        "entries are being evicted/rebuilt>")

    # -- compile-time accounting (the set-up feed calls it with each
    #    trace, lowering or compile interval of this cache's programs;
    #    cumulative per cache, mirrored to /metrics)
    def compile_note(self, seconds):
        seconds = float(seconds)
        with _lock:
            self._compile_s += seconds
            total = self._compile_s
        if _tel._enabled:
            _tel.gauge("compile_seconds", round(total, 3), cache=self.name)

    def snapshot(self):
        with _lock:
            return {"name": self.name, "kind": self.kind,
                    "entries": self.entries(), "misses": self._misses,
                    "warm": len(self._warm), "warmup": self._budget(),
                    "compile_seconds": round(self._compile_s, 6)}


def register_cache(name, kind=None, owner=None, sizer=None, warmup=None,
                   jit_names=()):
    """Register a jit cache with the sanitizer; returns a handle.

    Call :meth:`handle.miss(fields)` on every cache miss with the key as
    a dict of *named* fields (field names make the RECOMPILE diff
    readable: ``num_update (0 -> 50)``).  ``sizer`` reports live entry
    count — ``sizer(owner)`` when ``owner`` is given (held by weakref so
    a dead owner drops out of the ``jit_cache_size`` gauge), else
    ``sizer()``.  ``warmup`` is this cache's miss budget (default: the
    per-``kind`` entry in ``DEFAULT_WARMUPS``; ``MXNET_SAN_WARMUP``
    overrides every budget).  ``jit_names`` declares the inner function
    names this cache jits (``("fwd", "f")`` for the executor): their
    compiles are this cache's own misses, so the raw-jit log watcher
    skips them, and the set-up feed charges their trace, lowering and
    compile seconds to this handle (the newest handle that declares a
    name owns it).  Registration is always active and costs a list append —
    the checkers consult it only when armed."""
    h = _CacheHandle(name, kind, owner, sizer, warmup, jit_names=jit_names)
    with _lock:
        _CACHES.append(h)
        if len(_CACHES) % 64 == 0:      # prune dead owners occasionally
            _CACHES[:] = [c for c in _CACHES if c.alive()]
    return h


def total_cache_entries():
    """Live compiled-program count across every registered cache — the
    ``jit_cache_size`` gauge source (executor kinds + imperative op keys
    + fused-fit steps + serving rungs all visible)."""
    with _lock:
        handles = list(_CACHES)
    return sum(h.entries() for h in handles if h.alive())


def caches():
    """Snapshot of every live registered cache (diagnostics/tests)."""
    with _lock:
        handles = list(_CACHES)
    return [h.snapshot() for h in handles if h.alive()]


# ------------------------------------------------------- raw-jit compile log
_PXLA_LOGGER = "jax._src.interpreters.pxla"


def _raw_compile(fun_name, shapes):
    """One XLA compile seen through the log hook.  A *healthy* process
    never compiles the same (function, shapes) signature twice — jax's
    own pjit cache would have hit; repeats mean fresh jit objects are
    being created for the same program (the PR-7 loop at the raw-jit
    level).  Distinct shapes are normal warmup (buckets, rungs)."""
    with _lock:
        if len(_RAW_COMPILES) > 65536:       # runaway/shape-churn guard
            _RAW_COMPILES.clear()
        key = (fun_name, shapes)
        _RAW_COMPILES[key] = n = _RAW_COMPILES.get(key, 0) + 1
        _stats["raw_compiles"] += 1
    env = get_env("MXNET_SAN_WARMUP", None, typ=int)
    budget = max(0, env) if env is not None else DEFAULT_WARMUPS["jax.jit"]
    if n > budget:
        # raise_ok=False: logging swallows exceptions raised from
        # handlers, so the raw-jit watcher always warns (and counts);
        # quiet past the per-signature quota, mirroring the per-cache cap
        _violation(
            "recompile",
            "mxsan RECOMPILE: raw jax.jit '%s' compiled %d times (budget "
            "%d) for the SAME input signature %s — an unstable cache key "
            "or an untracked jit site; route it through a cache "
            "registered with sanitize.register_cache"
            % (fun_name, n, budget, _short(shapes, 96)),
            raise_ok=False, quiet=(n - budget) > _WARN_QUOTA)


def _make_log_handler():
    import logging
    import re
    # jax 0.9: "Compiling jit(<fun>) with global shapes and types
    # (<avals>,). Argument mapping: ..."
    pat = re.compile(r"^Compiling jit\((\S+)\) with global shapes and "
                     r"types (\(.*?\))\. Argument mapping:")

    class _CompileLogHandler(logging.Handler):
        def emit(self, record):
            try:
                m = pat.match(record.getMessage())
            except Exception:       # never break the observed process
                return
            # zero-arg programs are jax's own trace-time constant
            # subroutines (jit('call') churn while tracing) — not a
            # recompile-loop signal; names a registered cache declared
            # (via jit_names=) are that cache's own misses, watched by
            # its handle with its own warmup budget
            if m and m.group(2) != "()" \
                    and m.group(1) not in _REGISTERED_JIT_NAMES:
                _raw_compile(m.group(1), m.group(2))

    return _CompileLogHandler(level=logging.DEBUG)


def _attach_compile_log():
    global _log_handler, _log_prev_level, _log_prev_propagate
    import logging
    logger = logging.getLogger(_PXLA_LOGGER)
    _log_handler = _make_log_handler()
    _log_prev_level = logger.level
    _log_prev_propagate = logger.propagate
    logger.addHandler(_log_handler)
    # the "Compiling <fun>" line logs at DEBUG unless jax_log_compiles is
    # on, so the logger's level must drop to DEBUG — and propagation must
    # stop, or every compile line would spill to stderr through the
    # handler jax installs on its parent "jax" logger.  Both are restored
    # exactly on disarm.
    logger.propagate = False
    if logger.getEffectiveLevel() > logging.DEBUG:
        logger.setLevel(logging.DEBUG)


def _detach_compile_log():
    global _log_handler, _log_prev_level, _log_prev_propagate
    if _log_handler is None:
        return
    import logging
    logger = logging.getLogger(_PXLA_LOGGER)
    logger.removeHandler(_log_handler)
    logger.setLevel(_log_prev_level if _log_prev_level is not None
                    else logging.NOTSET)
    if _log_prev_propagate is not None:
        logger.propagate = _log_prev_propagate
    _log_handler = None
    _log_prev_level = None
    _log_prev_propagate = None


# ------------------------------------------------------------- sync checker
_NOOP = nullcontext()     # shared disabled-path singleton (reentrant)


class _HotRegion(object):
    """Armed hot-path region: transfer guard + thread-local region mark."""

    __slots__ = ("name", "_tg")

    def __init__(self, name):
        self.name = name
        self._tg = None

    def __enter__(self):
        import jax
        self._tg = jax.transfer_guard_device_to_host(
            "disallow" if _mode == "raise" else "log")
        self._tg.__enter__()
        # marked LAST: a failure above must not leave a stale region
        # (the with-statement skips __exit__ when __enter__ raises)
        _state().regions.append(self.name)
        return self

    def __exit__(self, *exc):
        try:
            if self._tg is not None:
                self._tg.__exit__(*exc)
        finally:
            st = _state()
            if st.regions:
                st.regions.pop()
        return False


def hot_region(name):
    """Mark a hot-path region (fused TrainStep call, executor
    forward/backward, the serving batcher's coalesced forward).  A no-op
    singleton while the SYNC checker is off; armed, it enables jax's
    device->host transfer guard and the Python sync hooks for the
    dynamic extent of the ``with`` block."""
    if not _sync_on:
        return _NOOP
    return _HotRegion(name)


class _AllowSync(object):
    """Scoped escape hatch for planned syncs inside a hot region."""

    __slots__ = ("reason", "_tg")

    def __init__(self, reason):
        self.reason = reason
        self._tg = None

    def __enter__(self):
        if _sync_on:
            import jax
            self._tg = jax.transfer_guard_device_to_host("allow")
            self._tg.__enter__()
        # incremented LAST: a failure above must not leak the allow count
        # (the with-statement skips __exit__ when __enter__ raises, and a
        # leaked increment would silently disable SYNC on this thread)
        _state().allow += 1
        return self

    def __exit__(self, *exc):
        try:
            if self._tg is not None:
                self._tg.__exit__(*exc)
        finally:
            _state().allow -= 1
        return False


def allow_sync(reason):
    """Declare a *planned* device sync (telemetry span timing, the
    numerics sentinel, monitor collection, ``amp_stats``): inside the
    scope the SYNC checker stands down and counts the use instead of
    flagging it.  No-op while the sanitizer is off."""
    if not (_sync_on or _donate_on):
        return _NOOP
    return _AllowSync(reason)


def _sync_event(what):
    """A Python-level sync hook fired.  Free outside hot regions."""
    st = _state()
    if not st.regions:
        return
    if st.allow:
        with _lock:
            _stats["sync_allowed"] += 1
        return
    _violation("sync",
               "mxsan SYNC: unplanned host sync (%s) inside hot region "
               "'%s' — the telemetry-off step must not touch the host; "
               "move it out of the per-step body or scope it with "
               "sanitize.allow_sync(reason)" % (what, st.regions[-1]))


# ----------------------------------------------------------- donate checker
def _donated_cleanup(key):
    def cb(_ref):
        _DONATED.pop(key, None)
    return cb


def note_donated(where, labeled_leaves, step=None):
    """Record buffers just donated to a jit (called AFTER dispatch by the
    donating entry points).  ``labeled_leaves`` yields ``(label, leaf)``
    pairs — the label names the pytree path in the violation message."""
    for label, leaf in labeled_leaves:
        if leaf is None or not hasattr(leaf, "dtype"):
            continue
        key = id(leaf)
        try:
            ref = weakref.ref(leaf, _donated_cleanup(key))
        except TypeError:
            ref = (lambda obj=leaf: obj)     # pin: id stays valid
        with _lock:
            _DONATED[key] = (label, where, step, ref)
            if len(_DONATED) > 65536:        # runaway guard
                _DONATED.clear()


def donated_entry(leaf):
    """(label, where, step) when ``leaf`` was donated earlier, else
    None.  Identity-checked through the stored weakref so a recycled
    ``id()`` can never mis-accuse a fresh array."""
    ent = _DONATED.get(id(leaf))
    if ent is None:
        return None
    label, where, step, ref = ent
    if ref() is not leaf:
        return None
    return label, where, step


def _deleted(leaf):
    try:
        return bool(leaf.is_deleted())
    except Exception:
        return False


def check_donated(where, labeled_leaves):
    """Flag any input buffer that an earlier step donated — the
    delete-on-donate crash surfaced as a named contract violation before
    the dispatch dies, and surfaced at all on backends that silently
    ignore donation (where the stale-buffer bug would ship latent)."""
    for label, leaf in labeled_leaves:
        if leaf is None:
            continue
        ent = donated_entry(leaf)
        if ent is not None:
            dlabel, dwhere, dstep = ent
            _violation(
                "donate",
                "mxsan DONATE: %s passed to %s was already donated (as %s "
                "by %s%s) — donated buffers die with the jit call; thread "
                "the step's RETURNED pytrees forward instead of re-using "
                "the inputs" % (label, where, dlabel, dwhere,
                                "" if dstep is None
                                else " at num_update=%s" % dstep))
        elif _deleted(leaf):
            _violation(
                "donate",
                "mxsan DONATE: %s passed to %s refers to a deleted (XLA-"
                "donated) buffer — thread the returned pytrees forward"
                % (label, where))


# ------------------------------------------------------- collective checker
_COLL_KEEP = 4096         # ledger entries remembered per rank (FIFO)
_COLL_TAIL = 64           # entries published at each hash-chain exchange
# seconds to wait for a peer's exchange payload: >= the LARGEST bounded
# barrier in the repo (coordination_barrier's 600 s default; the ckpt /
# elastic epoch barriers bound at 300 s) — a legitimately slow rank-0
# pre-barrier save must never turn into a false "never reached the
# checkpoint" violation.  Deliberately NOT tied to
# MXNET_SAN_COLL_TIMEOUT (the stall-watchdog budget): a tight deadlock
# budget must not shrink exchange tolerance.
_COLL_SYNC_DEFAULT = 600.0

_coll_seq = 0             # total dispatches this process has recorded
_coll_mseq = 0            # MAIN-thread dispatches only: the hash-chain
                          # position, comparable across ranks (side
                          # threads interleave nondeterministically, so
                          # they must not shift the chained numbering)
_coll_ledger = deque(maxlen=_COLL_KEEP)
_coll_chain = "0" * 40    # rolling sha1 over the canonical entry stream
_coll_xchg = 0            # exchange-point counter (agrees across ranks as
                          # long as every rank reaches the same barriers /
                          # epoch boundaries — which is what is checked)
_coll_gen = 0             # rebase generation: bumps at each live-resize
                          # membership transition (collective_rebase) so
                          # pre-transition chained entries stop feeding
                          # the exchanged tail — a fresh joiner has no
                          # pre-transition history to compare against
_coll_inflight = {}       # thread ident -> (entry, monotonic start)
_coll_stalled = set()     # entry seqs already dumped (one bundle each)
_coll_watch_thread = None
_coll_watch_stop = None   # threading.Event while the watchdog runs
_coll_client_warned = False


def _coll_canon(entry):
    """Canonical byte form of a ledger entry for the hash chain: the
    dispatch identity only.  The thread name stays out (a local property
    checked separately, not part of the cross-rank order contract) and
    so does the global ledger seq (side-thread dispatches consume seqs
    at rank-dependent points; the rolling hash already encodes order)."""
    import json
    return json.dumps([entry["kind"], entry["name"], entry["sig"],
                       entry["axes"]],
                      sort_keys=True, separators=(",", ":"))


def _fmt_entry(entry):
    parts = []
    if entry.get("name") is not None:
        parts.append("name=%s" % entry["name"])
    if entry.get("sig") is not None:
        parts.append("sig=%s" % (entry["sig"],))
    if entry.get("axes") is not None:
        parts.append("axes=%s" % entry["axes"])
    return "%s[%s]" % (entry.get("kind"), ", ".join(parts))


def collective_sig(arrays):
    """Shape/dtype signature of a collective's payload, from metadata
    only (never a device sync): ``("f32(8,4)", "i32(2,)")``."""
    out = []
    for a in arrays:
        dt = str(getattr(a, "dtype", "?"))
        dt = {"float32": "f32", "float64": "f64", "bfloat16": "bf16",
              "float16": "f16", "int32": "i32", "int64": "i64",
              "uint32": "u32", "bool": "b1"}.get(dt, dt)
        shape = tuple(getattr(a, "shape", ()))
        out.append("%s(%s)" % (dt, ",".join(str(d) for d in shape)))
    return tuple(out)


# itemsizes for the collective_sig dtype abbreviations (plus the raw
# numpy names a non-mapped dtype falls through as)
_SIG_ITEMSIZE = {
    "f64": 8, "i64": 8, "u64": 8, "c64": 8,
    "f32": 4, "i32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "i16": 2, "u16": 2,
    "i8": 1, "u8": 1, "b1": 1,
    "int16": 2, "uint16": 2, "int8": 1, "uint8": 1,
}


def sig_nbytes(sig):
    """Payload bytes of a :func:`collective_sig` tuple — the same
    metadata-only arithmetic, run in reverse: ``("f32(8,4)", "i32(2)")``
    -> 136.  Parts that are not shape/dtype-formed (a barrier's ``None``
    sig, historical free-text sigs) contribute 0, so the accounting can
    never raise or sync on an exotic dispatch."""
    total = 0
    for part in sig or ():
        if not isinstance(part, str):
            continue
        dt, sep, rest = part.partition("(")
        if not sep or not rest.endswith(")"):
            continue
        itemsize = _SIG_ITEMSIZE.get(dt)
        if itemsize is None:
            continue
        elems = 1
        try:
            for d in rest[:-1].split(","):
                d = d.strip()
                if d:
                    elems *= int(d)
        except ValueError:
            continue
        total += itemsize * elems
    return total


def record_wire_bytes(kind, sig=None, axes=None, nbytes=None):
    """Fold one collective dispatch's payload into the per-(kind, axes)
    wire-bytes ledger.  ``nbytes`` overrides the sig arithmetic for sites
    whose ledger sig is not shape-typed (the ZeRO gather's ``"%d
    tensors"``).  Emits the ``coll_wire_bytes[kind/axes]`` telemetry
    counter while recording.  Call sites gate on ``if _san._collective_on
    or _tel._enabled:`` — with both off this is never reached, so the
    accounting keeps the strict zero-overhead contract."""
    if nbytes is None:
        nbytes = sig_nbytes(sig)
    nbytes = int(nbytes)
    if nbytes <= 0:
        return 0
    key = (kind, axes if axes is not None else "-")
    with _lock:
        _wire_bytes[key] = _wire_bytes.get(key, 0) + nbytes
    if _tel._enabled:
        _tel.counter("coll_wire_bytes[%s/%s]" % key, nbytes)
    return nbytes


def wire_bytes():
    """Snapshot of cumulative collective payload bytes:
    ``{"kind/axes": bytes}`` (``-`` for axis-less dispatches).  Exposed to
    users as ``dist.wire_bytes()``; the per-key telemetry counters carry
    the same totals onto ``/metrics``."""
    with _lock:
        return {"%s/%s" % k: v for k, v in sorted(_wire_bytes.items())}


# ------------------------------------------- per-program HBM attribution
# The wire-bytes ledger's memory twin: every jit cache registered
# through register_cache captures its compiled program's
# ``memory_analysis()`` breakdown (argument / output / temp /
# generated-code bytes) at compile time.  Metadata only, dist-free, no
# device work — ``.lower(...).compile()`` on an already-jitted callable
# reuses the cached executable, and capture happens BEFORE the first
# call so donated arguments are still alive.  Armed by the sentinel
# (``MXNET_SENTINEL``); with ``_hbm_on`` False every entry point is one
# bool read.  Rendered by tools/hbm_report.py; surfaced as the ``hbm``
# diagnostics-bundle section and the ``hbm_program_bytes`` gauges.

def hbm_arm():
    """Arm per-program HBM attribution (capture-at-compile)."""
    global _hbm_on
    with _lock:
        _hbm_on = True


def hbm_disarm():
    """Disarm HBM attribution and clear the ledger."""
    global _hbm_on
    with _lock:
        _hbm_on = False
        _hbm_ledger.clear()


def hbm_ledger():
    """Snapshot of the per-program HBM ledger: ``{name: {args, outputs,
    temps, generated_code, alias, total}}``, bytes.  ``total`` is
    args + outputs + temps + generated_code − alias (donated pairs
    counted once), matching jax's CompiledMemoryStats accounting."""
    with _lock:
        return {k: dict(v) for k, v in sorted(_hbm_ledger.items())}


def hbm_note(name, mem_stats):
    """Fold one compiled program's ``CompiledMemoryStats`` into the
    ledger under ``name`` (last capture wins — a re-trace replaces its
    predecessor, mirroring the jit cache it describes)."""
    row = {
        "args": int(getattr(mem_stats, "argument_size_in_bytes", 0)),
        "outputs": int(getattr(mem_stats, "output_size_in_bytes", 0)),
        "temps": int(getattr(mem_stats, "temp_size_in_bytes", 0)),
        "generated_code": int(
            getattr(mem_stats, "generated_code_size_in_bytes", 0)),
        "alias": int(getattr(mem_stats, "alias_size_in_bytes", 0)),
    }
    row["total"] = (row["args"] + row["outputs"] + row["temps"]
                    + row["generated_code"] - row["alias"])
    with _lock:
        _hbm_ledger[str(name)] = row
    if _tel._enabled:
        _tel.gauge("hbm_program_bytes", row["total"], program=str(name))
    return row


def hbm_capture(name, fn, args=(), kwargs=None):
    """Lower+compile ``fn`` for ``args`` and record its memory analysis
    under ``name``.  Best-effort by contract: abstract tracers (an
    executor grad jit invoked under ``jax.vjp``), backends without
    ``memory_analysis``, or any lowering error degrade to a silent None
    — attribution must never add a failure mode to the program it
    measures."""
    if not _hbm_on:
        return None
    out = program_capture(name, fn, args, kwargs)
    return out.get("hbm") if out else None


def hbm_wrap(name, fn):
    """Wrap a jitted callable so its first invocation captures HBM
    attribution from the very arguments it compiles for.  Returns ``fn``
    unchanged while attribution is off (the strict-no-op contract); the
    armed wrapper self-removes its overhead down to one bool read after
    the first call."""
    if not _hbm_on:
        return fn
    return program_wrap(name, fn)


# ------------------------------------------- per-program cost attribution
# The HBM ledger's compute twin: the same capture-at-compile hook also
# records the compiled program's ``cost_analysis()`` — model FLOPs,
# bytes accessed, transcendentals — so every jit program has a cost
# identity (roofline arithmetic intensity) and the fused fit can fold
# measured step wall time into an MFU against MXNET_PEAK_FLOPS.  Armed
# with HBM attribution by the sentinel, or alone by the fused fit when
# peaks are configured; with ``_cost_on`` False every entry point is one
# bool read.  Rendered by tools/cost_report.py; surfaced as the ``cost``
# diagnostics-bundle section and the ``cost_program_flops`` gauges.

def cost_arm():
    """Arm per-program cost attribution (capture-at-compile)."""
    global _cost_on
    with _lock:
        _cost_on = True


def cost_disarm():
    """Disarm cost attribution and clear the ledger."""
    global _cost_on
    with _lock:
        _cost_on = False
        _cost_ledger.clear()


def cost_ledger():
    """Snapshot of the per-program cost ledger: ``{name: {flops,
    bytes_accessed, transcendentals, intensity, compile_seconds}}``.
    ``intensity`` is flops / bytes_accessed (the roofline x-axis); a
    program whose backend reports no byte traffic carries 0.0."""
    with _lock:
        return {k: dict(v) for k, v in sorted(_cost_ledger.items())}


def _cost_props(analysis):
    """Normalize a ``cost_analysis()`` result to one flat dict.  jax has
    returned both a list of per-device dicts and a bare dict across
    versions; every device runs the same SPMD program, so the first
    entry speaks for all."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return None
    return analysis


def cost_note(name, analysis, compile_s=None):
    """Fold one compiled program's ``cost_analysis()`` into the ledger
    under ``name`` (last capture wins, mirroring the jit cache it
    describes).  Returns the row, or None when the backend reported
    nothing usable."""
    props = _cost_props(analysis)
    if props is None:
        return None
    row = {
        "flops": int(props.get("flops", 0) or 0),
        "bytes_accessed": int(props.get("bytes accessed", 0) or 0),
        "transcendentals": int(props.get("transcendentals", 0) or 0),
    }
    row["intensity"] = (round(row["flops"] / float(row["bytes_accessed"]), 4)
                        if row["bytes_accessed"] else 0.0)
    if compile_s is not None:
        row["compile_seconds"] = round(float(compile_s), 6)
    with _lock:
        _cost_ledger[str(name)] = row
    if _tel._enabled:
        _tel.gauge("cost_program_flops", row["flops"], program=str(name))
    return row


def program_capture(name, fn, args=(), kwargs=None, cache=None):
    """The unified capture-at-compile hook: one
    ``fn.lower(*args).compile()``, then whatever ledgers are armed —
    ``memory_analysis()`` when ``_hbm_on``, ``cost_analysis()`` when
    ``_cost_on``.  It keeps no clock: the set-up feed times the capture's
    trace, lowering and compile, charges them to ``cache`` (a
    register_cache handle) where no handle declares the program's name,
    writes them as a ``compile.seconds`` telemetry span named ``name``,
    and the cost row's ``compile_seconds`` is their union.

    Arming pays each compile once: jax caches the lowering per argument
    signature and keeps the compiled executable on it, so the dispatch of
    ``fn`` with these same arguments reuses what was compiled here
    (test_cost.py and test_setup_account.py count the backend compiles).
    A program with a Pallas kernel lowered here carries this call stack
    in its kernel's payload, which the persistent cache's key keeps: its
    first capture misses entries that plain dispatches wrote.

    Attribution must never add a failure mode to the program it measures,
    so a capture that cannot run returns None — but not silently: tracer
    arguments (an executor grad jit first invoked under ``jax.vjp``) are
    the one expected case and skip quietly; any other lowering or compile
    error is logged as a warning naming the program, because the ledger
    row, and every MFU figure that needs it, will be missing.  Returns
    ``{"hbm": row|None, "cost": row|None}``."""
    if not (_hbm_on or _cost_on):
        return None
    import jax
    if any(isinstance(leaf, jax.core.Tracer)
           for leaf in jax.tree_util.tree_leaves((args, kwargs))):
        return None
    st = _state()
    outer, st.capture = getattr(st, "capture", None), \
        {"name": str(name), "cache": cache, "spans": []}
    try:
        compiled = fn.lower(*args, **(kwargs or {})).compile()
    except Exception as e:
        import logging
        logging.getLogger(__name__).warning(
            "mxsan: no HBM/cost row for program '%s' — lowering it for "
            "attribution failed: %s: %s", name, type(e).__name__, e)
        return None
    finally:
        spans, st.capture = st.capture["spans"], outer
    out = {"hbm": None, "cost": None}
    if _hbm_on:
        try:
            stats = compiled.memory_analysis()
            if stats is not None:
                out["hbm"] = hbm_note(name, stats)
        except Exception:
            pass
    if _cost_on:
        try:
            out["cost"] = cost_note(name, compiled.cost_analysis(),
                                    compile_s=_union_seconds(spans))
        except Exception:
            pass
    return out


def program_wrap(name, fn, cache=None):
    """Wrap a jitted callable so its first invocation runs
    :func:`program_capture` on the very arguments it compiles for.
    Returns ``fn`` unchanged while both ledgers are off (the
    strict-no-op contract); the armed wrapper self-removes its overhead
    down to one bool read after the first call."""
    if not (_hbm_on or _cost_on):
        return fn
    state = {"done": False}

    def first_call(*args, **kwargs):
        if not state["done"]:
            state["done"] = True
            program_capture(name, fn, args, kwargs, cache=cache)
        return fn(*args, **kwargs)

    first_call.__name__ = getattr(fn, "__name__", "first_call")
    first_call.__wrapped__ = fn
    return first_call


def compile_seconds():
    """Cumulative trace + lowering + compile seconds per registered cache
    (plus a ``total``), fed by the set-up feed through
    ``_CacheHandle.compile_note`` in every run — the seconds a warm
    persistent compilation cache shortens to a load.  Caches that never
    compiled are omitted; empty dict when nothing was measured."""
    with _lock:
        out = {h.name: round(h._compile_s, 6)
               for h in _CACHES if h._compile_s > 0.0}
        if out:
            out["total"] = round(sum(out.values()), 6)
        return out


# ------------------------------------------------------------ set-up account
# One feed, always on, times what a process spends before its first step:
# ``jax.monitoring``'s trace, lowering (Mosaic's included) and compile
# spans (``backend_compile_duration`` encloses the persistent cache's
# lookup, so a load is timed there too) and the persistent cache's request
# / hit / write events, plus the package's own import.  jax fires these
# only when it traces, lowers or compiles: a cached dispatch records
# nothing.  Every interval feeds the owning cache's compile_note, and,
# while the telemetry registry records, one span per program: ``xla_compile``
# for a program compiled by its first dispatch, ``compile.seconds`` for one
# compiled by program_capture.

_FEED_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                "/jax/core/compile/backend_compile_duration": "compile"}
_FEED_COUNTS = {"/jax/compilation_cache/compile_requests_use_cache":
                "requests",
                "/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "written"}
SETUP_PHASES = ("import", "trace", "lower", "compile")
_feed_installed = False
_feed_failed = False        # the feed's first failure is logged, once


class SetupAccount(object):
    """What the feed saw, in memory and bounded: intervals ``(phase,
    program, start, end)`` and counted events ``(time, kind)``, both on the
    ``time.perf_counter`` clock, and the package's import."""

    KEEP = 65536

    def __init__(self):
        self.intervals = deque(maxlen=self.KEEP)
        self.events = deque(maxlen=self.KEEP)
        self.imported = None
        self.lock = threading.Lock()    # jax may compile on any thread

    def read(self, since=None, until=None, top=5):
        """Seconds of each phase in ``[since, until]`` (open ends: all),
        each instant counted once, for the innermost interval that covers
        it: an inner jit traced inside its caller's trace is the caller's
        trace, a program compiled while another is traced is compile.  So
        the phases never overlap and their sum is at most the cut's
        length.  Also the persistent cache's ``requests``, ``hits``,
        ``misses`` (requests it did not answer) and ``written`` (entries
        jax wrote), and per phase the ``top`` programs by seconds."""
        lo = float("-inf") if since is None else since
        hi = float("inf") if until is None else until
        with self.lock:
            spans, events = list(self.intervals), list(self.events)
        if self.imported is not None:
            spans.append(("import", "mxnet_tpu") + tuple(self.imported))
        spans = [(p, n, max(a, lo), min(b, hi)) for p, n, a, b in spans
                 if min(b, hi) > max(a, lo)]
        by_program = _innermost_seconds(spans)
        out = dict.fromkeys(SETUP_PHASES, 0.0)
        programs = {p: [] for p in SETUP_PHASES}
        for (phase, name), sec in by_program.items():
            out[phase] += sec
            programs[phase].append([name, sec])
        counts = dict.fromkeys(_FEED_COUNTS.values(), 0)
        for t, kind in events:
            if lo <= t <= hi:
                counts[kind] += 1
        out.update(counts)
        out["misses"] = counts["requests"] - counts["hits"]
        out["programs"] = {
            p: sorted(rows, key=lambda r: -r[1])[:top]
            for p, rows in programs.items()}
        return out


_setup = SetupAccount()


def _innermost_seconds(spans):
    """``{(phase, program): seconds}``: each instant covered by ``spans``
    goes to the covering span that started last (at one start, the
    shorter), the innermost of nested spans."""
    marks = sorted([(a, 1, -b, i) for i, (_, _, a, b) in enumerate(spans)]
                   + [(b, 0, 0, i) for i, (_, _, _, b) in enumerate(spans)])
    out = {}
    active, prev = [], None
    for t, opens, _, i in marks:
        if active and t > prev:
            key = spans[active[-1]][:2]
            out[key] = out.get(key, 0.0) + (t - prev)
        if opens:
            active.append(i)
        else:
            active.remove(i)
        prev = t
    return out


def _union_seconds(spans):
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(spans):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


def program_name(fun_name):
    """The function's own name in a feed event: jax names a trace by the
    function and a lowering or compile by its module, ``jit(<name>)``,
    ``jvp(<name>)`` and so on."""
    name = str(fun_name)
    while name.endswith(")") and "(" in name:
        head, _, inner = name.partition("(")
        if not head.replace("_", "").isalnum():
            break
        name = inner[:-1]
    return name


def _owner(name):
    """The live registered cache that declares ``name`` among its
    ``jit_names`` (the newest, when several do), or None."""
    h = _REGISTERED_JIT_NAMES.get(name)
    if h is None or h.alive():
        return h
    with _lock:
        live = [c for c in _CACHES if c.alive() and name in c.jit_names]
        if live:
            _REGISTERED_JIT_NAMES[name] = live[-1]
        else:
            _REGISTERED_JIT_NAMES.pop(name, None)
    return live[-1] if live else None


def _on_feed_span(event, start_time, end_time, fun_name="?", **_):
    phase = _FEED_PHASES.get(event)
    if phase is None:
        return
    try:
        end = time.perf_counter()
        dur = end_time - start_time
        start = end - dur
        name = program_name(fun_name)
        with _setup.lock:
            _setup.intervals.append((phase, name, start, end))
        st = _state()
        cap = getattr(st, "capture", None)
        owner = _owner(name) or (cap["cache"] if cap else None)
        if owner is not None:
            owner.compile_note(dur)
        if cap is not None:
            cap["spans"].append((start, end))
        if _tel._enabled:
            _feed_telemetry(st, cap, phase, name, start, end)
    except Exception:       # noqa: BLE001 — the feed never fails a compile
        global _feed_failed
        if not _feed_failed:
            _feed_failed = True
            import logging
            logging.getLogger(__name__).warning(
                "mxsan: the set-up feed failed on %s of %r; its account "
                "may miss intervals", phase, fun_name, exc_info=True)


def _feed_telemetry(st, cap, phase, name, start, end):
    """One registry span a program, written at its compile's end: the
    union of the trace, lowering and compile intervals this thread saw
    under the program's name since its last compile."""
    pending = getattr(st, "feed_pending", None)
    if pending is None or len(pending) > 256:
        pending = st.feed_pending = {}
    seen = pending.setdefault(name, [])
    seen.append((start, end))
    if phase != "compile":
        return
    del pending[name]
    first = min(a for a, _ in seen)
    tags = {"kind": name, "persistent_hit": bool(
        getattr(st, "feed_hit", False))}
    st.feed_hit = False
    if cap is not None:
        tags["program"] = cap["name"]
    _tel.record_span("compile.seconds" if cap is not None else "xla_compile",
                     time.time() - (time.perf_counter() - first),
                     _union_seconds(seen), cat="compile", **tags)


def _on_feed_event(event, **_):
    kind = _FEED_COUNTS.get(event)
    if kind is None:
        return
    with _setup.lock:
        _setup.events.append((time.perf_counter(), kind))
    if kind == "hits" and _tel._enabled:
        _state().feed_hit = True


def install_setup_feed(import_start, import_end):
    """Install the feed's two ``jax.monitoring`` listeners, once per
    process, and note the package's import interval.  Called by the last
    line of ``mxnet_tpu/__init__.py``, where jax is already imported."""
    global _feed_installed
    if _setup.imported is None:
        _setup.imported = (import_start, import_end)
    with _lock:
        if _feed_installed:
            return
        _feed_installed = True
    from jax import monitoring
    monitoring.register_event_time_span_listener(_on_feed_span)
    monitoring.register_event_listener(_on_feed_event)


def setup_account(since=None, until=None, top=5):
    """The set-up account up to ``until`` (a ``time.perf_counter`` stamp,
    such as a window's first dispatch), from ``since``: seconds of
    ``import``, ``trace``, ``lower`` and ``compile`` (disjoint, see
    :meth:`SetupAccount.read`), the persistent cache's ``requests``,
    ``hits``, ``misses`` and ``written``, and the largest ``programs`` of
    each phase."""
    return _setup.read(since, until, top)


def note_collective(kind, name=None, sig=None, axes=None, device=True):
    """Record one collective dispatch in the per-rank ledger and fold it
    into the rolling hash chain.  ``device=True`` marks a DEVICE
    collective (XLA program over device slices): dispatching one off the
    main thread can interleave with in-flight training collectives and
    deadlock the world — named here (THR002's dynamic twin) unless the
    thread is scoped by :func:`allow_thread_collective`.
    ``coordination_barrier`` passes ``device=False`` (service RPC, safe
    from any thread).  Call sites guard with ``if _san._collective_on:``
    or go through :func:`collective_dispatch`."""
    import hashlib
    global _coll_seq, _coll_mseq, _coll_chain
    thread = threading.current_thread()
    on_main = thread is threading.main_thread()
    with _lock:
        _coll_seq += 1
        entry = {"seq": _coll_seq, "kind": kind, "name": name,
                 "sig": sig, "axes": axes, "thread": thread.name}
        if on_main:
            # only MAIN-thread dispatches fold into the hash chain: the
            # chain verifies the SPMD dispatch ORDER, and the async
            # checkpoint writer's service barriers interleave with the
            # main thread at nondeterministic points per rank (they pair
            # by barrier id, not by order — that id uniqueness is
            # COLL002's job).  Off-main entries still land in the
            # ledger (and in the thread/timeout checks below).  mseq is
            # the chain position — the rank-comparable numbering the
            # exchange diff aligns on.
            _coll_mseq += 1
            entry["mseq"] = _coll_mseq
            if _coll_gen:
                # post-rebase entries carry their generation so the
                # exchanged tail can exclude pre-transition history
                # (entries without the key predate the first rebase)
                entry["gen"] = _coll_gen
            _coll_chain = hashlib.sha1(
                (_coll_chain + _coll_canon(entry)).encode()).hexdigest()
        _coll_ledger.append(entry)
        _stats["collective_dispatches"] += 1
    if _tel._enabled:
        _tel.counter("collective_dispatches", kind=kind)
        _tel.gauge("collective_ledger_seq", entry["seq"])
    if device and thread is not threading.main_thread():
        if _state().coll_ok:
            with _lock:
                _stats["collective_thread_allowed"] += 1
        else:
            _violation(
                "collective",
                "mxsan COLLECTIVE: device collective %s dispatched from "
                "thread '%s' — an off-main-thread device collective can "
                "interleave with in-flight training collectives and "
                "deadlock the world; use dist.coordination_barrier "
                "(service RPC, thread-safe) or scope a deliberately "
                "bounded probe with sanitize.allow_thread_collective"
                % (_fmt_entry(entry), thread.name))
    return entry


class _CollDispatch(object):
    """In-flight marker around a blocking collective: entered dispatches
    are what the MXNET_SAN_COLL_TIMEOUT watchdog watches."""

    __slots__ = ("entry",)

    def __init__(self, entry):
        self.entry = entry

    def __enter__(self):
        import time
        with _lock:
            _coll_inflight[threading.get_ident()] = (self.entry,
                                                     time.monotonic())
        return self

    def __exit__(self, *exc):
        with _lock:
            _coll_inflight.pop(threading.get_ident(), None)
            self.entry["done"] = True
        return False


def collective_dispatch(kind, name=None, sig=None, axes=None, device=True):
    """Note a collective dispatch AND mark it in flight for the dynamic
    extent of the ``with`` block (barrier waits, blocking allreduces).
    The shared no-op singleton while the checker is off."""
    if not _collective_on:
        return _NOOP
    return _CollDispatch(note_collective(kind, name=name, sig=sig,
                                         axes=axes, device=device))


class _AllowThreadCollective(object):
    __slots__ = ()

    def __enter__(self):
        _state().coll_ok += 1
        return self

    def __exit__(self, *exc):
        _state().coll_ok -= 1
        return False


def allow_thread_collective(reason):
    """Scoped escape hatch for a *deliberately* off-main-thread device
    collective.  Counted, never flagged; the reason documents the
    protocol the same way ``allow_sync`` does.  The repo itself has no
    remaining user — elastic ``health_check``, the one historical case,
    now rides ``dist.membership_barrier`` (service RPC, no device
    collective, no thread) — but the hatch stays for embedders whose
    bounded probes the THR002/collective checkers cannot know about."""
    if not _collective_on:
        return _NOOP
    return _AllowThreadCollective()


def ledger_tail(n=_COLL_TAIL):
    """The last ``n`` ledger entries (copies — safe to serialize)."""
    with _lock:
        return [dict(e) for e in list(_coll_ledger)[-n:]]


def collective_state():
    """Snapshot for diagnostics bundles: chain position, in-flight
    dispatches, exchange count."""
    import time
    with _lock:
        inflight = [{"thread": tid, "age_sec": time.monotonic() - t0,
                     "entry": dict(e)}
                    for tid, (e, t0) in _coll_inflight.items()]
        return {"seq": _coll_seq, "mseq": _coll_mseq,
                "chain": _coll_chain, "exchanges": _coll_xchg,
                "inflight": inflight}


def _coll_payload():
    """The exchanged summary: chain + the last MAIN-thread (chained)
    entries, keyed by their chain position ``mseq`` — the numbering that
    is comparable across ranks (global ledger seqs shift with
    rank-local side-thread dispatches)."""
    with _lock:
        chained = [e for e in _coll_ledger
                   if "mseq" in e and e.get("gen", 0) == _coll_gen]
        return {"seq": _coll_mseq, "chain": _coll_chain,
                "tail": [{"seq": e["mseq"], "kind": e["kind"],
                          "name": e["name"], "sig": e["sig"],
                          "axes": e["axes"]}
                         for e in chained[-_COLL_TAIL:]]}


def _divergence_message(point, n, rank, mine, peers):
    """None when every rank's hash chain agrees; else a message naming
    the first divergent ledger entry with a field diff against the
    majority.  Pure — unit-testable with seeded payloads."""
    chains = {rank: mine["chain"]}
    chains.update({r: p["chain"] for r, p in peers.items()})
    if len(set(chains.values())) == 1:
        return None
    by_chain = {}
    for r, c in sorted(chains.items()):
        by_chain.setdefault(c, []).append(r)
    majority_chain = max(by_chain,
                         key=lambda c: (len(by_chain[c]), by_chain[c]))
    majority = by_chain[majority_chain]
    minority = sorted(r for r in chains if r not in majority)
    # diff one minority rank against one majority rank, by seq
    all_payloads = dict(peers)
    all_payloads[rank] = mine
    a_rank = minority[0]
    b_rank = majority[0]
    a = {e["seq"]: e for e in all_payloads[a_rank]["tail"]}
    b = {e["seq"]: e for e in all_payloads[b_rank]["tail"]}
    head = ("mxsan COLLECTIVE: collective dispatch streams diverged at "
            "checkpoint '%s' (exchange %d): " % (point, n))
    a_min = min(a, default=0)
    b_min = min(b, default=0)
    for seq in sorted(set(a) | set(b)):
        ea, eb = a.get(seq), b.get(seq)
        if (ea is None and seq < a_min) or (eb is None and seq < b_min):
            # below the other tail's publish window: the entry slid out
            # of its 64-entry tail, which is NOT evidence that the rank
            # skipped it — only seqs past a rank's MAX mean it stopped.
            # Comparing here would blame whichever rank is merely ahead.
            continue
        if ea is None or eb is None:
            who, last = (a_rank, b_rank) if ea is None else (b_rank, a_rank)
            have = eb if ea is None else ea
            return head + (
                "rank %s dispatched nothing at seq %d where rank%s %s "
                "dispatched %s — rank %s stopped at seq %d"
                % (who, seq, "s" if len(by_chain[chains[who]]) > 1 else "",
                   last, _fmt_entry(have), who,
                   max(a if ea is None else b, default=0)))
        if ea != eb:
            fields = [k for k in ("kind", "name", "sig", "axes")
                      if ea.get(k) != eb.get(k)]
            return head + (
                "rank %s seq %d: %s where rank%s %s dispatched %s — "
                "field diff: %s"
                % (a_rank, seq, _fmt_entry(ea),
                   "s" if len(majority) > 1 else "",
                   ",".join(str(r) for r in majority), _fmt_entry(eb),
                   "; ".join("%s (%s -> %s)" % (k, _short(eb.get(k)),
                                                _short(ea.get(k)))
                             for k in fields)))
    return head + (
        "rank(s) %s hold chain %s.. against %s.. on rank(s) %s, but the "
        "divergence is older than the last %d published entries (local "
        "seq %d) — raise the exchange cadence or rerun from the start"
        % (",".join(str(r) for r in minority), chains[a_rank][:12],
           majority_chain[:12], ",".join(str(r) for r in majority),
           _COLL_TAIL, mine["seq"]))


def expect_recompile(marker):
    """Declare an upcoming LEGITIMATE recompile wave: every registered
    cache's warmup budget counts from this point, so the re-trace is not
    reported as an unstable key.  A live world resize
    (parallel/resize.py) is the canonical caller — the fused-fit cache
    is keyed on the world size on purpose (a program traced for the old
    mesh must never run on the new one), so every transition pays
    exactly the compile wave this budgets for.  Warm keys are KEPT: a
    second unexplained miss after the wave still diffs against the
    pre-transition keys.  Safe to call with the checker off."""
    import logging
    with _lock:
        for h in _CACHES:
            h._miss_anchor = h._misses
            h._warned = 0
    logging.getLogger(__name__).info(
        "mxsan: recompile budgets re-armed at %s", marker)
    # the live sentinel keys its warmup suppression off the same markers
    # (a declared re-trace wave must not read as a perf anomaly); lazy
    # and best-effort — sanitize must never depend on the sentinel
    try:
        from . import sentinel as _sentinel
        _sentinel.note_recompile(marker)
    except Exception:
        pass


def collective_rebase(marker):
    """Rebase the cross-rank verification state at a world membership
    transition (live resize — parallel/resize.py): the hash chain, chain
    position and exchange counter restart from a marker-derived seed.
    Every member of the NEW world — survivors and joiners alike — calls
    this with the SAME marker before its next exchange: a survivor's
    pre-transition history can never align with a freshly joined rank,
    so verification restarts AT the transition instead of reporting the
    membership change itself as a divergence (the rebuilt world's
    dispatch order is still verified from the seam onward).  The ledger
    is kept — pre-transition entries remain forensic evidence, a
    ``rebase`` row marks the seam — but stops feeding the exchanged
    tail.  No-op while the checker is off."""
    import hashlib
    global _coll_chain, _coll_mseq, _coll_xchg, _coll_seq, _coll_gen
    if not _collective_on:
        return
    with _lock:
        _coll_gen += 1
        _coll_chain = hashlib.sha1(
            ("rebase:%s" % (marker,)).encode()).hexdigest()
        _coll_mseq = 0
        _coll_xchg = 0
        _coll_seq += 1
        _coll_ledger.append({"seq": _coll_seq, "kind": "rebase",
                             "name": str(marker), "sig": None,
                             "axes": None, "gen": _coll_gen,
                             "thread": threading.current_thread().name})


def _coord_client():
    # ONE owner for the fragile jax-internal lookup:
    # parallel.dist.coordination_client (coordination_barrier rides the
    # same helper, so a jax upgrade that moves the client breaks both
    # loudly together instead of silently disabling one)
    try:
        from .parallel import dist as _dist
        return _dist.coordination_client()
    except Exception:
        return None


def _coord_world(client):
    """``(world, rank)`` for the hash-chain exchange: the device
    backend's world when it is multi-process, else — the
    coordination-only coupling a live resize runs in — the MXTPU env
    contract, provided a client is actually connected.  Mirrors
    ``dist.peer_world`` without re-entering dist (whose idempotence
    latch may be mid-transition during a resize)."""
    import jax
    if jax.process_count() > 1:
        return jax.process_count(), jax.process_index()
    if client is not None:
        try:
            from . import checkpoint as _ckpt
            return _ckpt._world(), _ckpt._rank()
        except Exception:
            return 1, 0
    return 1, 0


def collective_sync(point, timeout_s=None):
    """Exchange the rolling hash chain with every peer rank through the
    coordination service (key-value RPC — no device collectives, safe
    from any thread) and name the first divergent dispatch on mismatch.
    Called at every barrier entry (``dist.barrier`` /
    ``coordination_barrier``) and at each fit epoch boundary; every rank
    must reach the same exchange points in the same order — which is
    exactly the property being verified, so a missing peer is itself a
    named finding (with this rank's ledger position) instead of a hang.
    No-op single-process and while the checker is off."""
    global _coll_xchg, _coll_client_warned
    if not _collective_on:
        return
    if threading.current_thread() is not threading.main_thread():
        # exchanges must hit the same points in the same ORDER on every
        # rank; a side thread (the async checkpoint writer at its ckpt
        # barrier) interleaves nondeterministically with the main
        # thread's exchanges, so it would desync the exchange counter
        # and report false divergence.  Its dispatches stay visible in
        # the ledger; the main thread's next exchange carries the chain.
        return
    import json
    client = _coord_client()
    world, rank = _coord_world(client)
    if world <= 1:
        return
    if client is None:
        with _lock:
            warned, _coll_client_warned = _coll_client_warned, True
        if not warned:
            warnings.warn(
                "mxsan COLLECTIVE: jax's coordination-service client is "
                "unavailable in this jax version; hash-chain exchange "
                "disabled (the ledger, thread and timeout checks still "
                "run)", SanitizerWarning)
        return
    if timeout_s is None:
        timeout_s = _COLL_SYNC_DEFAULT
    with _lock:
        _coll_xchg += 1
        n = _coll_xchg
    # one encode: the published bytes, re-decoded for the local copy so
    # the entry diff compares like with like (peers arrive JSON-decoded;
    # tuples become lists)
    raw = json.dumps(_coll_payload(), separators=(",", ":"))
    mine = json.loads(raw)
    try:
        client.key_value_set("mxsan-coll/%d/%d" % (n, rank), raw)
        if n > 2:
            # reclaim this rank's exchange-(n-2) key: every peer that
            # published n-1 (a prerequisite for anyone reaching n) had
            # already finished reading the n-2 round, so the delete can
            # never race a blocking get — without it a long fleet run
            # grows the coordinator's KV store without bound
            try:
                client.key_value_delete("mxsan-coll/%d/%d"
                                        % (n - 2, rank))
            except Exception:
                pass
    except Exception as e:
        _violation("collective",
                   "mxsan COLLECTIVE: hash-chain publish failed at "
                   "checkpoint '%s' (exchange %d): %s" % (point, n, e),
                   raise_ok=False)
        return
    import time
    peers, missing = {}, []
    # ONE deadline across every peer read: k dead ranks must cost one
    # timeout total, not k sequential timeouts (each surviving rank
    # would otherwise sit k*timeout inside the barrier's pre-wait
    # exchange while the stall watchdog fires on the enclosing dispatch)
    deadline = time.monotonic() + timeout_s
    for r in range(world):
        if r == rank:
            continue
        left_ms = max(1, int((deadline - time.monotonic()) * 1000))
        try:
            raw = client.blocking_key_value_get(
                "mxsan-coll/%d/%d" % (n, r), left_ms)
            peers[r] = json.loads(raw)
        except Exception:
            missing.append(r)
    if missing:
        last = ledger_tail(3)
        _violation(
            "collective",
            "mxsan COLLECTIVE: rank(s) %s never reached collective "
            "checkpoint '%s' (exchange %d) within %.0fs — suspected "
            "divergence or deadlock; this rank (%d) is at ledger seq %d"
            "%s" % (",".join(str(r) for r in missing), point, n,
                    timeout_s, rank, mine["seq"],
                    (", last dispatches: "
                     + "; ".join(_fmt_entry(e) for e in last))
                    if last else ""))
        return
    msg = _divergence_message(point, n, rank, mine, peers)
    if msg is not None:
        _violation("collective", msg)


# ---------------------------------------------- collective dispatch watchdog
def _coll_watch_loop(stop, budget_s):
    """Daemon watcher (the diagnostics armed-thread idiom): a dispatch
    still in flight past the budget writes ONE diagnostics bundle with
    the ledger tail — the post-mortem a hung fleet leaves behind."""
    import sys as _sys
    import time
    poll = min(1.0, budget_s / 4.0)
    while not stop.wait(poll):
        try:
            now = time.monotonic()
            overdue = []
            with _lock:
                for tid, (entry, t0) in _coll_inflight.items():
                    if now - t0 >= budget_s \
                            and entry["seq"] not in _coll_stalled:
                        _coll_stalled.add(entry["seq"])
                        overdue.append((tid, entry, now - t0))
            for tid, entry, age in overdue:
                from . import diagnostics as _diag
                path = _diag.write_snapshot(
                    "collective_stall",
                    extra={"collective_stall":
                           {"entry": dict(entry), "age_sec": age,
                            "timeout_sec": budget_s,
                            "thread_ident": tid},
                           "collective": collective_state(),
                           "collective_ledger": ledger_tail()})
                _sys.stderr.write(
                    "mxsan COLLECTIVE: dispatch %s in flight for %.1fs "
                    "(budget %.1fs) — suspected collective deadlock%s\n"
                    % (_fmt_entry(entry), age, budget_s,
                       "; ledger dumped to %s" % path if path else ""))
                _sys.stderr.flush()
                if _tel._enabled:
                    _tel.counter("collective_stalls")
        except Exception as e:   # a dump error must not kill the watch
            try:
                _sys.stderr.write(
                    "mxsan COLLECTIVE: watchdog dump failed (%s)\n" % e)
            except Exception:
                pass


def _start_coll_watchdog():
    """Armed only when the collective checker is on AND
    MXNET_SAN_COLL_TIMEOUT is set — plain ``MXNET_SAN=collective``
    starts no thread (import-hygiene contract)."""
    global _coll_watch_thread, _coll_watch_stop
    budget = get_env("MXNET_SAN_COLL_TIMEOUT", None, typ=float)
    if not budget or budget <= 0:
        return
    _coll_watch_stop = threading.Event()
    _coll_watch_thread = threading.Thread(
        target=_coll_watch_loop, args=(_coll_watch_stop, float(budget)),
        name="mxsan-coll-watchdog", daemon=True)
    _coll_watch_thread.start()


def _stop_coll_watchdog():
    global _coll_watch_thread, _coll_watch_stop
    stop, t = _coll_watch_stop, _coll_watch_thread
    _coll_watch_thread = None
    _coll_watch_stop = None
    if stop is not None:
        stop.set()
    if t is not None and t.is_alive():
        t.join(timeout=5.0)


# -------------------------------------------------------------- sync hooks
def _install_hooks():
    """Patch the Python-level sync/read choke points.  Installed only on
    arm, restored exactly on disarm; wrappers delegate unconditionally
    when execution is outside a hot region."""
    import jax

    def _patch(obj, attr, make):
        orig = getattr(obj, attr)
        try:
            setattr(obj, attr, make(orig))
        except (AttributeError, TypeError):
            return                   # unpatchable on this jax version
        _patches.append((obj, attr, orig))

    def _donate_guard(args):
        if not _donate_on or not args:
            return
        a0 = args[0]
        if hasattr(a0, "dtype"):
            leaves = (a0,)
        elif isinstance(a0, (dict, list, tuple)):
            # device_get/block_until_ready take whole pytrees (the repo's
            # own idiom passes dicts/lists) — check every leaf
            import jax
            leaves = jax.tree_util.tree_leaves(a0)
        else:
            return
        for a in leaves:
            ent = donated_entry(a) if hasattr(a, "dtype") else None
            if ent is not None:
                label, where, step = ent
                _violation(
                    "donate",
                    "mxsan DONATE: read of donated buffer %s (donated by "
                    "%s%s) — this raises XLA's 'Array has been deleted' "
                    "on a real accelerator" % (
                        label, where,
                        "" if step is None else " at num_update=%s" % step))

    def wrap_fn(what):
        def make(orig):
            def wrapper(*args, **kwargs):
                _donate_guard(args[:1])
                _sync_event(what)
                return orig(*args, **kwargs)
            wrapper.__name__ = getattr(orig, "__name__", what)
            wrapper._mxsan_orig = orig
            return wrapper
        return make

    def wrap_method(what):
        def make(orig):
            def wrapper(self, *args, **kwargs):
                _donate_guard((self,))
                _sync_event(what)
                return orig(self, *args, **kwargs)
            wrapper.__name__ = getattr(orig, "__name__", what)
            wrapper._mxsan_orig = orig
            return wrapper
        return make

    _patch(jax, "device_get", wrap_fn("jax.device_get"))
    _patch(jax, "block_until_ready", wrap_fn("jax.block_until_ready"))
    try:
        from jax._src.array import ArrayImpl
    except ImportError:
        return
    for attr, what in (("item", ".item()"), ("__float__", "float()"),
                       ("__int__", "int()"), ("__bool__", "bool()"),
                       ("__array__", "np.asarray()")):
        _patch(ArrayImpl, attr, wrap_method(what))


def _remove_hooks():
    while _patches:
        obj, attr, orig = _patches.pop()
        try:
            setattr(obj, attr, orig)
        except (AttributeError, TypeError):
            pass


# -------------------------------------------------------------- arm/disarm
def _parse_spec(raw):
    raw = raw.strip()
    mode = "warn"
    if raw.endswith(":raise"):
        mode, raw = "raise", raw[:-len(":raise")]
    elif raw.endswith(":warn"):
        raw = raw[:-len(":warn")]
    checkers = set()
    for tok in raw.split(","):
        tok = tok.strip()
        if not tok:
            continue
        if tok == "all":
            checkers.update(CHECKERS)
        elif tok in CHECKERS:
            checkers.add(tok)
        else:
            raise MXNetError(
                "MXNET_SAN: unknown checker %r (want a comma list of %s, "
                "optionally ending in ':raise')" % (tok, "/".join(CHECKERS)))
    return checkers, mode


def arm(checkers="all", mode=None):
    """Arm the sanitizer.  ``checkers`` is an iterable or a comma string
    (``"recompile,sync"``; may carry a trailing ``:raise``); ``mode`` is
    ``"warn"`` (default) or ``"raise"``.  Idempotent per configuration;
    warmup budgets count from the moment of arming."""
    global _armed, _mode, _recompile_on, _sync_on, _donate_on, \
        _collective_on
    if isinstance(checkers, str):
        parsed, spec_mode = _parse_spec(checkers)
    else:
        parsed, spec_mode = set(checkers), "warn"
        bad = parsed - set(CHECKERS)
        if bad:
            raise MXNetError("MXNET_SAN: unknown checker(s) %s"
                             % sorted(bad))
    mode = mode or spec_mode
    if mode not in ("warn", "raise"):
        raise MXNetError("sanitize.arm: mode must be 'warn' or 'raise'")
    # the handler/patch installs happen UNDER the arm lock: concurrent
    # arm() calls would otherwise double-install and disarm() would then
    # leak one handler forever (none of the installs re-enter it)
    with _arm_lock:
        disarm()
        if not parsed:
            return False
        with _lock:
            _armed = frozenset(parsed)
            _mode = mode
            _recompile_on = "recompile" in _armed
            _sync_on = "sync" in _armed
            _donate_on = "donate" in _armed
            _collective_on = "collective" in _armed
            for h in _CACHES:
                h._miss_anchor = h._misses  # budgets count from arming
                h._warned = 0
        if _recompile_on:
            _attach_compile_log()
        if _sync_on or _donate_on:
            _install_hooks()
        if _collective_on:
            _start_coll_watchdog()
    return True


def disarm():
    """Restore every patched function / handler and return to the
    strict-no-op state.  Registered caches, their warm keys and the
    stats survive (the registry also feeds the jit_cache_size gauge)."""
    global _armed, _mode, _recompile_on, _sync_on, _donate_on, \
        _collective_on
    with _arm_lock:
        with _lock:
            _armed = frozenset()
            _recompile_on = _sync_on = _donate_on = _collective_on = False
            _mode = "warn"
            _coll_inflight.clear()
        _detach_compile_log()
        _remove_hooks()
        _stop_coll_watchdog()


def armed():
    """The armed checker set (empty frozenset when off)."""
    return _armed


def stats():
    """Copy of the violation/usage counters."""
    with _lock:
        return dict(_stats)


def violations():
    """The most recent violation messages (bounded)."""
    with _lock:
        return list(_violations)


def reset():
    """Zero the stats, violation log, donated-buffer registry, raw-jit
    counts, the collective ledger/hash chain and every cache's miss
    anchor (test isolation)."""
    global _coll_seq, _coll_mseq, _coll_chain, _coll_xchg, \
        _coll_client_warned, _coll_gen
    with _lock:
        for k in _stats:
            _stats[k] = 0
        _violations.clear()
        _wire_bytes.clear()
        _hbm_ledger.clear()
        _cost_ledger.clear()
        _DONATED.clear()
        _RAW_COMPILES.clear()
        _coll_ledger.clear()
        _coll_inflight.clear()
        _coll_stalled.clear()
        _coll_seq = 0
        _coll_mseq = 0
        _coll_chain = "0" * 40
        _coll_xchg = 0
        _coll_gen = 0
        _coll_client_warned = False
        for h in _CACHES:
            h._miss_anchor = h._misses
            h._warned = 0
            h._compile_s = 0.0


# ------------------------------------------------- autostart (env contract)
def _autostart():
    """``MXNET_SAN=recompile,sync,donate[:raise]`` arms the sanitizer at
    import time.  A malformed value degrades to disabled-with-a-warning
    rather than failing the import; unset is a strict no-op."""
    raw = get_env("MXNET_SAN")
    if not raw:
        return False
    try:
        checkers, mode = _parse_spec(raw)
    except MXNetError as e:
        warnings.warn("MXNET_SAN=%r: %s; sanitizer disabled" % (raw, e))
        return False
    if not checkers:
        return False
    return arm(checkers, mode)


_autostart()
