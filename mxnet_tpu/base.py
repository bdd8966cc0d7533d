"""Base utilities for the TPU-native MXNet rebuild.

Replaces the reference's ctypes plumbing (reference: python/mxnet/base.py) and the
dmlc-core slice (logging/CHECK, registry, env config).  There is no C-API marshalling
layer here because the compute substrate is JAX/XLA reached directly from Python; the
native runtime (engine / IO) is bound through :mod:`mxnet_tpu.lib` instead.
"""
from __future__ import annotations

import os
import threading

__all__ = ["MXNetError", "string_types", "numeric_types", "get_env", "check",
           "Registry", "classproperty", "TRACE_ENV_DEFAULTS", "trace_env_key",
           "atomic_write", "COMPILE_CACHE_DIR", "enable_compile_cache"]

string_types = (str,)
numeric_types = (float, int)


class MXNetError(Exception):
    """Error raised by mxnet_tpu (parity: reference python/mxnet/base.py:MXNetError)."""


def check(cond, msg="check failed"):
    """CHECK-style assertion (parity: dmlc-core CHECK macros)."""
    if not cond:
        raise MXNetError(msg)


def get_env(name, default=None, typ=None):
    """Read a runtime env var (parity: dmlc::GetEnv, docs/how_to/env_var.md)."""
    val = os.environ.get(name)
    if val is None:
        return default
    if typ is bool:
        return val not in ("0", "false", "False", "")
    if typ is not None:
        return typ(val)
    return val


# Env flags whose value is consulted while a computation is being traced.
# Every jit dispatch cache keys on trace_env_key() so toggling one of these
# between calls retraces instead of silently reusing a program compiled
# under the old value.  A row here is the one contract for reading a
# variable at trace time; mxlint's JIT001 rule polices reads that bypass it.
# Kept on purpose: the other values of MXNET_CONV_LAYOUT and MXNET_STEM_FUSE
# are the logical-NCHW and unfused lowerings, the references that
# test_layout.py and test_stem_fuse.py hold the default against in float64;
# MXNET_MONITOR is a feature.  A lever that loses its A/B leaves with its
# row.
TRACE_ENV_DEFAULTS = (
    ("MXNET_CONV_LAYOUT", "NHWC"),
    ("MXNET_STEM_FUSE", "1"),
    # numerics monitor: the spec decides whether the fused step traces
    # the auxiliary stats pytree, so it must retrace on toggle
    ("MXNET_MONITOR", ""),
)


def trace_env_key():
    """Snapshot of the trace-affecting env flags, for jit cache keys."""
    return tuple(get_env(n, d) for n, d in TRACE_ENV_DEFAULTS)


# The persistent XLA compile cache's home when nobody places it from
# outside: one fixed directory at the root of the checkout (listed in
# .gitignore).  Fixed, because a path built from tempfile, a pid or the
# clock is a cache that never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  Entry points that compile (chip_smoke.py,
    bench.py, the examples, the measuring tools) call this once before
    their first program; the library never does so on import.

    The directory is ``JAX_COMPILATION_CACHE_DIR`` where that is set — jax
    reads it itself and no code names another — and ``COMPILE_CACHE_DIR``
    otherwise.  Every program is kept, not only those over jax's one-second
    default: a machine that starts cold on every run otherwise recompiles
    the many small programs (initializers, copies, metrics) each time."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


class atomic_write(object):
    """Crash-consistent local file write: bytes land in a same-directory
    temp file, are flushed + fsynced, then atomically renamed over the
    target — a process killed mid-write leaves the previous file intact
    and never exposes a truncated one (the checkpoint durability
    contract, docs/elastic.md).  Context manager yielding the open file;
    on error the temp file is removed and the target untouched."""

    def __init__(self, fname, mode="wb", fsync=True):
        self.fname = str(fname)
        self.tmp = "%s.tmp-%d" % (self.fname, os.getpid())
        self.mode = mode
        self.fsync = fsync
        self._f = None

    def __enter__(self):
        self._f = open(self.tmp, self.mode)
        return self._f

    def __exit__(self, exc_type, exc, tb):
        try:
            try:
                if exc_type is None:
                    self._f.flush()
                    if self.fsync:
                        os.fsync(self._f.fileno())
            finally:
                # close unconditionally: a failed fsync (ENOSPC) must not
                # leak the descriptor — full-disk checkpointing retries
                # would otherwise march the process to EMFILE
                self._f.close()
            if exc_type is None:
                os.replace(self.tmp, self.fname)
                # the rename itself lives in the directory: without a
                # dir fsync a power cut can drop the entry even though
                # the save reported success (the durability half of the
                # crash-consistency contract)
                d = os.path.dirname(self.fname) or "."
                try:
                    fd = os.open(d, os.O_RDONLY)
                    try:
                        os.fsync(fd)
                    finally:
                        os.close(fd)
                except OSError:
                    pass   # platform without directory fsync
                return False
        finally:
            if os.path.exists(self.tmp):
                try:
                    os.remove(self.tmp)
                except OSError:
                    pass
        return False


def smart_open(uri, mode="rb"):
    """Open a local path or a remote URI (parity: dmlc::Stream with
    USE_S3/USE_HDFS, reference make/config.mk:136-144 — the reference's
    RecordIO/params files can live on s3:// or hdfs://).  Remote schemes
    route through fsspec, which resolves s3/gs/hdfs/http drivers at
    runtime; local paths use plain open()."""
    if "://" in str(uri):
        try:
            import fsspec
        except ImportError:
            raise MXNetError(
                "remote URI %r requires fsspec (the dmlc::Stream S3/HDFS "
                "equivalent)" % (uri,))
        return fsspec.open(uri, mode).open()
    return open(uri, mode)


class Registry(object):
    """Generic name->entry registry (parity: dmlc registry used for ops/iters/metrics)."""

    def __init__(self, kind):
        self.kind = kind
        self._entries = {}
        self._lock = threading.Lock()

    def register(self, name, entry, override=False):
        with self._lock:
            if name in self._entries and not override:
                raise MXNetError("%s '%s' already registered" % (self.kind, name))
            self._entries[name] = entry
        return entry

    def get(self, name):
        try:
            return self._entries[name]
        except KeyError:
            raise MXNetError("unknown %s: %s" % (self.kind, name))

    def find(self, name):
        return self._entries.get(name)

    def __contains__(self, name):
        return name in self._entries

    def list_names(self):
        return sorted(self._entries)


class classproperty(object):
    def __init__(self, f):
        self.f = f

    def __get__(self, obj, owner):
        return self.f(owner)
