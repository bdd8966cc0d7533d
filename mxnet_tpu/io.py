"""Data iterators (parity: reference python/mxnet/io.py + src/io C++ iterators;
SURVEY.md §2.7).

The iterator protocol (provide_data/provide_label, DataBatch with pad/index,
reset/next) is identical to the reference.  MNIST/CSV parse with numpy; the
RecordIO image pipeline lives in mxnet_tpu/recordio.py + image.py; host→HBM
staging happens when the Module slices batches onto devices.
"""
from __future__ import annotations

import gzip
import os
import struct
import threading
import time
from collections import namedtuple

import numpy as np

from .base import MXNetError
from . import ndarray as nd
from .ndarray import NDArray
from . import telemetry as _tel

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter", "MNISTIter",
           "CSVIter", "ResizeIter", "PrefetchingIter", "DevicePrefetchIter",
           "device_prefetch_depth"]


def _count_batch(it):
    """Telemetry hook shared by every ``DataIter.next`` implementation —
    iterators that build batches without going through the base ``next()``
    (image/record/bucketing pipelines) call this before returning."""
    if _tel._enabled:
        _tel.counter("io_batches", iter=type(it).__name__)


class DataDesc(namedtuple("DataDesc", ["name", "shape"])):
    """Name+shape descriptor (parity: io.DataDesc; dtype carried separately)."""

    def __new__(cls, name, shape, dtype=np.float32, layout="NCHW"):
        ret = super().__new__(cls, name, shape)
        ret.dtype = dtype
        ret.layout = layout
        return ret

    @staticmethod
    def get_batch_axis(layout):
        return 0 if layout is None else layout.find("N")


class DataBatch(object):
    """One mini-batch (parity: io.DataBatch)."""

    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter(object):
    """Iterator base (parity: io.DataIter)."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            batch = DataBatch(data=self.getdata(), label=self.getlabel(),
                              pad=self.getpad(), index=self.getindex())
            # counted after materialization: a getdata() that raises on a
            # malformed row must not report a batch that never existed
            _count_batch(self)
            return batch
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError()

    def getdata(self):
        raise NotImplementedError()

    def getlabel(self):
        raise NotImplementedError()

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError()


def _init_data(data, allow_empty, default_name):
    """Normalize input data into an ordered list of (name, numpy) pairs."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {"_%d_%s" % (i, default_name): d
                    for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, a list of them "
                        "or dict with them as values")
    out = []
    for k, v in data.items():
        if isinstance(v, NDArray):
            v = v.asnumpy()
        out.append((k, np.ascontiguousarray(np.asarray(v))))
    return out


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays.

    TPU-first design: instead of walking a cursor through the arrays, each
    epoch is a precomputed *gather schedule* — a list of ``(indices, pad)``
    batches built once per reset.  Every batch is then a single fancy-index
    gather (one XLA-friendly contiguous copy), padding wraps indices to the
    epoch start, and ``roll_over`` carries the unscheduled tail into the next
    epoch's first batch.  Capability parity with reference io.NDArrayIter
    (python/mxnet/io.py); mechanism is original.
    """

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True,
                                default_name=label_name)
        self.num_data = self.data[0][1].shape[0]
        for k, v in self.data + self.label:
            if v.shape[0] != self.num_data:
                raise MXNetError("source %s has %d rows, expected %d"
                                 % (k, v.shape[0], self.num_data))
        if self.num_data < batch_size:
            raise MXNetError("batch_size needs to be smaller than data size.")
        self.shuffle = shuffle
        self.last_batch_handle = last_batch_handle
        self._rng = np.random
        self._carry = np.array([], dtype=np.int64)  # roll_over tail
        self._schedule = []
        self._pos = 0
        self._build_schedule()

    # ------------------------------------------------------------- scheduling
    def _build_schedule(self):
        order = np.arange(self.num_data, dtype=np.int64)
        if self.shuffle:
            order = self._rng.permutation(self.num_data).astype(np.int64)
        if self.last_batch_handle == "roll_over" and self._carry.size:
            order = np.concatenate([self._carry, order])
            self._carry = np.array([], dtype=np.int64)
        b = self.batch_size
        n_full = order.size // b
        batches = [(order[i * b:(i + 1) * b], 0) for i in range(n_full)]
        tail = order[n_full * b:]
        if tail.size:
            if self.last_batch_handle == "pad":
                # wrap to the epoch start, report the wrapped count as pad
                fill = order[:b - tail.size]
                batches.append((np.concatenate([tail, fill]), b - tail.size))
            elif self.last_batch_handle == "roll_over":
                self._carry = tail  # becomes the head of the next epoch
            # "discard": drop the tail
        self._schedule = batches
        self._pos = 0

    @property
    def provide_data(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.label]

    def hard_reset(self):
        self._carry = np.array([], dtype=np.int64)
        self._build_schedule()

    def reset(self):
        self._build_schedule()

    def iter_next(self):
        if self._pos >= len(self._schedule):
            return False
        self._pos += 1
        return True

    def _current(self):
        if not 0 < self._pos <= len(self._schedule):
            raise MXNetError("DataIter needs reset.")
        return self._schedule[self._pos - 1]

    def getdata(self):
        idx, _ = self._current()
        return [nd.array(v[idx]) for _, v in self.data]

    def getlabel(self):
        idx, _ = self._current()
        return [nd.array(v[idx]) for _, v in self.label]

    def getpad(self):
        return self._current()[1]


class MNISTIter(DataIter):
    """MNIST idx-format reader (parity: src/io/iter_mnist.cc:61-241)."""

    def __init__(self, image, label, batch_size=128, shuffle=True, flat=False,
                 seed=0, silent=False, num_parts=1, part_index=0,
                 input_shape=None, **_):
        super().__init__(batch_size)
        imgs = self._read_idx(image)
        labs = self._read_idx(label)
        assert imgs.shape[0] == labs.shape[0]
        if shuffle:
            rng = np.random.RandomState(seed)
            idx = rng.permutation(imgs.shape[0])
            imgs, labs = imgs[idx], labs[idx]
        if num_parts > 1:  # data-parallel partitioning
            n = imgs.shape[0] // num_parts
            imgs = imgs[part_index * n:(part_index + 1) * n]
            labs = labs[part_index * n:(part_index + 1) * n]
        imgs = imgs.astype(np.float32) / 255.0
        if flat:
            imgs = imgs.reshape(imgs.shape[0], -1)
        else:
            imgs = imgs.reshape(imgs.shape[0], 1, imgs.shape[1], imgs.shape[2])
        if input_shape is not None:
            imgs = imgs.reshape((imgs.shape[0],) + tuple(input_shape))
        self._inner = NDArrayIter(imgs, labs.astype(np.float32),
                                  batch_size=batch_size,
                                  last_batch_handle="discard")

    @staticmethod
    def _read_idx(path):
        opener = gzip.open if path.endswith(".gz") else open
        if not os.path.exists(path) and os.path.exists(path + ".gz"):
            path, opener = path + ".gz", gzip.open
        with opener(path, "rb") as f:
            data = f.read()
        magic = struct.unpack(">I", data[:4])[0]
        ndim = magic & 0xFF
        dims = struct.unpack(">" + "I" * ndim, data[4:4 + 4 * ndim])
        arr = np.frombuffer(data, dtype=np.uint8, offset=4 + 4 * ndim)
        return arr.reshape(dims)

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()

    def iter_next(self):
        return self._inner.iter_next()


class CSVIter(DataIter):
    """CSV reader (parity: src/io/iter_csv.cc)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, **_):
        super().__init__(batch_size)
        data = np.loadtxt(data_csv, delimiter=",", dtype=np.float32, ndmin=2)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=",", dtype=np.float32,
                               ndmin=2)
            label = label.reshape((-1,) + tuple(label_shape))
            if label_shape == (1,):
                label = label.reshape(-1)
        else:
            label = np.zeros((data.shape[0],), dtype=np.float32)
        self._inner = NDArrayIter(
            data, label, batch_size=batch_size,
            last_batch_handle="pad" if round_batch else "discard",
            label_name="label")

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()


class ResizeIter(DataIter):
    """Resize an iterator to a fixed number of batches per epoch (parity:
    io.ResizeIter)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class PrefetchingIter(DataIter):
    """Bounded-queue staging prefetcher.

    TPU-first design (capability parity with reference io.PrefetchingIter /
    src/io/iter_prefetcher.h; mechanism is original): one producer thread per
    child iterator feeds a bounded ``queue.Queue`` of depth ``prefetch_depth``.
    The producer optionally *stages batches into device HBM* (``ctx=`` →
    ``jax.device_put``) while the accelerator is busy with the previous step,
    so the host→HBM copy overlaps compute — the role the reference fills with
    a pinned-memory dmlc::ThreadedIter.  Epoch end is a sentinel in the queue,
    so there is no event/flag handshake to get wrong.
    """

    _STOP = object()   # epoch-end sentinel

    class _Raised(object):
        """Producer-side exception forwarded through the queue."""

        def __init__(self, exc):
            self.exc = exc

    def __init__(self, iters, rename_data=None, rename_label=None,
                 prefetch_depth=2, ctx=None):
        super().__init__()
        self.iters = iters if isinstance(iters, list) else [iters]
        assert self.iters, "need at least one child iterator"
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.prefetch_depth = max(1, prefetch_depth)
        self._ctx = ctx
        self.batch_size = self.provide_data[0][1][0]
        self.current_batch = None
        self._queues = None
        self._threads = []
        self._alive = False
        self._exhausted = False
        self._start_epoch()

    # ---------------------------------------------------------------- workers
    def _stage(self, arrays):
        """Move a list of NDArrays toward the device ahead of consumption."""
        if self._ctx is None:
            return arrays
        return [a.copyto(self._ctx) if a.context != self._ctx else a
                for a in arrays]

    def _producer(self, child, q):
        while True:
            try:
                b = child.next()
                b.data = self._stage(b.data)
                if b.label is not None:
                    b.label = self._stage(b.label)
            except StopIteration:
                q.put(self._STOP)
                return
            except Exception as exc:  # forward to the consumer, don't vanish
                q.put(self._Raised(exc))
                return
            q.put(b)
            if not self._alive:
                return

    def _start_epoch(self):
        import queue as _queue
        self._drain()
        self._alive = True
        self._exhausted = False
        self._queues = [_queue.Queue(maxsize=self.prefetch_depth)
                        for _ in self.iters]
        self._threads = [threading.Thread(target=self._producer, args=(c, q),
                                          daemon=True)
                         for c, q in zip(self.iters, self._queues)]
        for t in self._threads:
            t.start()

    def _drain(self):
        """Stop current producers and empty their queues."""
        self._alive = False
        if self._queues:
            for q, t in zip(self._queues, self._threads):
                while t.is_alive():
                    try:
                        q.get(timeout=0.01)
                    except Exception:
                        pass
                t.join()
        self._queues = None
        self._threads = []

    # -------------------------------------------------------------- protocol
    @property
    def provide_data(self):
        descs = []
        for i, child in enumerate(self.iters):
            ren = self.rename_data[i] if self.rename_data else {}
            for x in child.provide_data:
                d = x if isinstance(x, DataDesc) else DataDesc(*x)
                # keep the child's layout: consumers locate the batch axis
                # through it (time-major iterators put batch on axis 1)
                descs.append(DataDesc(ren.get(d.name, d.name), d.shape,
                                      d.dtype,
                                      getattr(d, "layout", "NCHW")))
        return descs

    @property
    def provide_label(self):
        descs = []
        for i, child in enumerate(self.iters):
            ren = self.rename_label[i] if self.rename_label else {}
            for x in child.provide_label:
                d = x if isinstance(x, DataDesc) else DataDesc(*x)
                descs.append(DataDesc(ren.get(d.name, d.name), d.shape,
                                      d.dtype,
                                      getattr(d, "layout", "NCHW")))
        return descs

    def reset(self):
        self._drain()  # stop producers before touching the children
        for child in self.iters:
            child.reset()
        self._start_epoch()

    def iter_next(self):
        if self._exhausted:
            return False
        telem = _tel._enabled
        if telem:
            # time blocked-on-producer separately: a non-trivial queue wait
            # means the pipeline is input-bound despite the prefetch depth
            wall = time.time()
            t0 = time.perf_counter()
            parts = [q.get() for q in self._queues]
            wait = time.perf_counter() - t0
        else:
            parts = [q.get() for q in self._queues]
        if telem and not any(p is self._STOP or isinstance(p, self._Raised)
                             for p in parts):
            # only real batches count — the end-of-epoch sentinel fetch
            # measures producer teardown, not input wait
            _tel.record_span("io.queue_wait", wall, wait, cat="io")
        for p in parts:
            if isinstance(p, self._Raised):
                self._exhausted = True
                raise p.exc
        done = [p is self._STOP for p in parts]
        if any(done):
            self._exhausted = True
            if not all(done):
                raise MXNetError(
                    "child iterators ended at different batch counts")
            return False
        pad0 = parts[0].pad
        if any(p.pad != pad0 for p in parts):
            raise MXNetError("child iterators disagree on pad")
        self.current_batch = DataBatch(
            sum([p.data for p in parts], []),
            sum([p.label for p in parts], []),
            pad0, parts[0].index)
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad

    def __del__(self):
        try:
            self._drain()  # unblock producers stuck in q.put, release batches
        except Exception:
            pass


def device_prefetch_depth():
    """Device-prefetch staging depth from ``MXNET_DEVICE_PREFETCH``:
    unset/``1`` -> 2 (double buffering, the default), ``0`` -> 0
    (disabled), ``N >= 2`` -> depth N.  Read at dispatch time (when a fit
    epoch or a bench staging loop starts), never under trace."""
    from .base import get_env
    raw = get_env("MXNET_DEVICE_PREFETCH", "1")
    try:
        n = int(raw)
    except (TypeError, ValueError):
        raise MXNetError("MXNET_DEVICE_PREFETCH=%r: expected 0 (off), 1 "
                         "(double buffering) or a queue depth >= 2" % raw)
    if n <= 0:
        return 0
    return max(2, n)


class DevicePrefetchIter(object):
    """Depth-2 (default) *device-side* staging pipeline.

    ``PrefetchingIter`` overlaps host-side batch PRODUCTION with compute;
    the host->HBM transfer itself still happens synchronously when the
    step is dispatched.  This wrapper closes that gap — the TPU-native
    replacement for the reference's pinned-memory ``dmlc::ThreadedIter``
    (src/io/iter_prefetcher.h): a daemon producer thread pulls items from
    ``source`` and calls ``stage`` on each, ISSUING the sharded
    ``jax.device_put`` for batch N+1 while the consumer computes step N,
    through a bounded queue of ``depth`` staged batches.

    ``stage`` owns the placement (it receives whatever ``source`` yields
    and its return value is what ``next()`` hands back): the fused fit
    driver stages ``DataBatch`` dicts onto the TrainStep's device/sharding
    (module/_FusedFit), bench.py stages host arrays with
    ``TrainStep.shard_batch``.  Staging runs on the producer thread, so a
    ``stage`` that blocks on the transfer still overlaps compute.

    Exceptions in ``source``/``stage`` are forwarded to the consumer;
    exhaustion is a queue sentinel (same discipline as PrefetchingIter).
    One epoch per instance — wrap the epoch's iterator, drain falls out
    at StopIteration or garbage collection.
    """

    _STOP = object()

    class _Raised(object):
        def __init__(self, exc):
            self.exc = exc

    def __init__(self, source, stage=None, depth=2):
        import queue as _queue
        self._source = iter(source)
        self._stage = stage if stage is not None else (lambda b: b)
        self._queue = _queue.Queue(maxsize=max(1, int(depth)))
        self._alive = True
        self._exhausted = False
        self._thread = threading.Thread(target=self._producer, daemon=True)
        self._thread.start()

    def _producer(self):
        while True:
            try:
                with _tel.span("input.source_next", cat="io"):
                    item = next(self._source)
                with _tel.span("input.stage", cat="io"):
                    item = self._stage(item)
            except StopIteration:
                self._queue.put(self._STOP)
                return
            except Exception as exc:   # forward, don't vanish
                self._queue.put(self._Raised(exc))
                return
            # blocked here = the consumer is the slower side (queue full)
            with _tel.span("input.put", cat="io"):
                self._queue.put(item)
            if not self._alive:
                return

    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration
        item = self._queue.get()
        if item is self._STOP:
            self._exhausted = True
            raise StopIteration
        if isinstance(item, self._Raised):
            self._exhausted = True
            raise item.exc
        return item

    next = __next__

    def drain(self):
        """Stop the producer and empty the queue (idempotent)."""
        self._alive = False
        t = self._thread
        if t is not None:
            while t.is_alive():
                try:
                    self._queue.get(timeout=0.01)
                except Exception:
                    pass
            t.join()
        self._exhausted = True

    def __del__(self):
        try:
            self.drain()   # unblock a producer stuck in queue.put
        except Exception:
            pass


def __getattr__(name):
    """Lazy aliases for iterators that live in mxnet_tpu.image (parity: the
    reference registers ImageRecordIter in src/io and exposes it via mx.io).
    Lazy to avoid a circular import (image.py imports this module)."""
    if name in ("ImageRecordIter", "ImageIter"):
        from . import image as _image
        return getattr(_image, name)
    raise AttributeError("module %r has no attribute %r" % (__name__, name))
