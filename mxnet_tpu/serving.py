"""Production serving — concurrent predictor with dynamic bucketed batching.

``predictor.py`` is a faithful port of the reference's synchronous,
one-request-at-a-time ``c_predict_api`` (MXPredCreate/SetInput/Forward).
This module is the throughput layer on top of it: concurrent callers
``submit()`` single-sample requests into a queue, a batcher thread
coalesces whatever is in flight into ONE jitted forward per tick, and the
results are scattered back to per-request futures.

Three ideas carry the design:

* **Dynamic batching with a deadline.**  The first request of a tick
  waits at most ``max_wait_ms`` (default 2 ms, ``MXNET_SERVE_WAIT_MS``)
  for company; whatever arrived by then rides the same forward.  A lone
  request is never starved — its worst case is one deadline — and under
  load the wait never fires because the queue is already full when the
  tick starts (continuous batching: steady-state batch size approaches
  the number of outstanding clients, capped at ``max_batch``).
* **Bucketed batch shapes.**  XLA compiles one program per shape, so
  batching with arbitrary ``n`` would retrace constantly.  Requests are
  padded up to a small ladder of batch sizes (1/2/4/8/.../``max_batch``
  — the BucketingModule jit-cache idea applied to serving), ONE
  ``Predictor`` binding per bucket, created on first use or eagerly via
  ``warm()``.  The jit cache stays warm and tail latency stays flat.
  Padded rows are zeros; their outputs are dropped before the scatter, so
  padding never leaks into results.
* **Multi-model hosting.**  A ``Server`` is a named registry of
  ``ServedModel``s, each with its own queue, batcher thread, bucket
  ladder, and stats — the HTTP front end routes ``/predict/<name>`` to
  the right one.

Telemetry (strict no-op while disabled, docs/observability.md): each
request's time-to-tick is a ``serve.queue_wait`` span, each coalesced
forward a ``serve.batch`` span (both histogram-backed, so
``quantile("serve.batch", 0.99)``, the metrics endpoint, and the fleet
report see the serving tail), plus ``serve_batch_size`` /
``serve_queue_depth`` gauges and ``serve_requests`` /
``serve_padded_slots`` counters.  The per-bucket ``Predictor`` spans
(``predict.forward``) keep flowing underneath.

The stdlib HTTP front end follows the ``metrics_server.py`` idiom:
``MXNET_SERVE_PORT=<port>`` (or ``<host>:<port>``) autostarts it at
import, binding ``127.0.0.1`` unless a host is given; with the env var
unset this module creates no thread and no socket, and
``start_server``/``ServedModel.submit`` are the only entry points that
ever do.
"""
from __future__ import annotations

import json
import math as _math
import queue as _queue_mod
import threading
import time
from concurrent.futures import Future, TimeoutError as _FutureTimeout
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as _np

from .base import MXNetError, get_env
from . import sanitize as _san
from . import telemetry as _tel
from .predictor import Predictor, read_checkpoint

__all__ = ["bucket_ladder", "ServedModel", "Server", "default_server",
           "start_server", "stop_server", "server_port"]


def bucket_ladder(max_batch):
    """Power-of-two batch-size ladder up to ``max_batch`` inclusive:
    ``bucket_ladder(8) == [1, 2, 4, 8]``; a non-power-of-two max is
    appended as the top rung (``bucket_ladder(6) == [1, 2, 4, 6]``)."""
    max_batch = int(max_batch)
    if max_batch < 1:
        raise MXNetError("max_batch must be >= 1, got %d" % max_batch)
    ladder = []
    b = 1
    while b < max_batch:
        ladder.append(b)
        b *= 2
    ladder.append(max_batch)
    return ladder


def _env_max_batch():
    """``MXNET_SERVE_MAX_BATCH`` (default 8) — read (and validated) only
    when the constructor didn't override it, so an invalid env value
    can't break a fully-overridden model.  Dispatch time, never under
    trace."""
    max_batch = get_env("MXNET_SERVE_MAX_BATCH", 8, typ=int)
    if max_batch < 1:
        raise MXNetError("MXNET_SERVE_MAX_BATCH=%d: must be >= 1"
                         % max_batch)
    return max_batch


def _env_wait_s():
    """``MXNET_SERVE_WAIT_MS`` (default 2 ms) in seconds — same
    read-only-when-needed discipline as :func:`_env_max_batch`."""
    wait_ms = get_env("MXNET_SERVE_WAIT_MS", 2.0, typ=float)
    if wait_ms < 0:
        raise MXNetError("MXNET_SERVE_WAIT_MS=%g: must be >= 0" % wait_ms)
    return wait_ms / 1e3


class _Request(object):
    """One enqueued sample: staged inputs + the future its row resolves."""

    __slots__ = ("inputs", "future", "wall", "t0")

    def __init__(self, inputs):
        self.inputs = inputs
        self.future = Future()
        self.wall = time.time()          # span start (wall clock)
        self.t0 = time.perf_counter()    # deadline / queue-wait base


class _WarmRequest(object):
    """A ladder-warm command processed ON the batcher thread, so warming
    never races a live forward — the batcher is the predictors' only
    executor."""

    __slots__ = ("future",)

    def __init__(self):
        self.future = Future()


_STOP = object()


class ServedModel(object):
    """One model under dynamic bucketed batching.

    Parameters
    ----------
    symbol : Symbol or saved-symbol JSON string
    param_blob : params dict / ``.params`` path / raw bytes (as Predictor)
    input_shapes : {name: per-SAMPLE shape} — no batch dimension; each
        request carries exactly one sample per input and the batcher owns
        the batch axis.
    name : registry/telemetry label
    max_batch : top of the bucket ladder (default ``MXNET_SERVE_MAX_BATCH``
        or 8)
    max_wait_ms : dynamic-batching deadline (default ``MXNET_SERVE_WAIT_MS``
        or 2 ms; 0 means "never wait — serve whatever already queued")
    buckets : explicit ladder override (sorted, deduped; max_batch becomes
        the top rung)
    input_types / output_names / dev_type / dev_id : forwarded to each
        bucket's ``Predictor`` binding
    """

    def __init__(self, symbol, param_blob, input_shapes, name=None,
                 max_batch=None, max_wait_ms=None, buckets=None,
                 input_types=None, output_names=None, dev_type="cpu",
                 dev_id=0):
        from . import symbol as sym_mod
        from . import ndarray as nd
        from .context import Context
        from .predictor import _load_params
        if isinstance(symbol, (str, bytes)):
            # parse once — every bucket binding shares the graph
            symbol = sym_mod.load_json(
                symbol.decode() if isinstance(symbol, bytes) else symbol)
        self.name = name or "model"
        self._symbol = symbol
        # load + device-stage the params ONCE: every bucket binding then
        # shares the same read-only device arrays (copy_params=False) —
        # the ladder costs one weight set in device memory, not one per
        # rung, and rung creation never re-parses the blob
        arg_p, aux_p = _load_params(param_blob)
        ctx = Context(dev_type, dev_id)
        import logging
        from .context import announce_placement
        announce_placement("ServedModel %r" % self.name, [ctx], logging)
        self._param_blob = {}
        for prefix, group in (("arg:", arg_p), ("aux:", aux_p)):
            for k, v in group.items():
                if not isinstance(v, nd.NDArray):
                    v = nd.array(v)
                self._param_blob[prefix + k] = v.as_in_context(ctx)
        self._output_names = output_names
        self._dev = (dev_type, dev_id)
        self._sample_shapes = {k: tuple(int(x) for x in v)
                               for k, v in input_shapes.items()}
        self._input_types = {k: _np.dtype(_np.float32)
                             for k in self._sample_shapes}
        for k, t in (input_types or {}).items():
            self._input_types[k] = _np.dtype(t)
        unknown_types = set(input_types or {}) - set(self._sample_shapes)
        if unknown_types:
            raise MXNetError("input_types names non-inputs %s"
                             % sorted(unknown_types))
        if buckets:
            if any(b != int(b) for b in buckets):
                raise MXNetError("bucket sizes must be integers, got %s"
                                 % (sorted(buckets),))
            ladder = sorted({int(b) for b in buckets})
            if not ladder or ladder[0] < 1:
                raise MXNetError("bucket sizes must be >= 1, got %s"
                                 % (sorted(buckets),))
            self.max_batch = ladder[-1]
            self.buckets = ladder
        else:
            self.max_batch = int(max_batch) if max_batch is not None \
                else _env_max_batch()
            self.buckets = bucket_ladder(self.max_batch)
        self._wait_s = (_env_wait_s() if max_wait_ms is None
                        else float(max_wait_ms) / 1e3)
        if self._wait_s < 0:
            raise MXNetError("max_wait_ms must be >= 0")
        self._lock = threading.RLock()
        self._predictors = {}     # bucket size -> Predictor binding
        # mxsan: the bucket-rung ladder is a jit cache (one Predictor
        # binding per rung); the warmup budget is one miss per rung —
        # any further miss means rungs are being rebuilt
        self._san_cache = _san.register_cache(
            "serving:%s" % self.name, kind="serving-rung", owner=self,
            sizer=lambda m: len(m._predictors), warmup=len(self.buckets))
        self._queue = _queue_mod.Queue()
        self._thread = None
        self._closed = False
        self._stats = {"requests": 0, "batches": 0, "slots": 0,
                       "padded_slots": 0, "errors": 0,
                       "batches_by_bucket": {}}

    # ------------------------------------------------------------- lifecycle
    def _enqueue(self, item):
        """Closed-check + lazy batcher start + enqueue under ONE lock
        hold, so a concurrent ``close()`` can never slip its _STOP
        sentinel in front of a request that already passed the closed
        check (which would leave that request's future unresolved
        forever).  Lazy start keeps construction free: the daemon thread
        exists only once traffic does."""
        with self._lock:
            if self._closed:
                raise MXNetError("ServedModel %r is closed" % self.name)
            if self._thread is None:
                t = threading.Thread(target=self._batch_loop,
                                     name="mxtpu-serve-%s" % self.name,
                                     daemon=True)
                self._thread = t
                t.start()
            self._queue.put(item)

    def close(self, timeout=5.0):
        """Stop the batcher thread after in-flight requests drain.
        Idempotent; further ``submit`` calls raise."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            t = self._thread
            if t is not None:
                # under the lock: every accepted request sits ahead of
                # the sentinel, so the batcher drains them all first
                self._queue.put(_STOP)
        if t is not None:
            t.join(timeout)

    # ------------------------------------------------------------------- api
    def submit(self, inputs):
        """Enqueue one request (ONE sample per input, matching the
        per-sample ``input_shapes``) and return its
        ``concurrent.futures.Future``.  The future resolves to a list of
        per-output numpy rows (one entry per model output); errors raised
        by the forward resolve the future exceptionally.  Shape/name
        validation happens here, in the caller's thread, so a bad request
        can never poison a coalesced batch."""
        staged = {}
        for k, shape in self._sample_shapes.items():
            if k not in inputs:
                raise MXNetError("request for %r is missing input %r"
                                 % (self.name, k))
            # copy=True: np.asarray would alias a caller array that
            # already matches the dtype, and the batcher reads the
            # staged buffer up to a deadline later — a client reusing
            # one buffer across submits must not corrupt queued requests
            arr = _np.array(inputs[k], dtype=self._input_types[k],
                            copy=True)
            if tuple(arr.shape) != shape:
                raise MXNetError(
                    "request input %r has shape %s, want per-sample %s "
                    "(the batcher owns the batch axis)"
                    % (k, tuple(arr.shape), shape))
            staged[k] = arr
        unknown = set(inputs) - set(self._sample_shapes)
        if unknown:
            raise MXNetError("unknown request inputs %s (model %r takes %s)"
                             % (sorted(unknown), self.name,
                                sorted(self._sample_shapes)))
        req = _Request(staged)
        self._enqueue(req)
        return req.future

    def predict(self, inputs, timeout=None):
        """Blocking convenience: ``submit(inputs).result(timeout)``."""
        return self.submit(inputs).result(timeout)

    def warm(self, timeout=None):
        """Eagerly create every bucket's ``Predictor`` binding and run one
        zero-batch forward through each, so the whole ladder's jit cache
        is compiled before real traffic arrives (first-request latency
        becomes steady-state latency).  The warming runs ON the batcher
        thread (started if need be), so calling this while traffic is
        already flowing never races a live forward; the call blocks until
        the ladder is compiled."""
        req = _WarmRequest()
        self._enqueue(req)
        req.future.result(timeout)
        return self

    def _do_warm(self, req):
        """Batcher-thread half of :meth:`warm`."""
        try:
            for b in self.buckets:
                pred = self._predictor(b)
                pred.forward(**{k: _np.zeros((b,) + s,
                                             dtype=self._input_types[k])
                                for k, s in self._sample_shapes.items()})
            req.future.set_result(True)
        except Exception as exc:
            req.future.set_exception(exc)

    def stats(self):
        """Snapshot of serving counters: requests, batches, slots (rows
        the buckets provided), padded_slots, errors, batches_by_bucket,
        plus derived mean ``occupancy`` (requests / slots — 1.0 means
        every forward ran full)."""
        with self._lock:
            s = dict(self._stats)
            s["batches_by_bucket"] = dict(self._stats["batches_by_bucket"])
        s["occupancy"] = (s["requests"] / s["slots"]) if s["slots"] else None
        s["buckets"] = list(self.buckets)
        s["max_batch"] = self.max_batch
        s["max_wait_ms"] = self._wait_s * 1e3
        s["inputs"] = {k: list(v) for k, v in self._sample_shapes.items()}
        return s

    # ---------------------------------------------------------------- batcher
    def _predictor(self, bucket):
        """The ``Predictor`` bound at batch size ``bucket`` (one jit-cached
        XLA program per rung), created on first use.  Only the batcher
        thread ever calls this (warm commands run there too), so the
        build — bind + first-call XLA compile, potentially seconds —
        happens OUTSIDE the model lock: request intake and stats stay
        responsive while a new rung compiles."""
        with self._lock:
            pred = self._predictors.get(bucket)
        if pred is None:
            shapes = {k: (bucket,) + s
                      for k, s in self._sample_shapes.items()}
            types = {k: t for k, t in self._input_types.items()
                     if t != _np.dtype(_np.float32)}
            pred = Predictor(self._symbol, self._param_blob, shapes,
                             dev_type=self._dev[0], dev_id=self._dev[1],
                             output_names=self._output_names,
                             input_types=types or None,
                             copy_params=False)
            with self._lock:
                self._predictors[bucket] = pred
            self._san_cache.miss({"bucket": bucket})
        return pred

    def _bucket_for(self, n):
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _batch_loop(self):
        """Batcher tick: block for the first request, give it at most the
        deadline to attract company (skipped entirely when the queue
        already holds a full bucket), then run the coalesced forward.
        Warm commands run here too — this thread is the predictors' only
        executor, so warming and serving can never race."""
        while True:
            req = self._queue.get()
            if req is _STOP:
                return
            if isinstance(req, _WarmRequest):
                self._do_warm(req)
                continue
            batch = [req]
            warms = []
            deadline = req.t0 + self._wait_s
            stop = False
            while len(batch) < self.max_batch:
                remaining = deadline - time.perf_counter()
                try:
                    nxt = (self._queue.get_nowait() if remaining <= 0
                           else self._queue.get(timeout=remaining))
                except _queue_mod.Empty:
                    break
                if nxt is _STOP:
                    stop = True
                    break
                if isinstance(nxt, _WarmRequest):
                    warms.append(nxt)   # after the in-flight batch
                    continue
                batch.append(nxt)
            self._run_batch(batch)
            for w in warms:
                self._do_warm(w)
            if stop:
                return

    def _run_batch(self, batch):
        n = len(batch)
        bucket = self._bucket_for(n)
        try:
            if _tel._enabled:
                now = time.perf_counter()
                for r in batch:
                    # queue wait = enqueue -> tick start; recorded from
                    # the batcher thread with the request's own timestamp
                    _tel.record_span("serve.queue_wait", r.wall, now - r.t0,
                                     cat="serve", model=self.name)
                _tel.gauge("serve_batch_size", n, model=self.name)
                _tel.gauge("serve_queue_depth", self._queue.qsize(),
                           model=self.name)
            with _tel.span("serve.batch", cat="serve", model=self.name,
                           bucket=bucket, n=n):
                pred = self._predictor(bucket)
                padded = {}
                for k, shape in self._sample_shapes.items():
                    buf = _np.zeros((bucket,) + shape,
                                    dtype=self._input_types[k])
                    for i, r in enumerate(batch):
                        buf[i] = r.inputs[k]
                    padded[k] = buf
                # batched staging: ONE forward call stages every padded
                # input (at the binding's dtype) and runs the bucket's
                # compiled program.  mxsan SYNC treats the tick's forward
                # as a hot region — only the row extraction below is a
                # planned device->host transfer
                with _san.hot_region("serve.batch"):
                    pred.forward(**padded)
                outs = [pred.get_output(j) for j in range(pred.num_outputs)]
                # row extraction happens INSIDE the guard: an output
                # without a leading batch axis must scatter as an error,
                # not kill the batcher thread with futures unresolved
                # mxlint: disable=SYNC001 planned d2h — rows scatter to the client futures
                rows = [[_np.array(o[i]) for o in outs] for i in range(n)]
        except Exception as exc:   # scatter the failure, keep serving
            with self._lock:
                self._stats["errors"] += n
            for r in batch:
                if not r.future.set_running_or_notify_cancel():
                    continue
                r.future.set_exception(exc)
            return
        if _tel._enabled:
            _tel.counter("serve_requests", n, model=self.name)
            if bucket > n:
                _tel.counter("serve_padded_slots", bucket - n,
                             model=self.name)
        with self._lock:
            st = self._stats
            st["requests"] += n
            st["batches"] += 1
            st["slots"] += bucket
            st["padded_slots"] += bucket - n
            by = st["batches_by_bucket"]
            by[bucket] = by.get(bucket, 0) + 1
        for r, row in zip(batch, rows):
            if not r.future.set_running_or_notify_cancel():
                continue   # caller cancelled while queued; row discarded
            # padded rows (index >= n) were never extracted — padding
            # cannot leak into any scattered result
            r.future.set_result(row)


class Server(object):
    """Named registry of :class:`ServedModel`s — multi-model hosting with
    per-model buckets, queues, and stats.  The HTTP front end serves the
    process-wide :func:`default_server`; embedders can run their own."""

    def __init__(self):
        self._lock = threading.RLock()
        self._models = {}

    def register(self, name, model=None, **kwargs):
        """Register ``model`` (a ServedModel) under ``name``, or build one
        from ``kwargs`` (the ServedModel constructor signature: symbol,
        param_blob, input_shapes, ...).  Returns the registered model.
        Re-registering a name replaces (and closes) the old model."""
        if model is None:
            model = ServedModel(name=name, **kwargs)
        elif not isinstance(model, ServedModel):
            raise MXNetError("register() wants a ServedModel (or kwargs "
                             "to build one), got %s" % type(model).__name__)
        else:
            if kwargs:
                raise MXNetError("register(model=...) takes no build "
                                 "kwargs; got %s" % sorted(kwargs))
            # the registry name IS the model's serving identity — routes,
            # telemetry tags, and the batcher thread name must agree
            model.name = name
        with self._lock:
            old = self._models.get(name)
            self._models[name] = model
        if old is not None and old is not model:
            old.close()
        return model

    def register_checkpoint(self, name, prefix, epoch, input_shapes,
                            **kwargs):
        """Register from ``prefix-symbol.json`` + ``prefix-%04d.params``
        (the save_checkpoint layout) — the serving twin of
        ``Predictor.from_checkpoint``.  ``input_shapes`` are per-sample."""
        sym_json, blob = read_checkpoint(prefix, epoch)
        return self.register(name, symbol=sym_json, param_blob=blob,
                             input_shapes=input_shapes, **kwargs)

    def unregister(self, name):
        """Remove and close one model (no-op when absent)."""
        with self._lock:
            model = self._models.pop(name, None)
        if model is not None:
            model.close()

    def names(self):
        """Registered model names (cheap — no stats snapshot)."""
        with self._lock:
            return sorted(self._models)

    def model(self, name):
        with self._lock:
            model = self._models.get(name)
        if model is None:
            raise MXNetError("no model %r is registered (have %s)"
                             % (name, self.names()))
        return model

    def submit(self, name, inputs):
        return self.model(name).submit(inputs)

    def predict(self, name, inputs, timeout=None):
        return self.model(name).predict(inputs, timeout=timeout)

    def models(self):
        """{name: stats-snapshot} for every registered model."""
        with self._lock:
            items = list(self._models.items())
        return {name: model.stats() for name, model in items}

    def close(self):
        """Close every registered model (the HTTP front end is owned by
        :func:`stop_server`, not the registry)."""
        with self._lock:
            models, self._models = list(self._models.values()), {}
        for model in models:
            model.close()


# ------------------------------------------------------------- HTTP frontend
_lock = threading.Lock()
_http = None
_http_thread = None
_default_server = None
_default_lock = threading.Lock()


def default_server():
    """The process-wide :class:`Server` the HTTP front end exposes
    (created on first use; creating it spawns nothing)."""
    global _default_server
    with _default_lock:
        if _default_server is None:
            _default_server = Server()
        return _default_server


def _json_safe(obj):
    """Replace non-finite floats with their string forms so responses
    stay RFC-8259 parseable — a model that starts emitting NaN is exactly
    the incident a strict-JSON client must be able to read (the same
    convention as metrics_server.json_snapshot and run_compare --json)."""
    if isinstance(obj, float) and not _math.isfinite(obj):
        return str(obj)
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    return obj


class _Handler(BaseHTTPRequestHandler):
    def _send(self, code, doc):
        body = json.dumps(_json_safe(doc)).encode()
        try:
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass   # client went away mid-response

    def do_GET(self):   # noqa: N802 — http.server contract
        path = self.path.split("?", 1)[0]
        registry = self.server.mx_registry
        if path in ("/models", "/"):
            self._send(200, {"models": registry.models()})
        elif path == "/healthz":
            self._send(200, {"ok": True, "models": registry.names()})
        else:
            self._send(404, {"error": "no route %s (have /models, /healthz, "
                                      "POST /predict/<model>)" % path})

    def do_POST(self):  # noqa: N802 — http.server contract
        path = self.path.split("?", 1)[0]
        registry = self.server.mx_registry
        if not path.startswith("/predict/"):
            self._send(404, {"error": "POST route is /predict/<model>"})
            return
        name = path[len("/predict/"):]
        try:
            model = registry.model(name)
        except MXNetError as e:
            self._send(404, {"error": str(e)})
            return
        # request faults (bad JSON, bad shape/name: raised by parsing or
        # submit() itself) answer 400 ...
        try:
            length = int(self.headers.get("Content-Length") or 0)
            doc = json.loads(self.rfile.read(length) or b"{}")
            if not isinstance(doc, dict):
                raise ValueError("body must be a JSON object")
            if "inputs" in doc:
                inputs = doc["inputs"]
            else:
                # shorthand: the top-level object IS the inputs dict —
                # minus the envelope's own keys, so {"data": ..,
                # "timeout_s": 5} works instead of 400ing on timeout_s
                inputs = {k: v for k, v in doc.items() if k != "timeout_s"}
            if not isinstance(inputs, dict):
                raise ValueError('"inputs" must be an object of '
                                 "{input_name: nested list}")
            timeout = float(doc.get("timeout_s", 30.0))
            fut = model.submit(inputs)
        except (ValueError, TypeError, MXNetError) as e:
            # TypeError included: float(None) for a null timeout_s, or
            # np.array over a non-numeric nested structure — request
            # faults must answer 400, never drop the connection
            self._send(400, {"error": str(e)})
            return
        # ... while anything scattered into the future is a SERVER fault
        # (failed bind/forward — even when it raises MXNetError): 500
        # JSON, never a dropped connection or a misleading 400
        try:
            outs = fut.result(timeout)
        except (TimeoutError, _FutureTimeout):
            # futures.TimeoutError only aliases the builtin on 3.11+
            self._send(504, {"error": "predict timed out"})
            return
        except Exception as e:
            self._send(500, {"error": "%s: %s" % (type(e).__name__, e)})
            return
        self._send(200, {"model": name,
                         "outputs": [o.tolist() for o in outs]})

    def log_message(self, *args):
        """Per-request stderr lines off — a load test must not flood the
        process log (same discipline as metrics_server)."""


def start_server(port=None, host=None, registry=None):
    """Start the serving HTTP endpoint; returns the bound port (idempotent
    — a running endpoint's port is returned as-is).  ``port=None`` reads
    ``MXNET_SERVE_PORT`` (``<port>`` or ``<host>:<port>``) and returns
    None when unset/0 — strict no-op: no socket, no thread.  Pass
    ``port=0`` explicitly for an ephemeral port (tests).  ``registry``
    defaults to :func:`default_server`."""
    from .metrics_server import parse_endpoint
    global _http, _http_thread
    with _lock:
        if _http is not None:
            return _http.server_address[1]
        if port is None:
            raw = get_env("MXNET_SERVE_PORT")
            if not raw:
                return None
            env_host, base = parse_endpoint(raw)
            if base <= 0:
                return None
            if host is None:
                host = env_host
            port = base
        srv = ThreadingHTTPServer((host or "127.0.0.1", port), _Handler)
        srv.daemon_threads = True
        srv.mx_registry = registry if registry is not None \
            else default_server()
        _http = srv
        _http_thread = threading.Thread(target=srv.serve_forever,
                                        name="mxtpu-serve-http", daemon=True)
        _http_thread.start()
        return srv.server_address[1]


def stop_server():
    """Shut the HTTP endpoint down and close its socket (registered
    models keep running — close them via their Server).  Idempotent."""
    global _http, _http_thread
    with _lock:
        srv, _http = _http, None
        t, _http_thread = _http_thread, None
    if srv is not None:
        srv.shutdown()
        srv.server_close()
    if t is not None and t.is_alive():
        t.join(timeout=5.0)


def server_port():
    """Bound port while the HTTP endpoint runs, else None."""
    with _lock:
        return _http.server_address[1] if _http is not None else None


# ------------------------------------------------- autostart (env contract)
def _autostart():
    """``MXNET_SERVE_PORT=<port>`` (or ``<host>:<port>``) starts the HTTP
    front end at import time (models are registered by user code against
    :func:`default_server`).  A malformed value or an unbindable port
    degrades to disabled-with-a-warning rather than failing the import;
    with the var unset this is a strict no-op."""
    from .metrics_server import parse_endpoint
    raw = get_env("MXNET_SERVE_PORT")
    if not raw:
        return False
    import warnings
    try:
        _, base = parse_endpoint(raw)
    except ValueError:
        warnings.warn("MXNET_SERVE_PORT=%r is not <port> or <host>:<port>; "
                      "serving endpoint disabled" % raw)
        return False
    if base <= 0:
        return False
    try:
        return start_server() is not None
    except OSError as e:
        warnings.warn("MXNET_SERVE_PORT=%s: cannot bind (%s); serving "
                      "endpoint disabled" % (raw, e))
        return False


_autostart()
