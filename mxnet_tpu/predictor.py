"""Inference-only predictor (parity: reference src/c_api/c_predict_api.cc
MXPred* — load saved symbol JSON + params blob, bind a forward-only
executor, feed inputs, read outputs).

TPU-first: the forward pass is ONE jit-compiled XLA computation (the
MXNET_PREDICT_ONLY/NaiveEngine distinction disappears — inference is always
the maximally-bulked path).  This module is both the Python inference API
and the engine behind the native C predict API (src/c_api/c_api.cc)."""
from __future__ import annotations

import numpy as _np

from .base import MXNetError
from . import ndarray as nd
from . import symbol as sym_mod

__all__ = ["Predictor", "read_checkpoint"]


def read_checkpoint(prefix, epoch):
    """``(symbol_json, params_blob)`` of a ``save_checkpoint`` pair
    (``prefix-symbol.json`` + ``prefix-%04d.params``) — the one place the
    checkpoint file layout is known; ``Predictor.from_checkpoint`` and
    ``serving.Server.register_checkpoint`` both load through it."""
    with open("%s-symbol.json" % prefix) as f:
        sym_json = f.read()
    with open("%s-%04d.params" % (prefix, epoch), "rb") as f:
        blob = f.read()
    return sym_json, blob


class Predictor(object):
    """Forward-only bound model.

    Parameters
    ----------
    symbol : Symbol or JSON string (the ``-symbol.json`` content)
    param_blob : dict of params, a ``.params`` path, or raw bytes of one
    input_shapes : {name: shape} for all data inputs
    dev_type / dev_id : placement (parity: MXPredCreate signature)
    input_types : optional {name: dtype} for data inputs that are not
        float32 (embedding id streams, pre-cast bf16 activations); the
        input binds — and ``set_input`` stages — at that dtype.
    copy_params : default True (each binding owns a private copy of the
        weights, reference semantics).  ``False`` binds param NDArrays
        already resident on the target device as-is — safe because a
        forward-only executor never writes its weight/aux args (jax
        arrays are immutable), and what lets the serving bucket ladder
        (serving.py) share ONE device-resident weight set across every
        batch-size binding instead of one copy per rung.
    """

    def __init__(self, symbol, param_blob, input_shapes, dev_type="cpu",
                 dev_id=0, output_names=None, input_types=None,
                 copy_params=True):
        from .context import Context
        if isinstance(symbol, (str, bytes)):
            symbol = sym_mod.load_json(
                symbol.decode() if isinstance(symbol, bytes) else symbol)
        if output_names:
            # feature-extraction binding: outputs become the named internal
            # node outputs (parity: MXPredCreatePartialOut, reference
            # c_predict_api.h:92 + c_predict_api.cc output_keys matching)
            internals = symbol.get_internals()
            names = internals.list_outputs()
            picked = []
            for key in output_names:
                if key in names:
                    picked.append(names.index(key))
                elif key + "_output" in names:
                    picked.append(names.index(key + "_output"))
                else:
                    raise MXNetError("output %r not found in graph (%d "
                                     "internal outputs)" % (key, len(names)))
            symbol = sym_mod.Symbol(
                [internals._outputs[i] for i in picked])
        self.symbol = symbol
        ctx = Context(dev_type, dev_id)
        if copy_params:   # a serving rung shares ServedModel's announcement
            import logging
            from .context import announce_placement
            announce_placement("Predictor", [ctx], logging)
        arg_params, aux_params = _load_params(param_blob)

        input_shapes = {k: tuple(int(x) for x in v)
                        for k, v in input_shapes.items()}
        arg_shapes, _, aux_shapes = symbol.infer_shape(**input_shapes)
        if arg_shapes is None:
            raise MXNetError("Predictor: cannot infer shapes from %r"
                             % (input_shapes,))
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        self._input_names = list(input_shapes)
        input_types = {k: _np.dtype(v)
                       for k, v in (input_types or {}).items()}
        unknown_types = set(input_types) - set(input_shapes)
        if unknown_types:
            raise MXNetError("input_types names non-inputs %s"
                             % sorted(unknown_types))
        # params not in the blob (e.g. the loss head's label input) bind as
        # zeros — reference c_predict_api.cc:191-195 does exactly this
        def place(p):
            if not copy_params and p.context == ctx:
                return p   # share the device-resident array (read-only)
            return p.copyto(ctx)

        args = {}
        for name, shape in zip(arg_names, arg_shapes):
            if name in arg_params and name not in input_shapes:
                args[name] = place(arg_params[name])
            else:
                args[name] = nd.zeros(shape, ctx=ctx,
                                      dtype=input_types.get(name,
                                                            _np.float32))
        auxs = {}
        for name, shape in zip(aux_names, aux_shapes):
            if name in aux_params:
                auxs[name] = place(aux_params[name])
            else:
                auxs[name] = nd.zeros(shape, ctx=ctx)
        self._executor = symbol.bind(ctx, args, aux_states=auxs,
                                     grad_req="null")
        self._outputs = None

    # ------------------------------------------------------------------- api
    def set_input(self, name, value):
        """(parity: MXPredSetInput).  The value stages at the BOUND
        argument's dtype (an int32 id stream or a bf16 input binding never
        round-trips through a forced float32 host cast — large ids would
        silently lose precision).  While telemetry records, the host→
        device staging copy is timed as a ``predict.set_input`` span (the
        serving analogue of the fit loop's ``load_data``)."""
        if name not in self._input_names:
            raise MXNetError("unknown input %s (have %s)"
                             % (name, self._input_names))
        arr = self._executor.arg_dict[name]
        from . import telemetry as _tel
        with _tel.span("predict.set_input", cat="serve", input=name):
            arr[:] = _np.asarray(value, dtype=arr.dtype)

    def forward(self, **inputs):
        """(parity: MXPredForward).  Keyword arguments are batched input
        staging — ``forward(data=batch)`` stages every given input (each
        at its bound dtype, exactly like ``set_input``) and runs the
        forward in one call; the serving batcher (serving.py) uses this
        so a coalesced tick is a single predictor invocation.  While
        telemetry records, each call is a ``predict.forward`` span
        (histogram-backed — the executor blocks on its result while
        recording, so the span is true serving latency, and
        ``quantile("predict.forward", 0.99)``, the metrics endpoint, and
        the fleet report all see the tail) plus ``predict_requests``/
        ``predict_samples`` counters.  Strict no-op when telemetry is
        disabled."""
        staged = {}
        for name, value in inputs.items():
            if name not in self._input_names:
                raise MXNetError("unknown input %s (have %s)"
                                 % (name, self._input_names))
            staged[name] = _np.asarray(
                value, dtype=self._executor.arg_dict[name].dtype)
        from . import telemetry as _tel
        with _tel.span("predict.forward", cat="serve"):
            self._outputs = self._executor.forward(is_train=False, **staged)
        if not _tel._enabled:
            return
        _tel.counter("predict_requests")
        if self._input_names:
            _tel.counter("predict_samples", int(
                self._executor.arg_dict[self._input_names[0]].shape[0]))

    def partial_forward(self, step):
        """Stepwise-forward protocol (parity: MXPredPartialForward,
        reference c_predict_api.h:150).  The reference runs graph nodes
        [0, step); under XLA the graph is ONE compiled computation, so the
        real execution happens on the first call and the remaining calls
        count the protocol down — the caller's
        ``while (step_left > 0) partial_forward(++step)`` loop observes
        identical end state.  Returns step_left."""
        from .symbol import _topo
        n_steps = max(1, sum(
            1 for n in _topo([nd_ for nd_, _ in self.symbol._outputs])
            if not n.is_var))
        if self._outputs is None:
            self.forward()
        return max(0, n_steps - int(step))

    def get_output_shape(self, index=0):
        """(parity: MXPredGetOutputShape)"""
        outs = self._outputs or self._executor.outputs
        return tuple(outs[index].shape)

    def get_output(self, index=0):
        """Blocking copy of one output to host numpy (parity: MXPredGetOutput)."""
        if self._outputs is None:
            raise MXNetError("call forward() first")
        return self._outputs[index].asnumpy()

    @property
    def num_outputs(self):
        return len(self._executor.outputs)

    # ------------------------------------------------------------- factories
    @staticmethod
    def from_checkpoint(prefix, epoch, input_shapes, dev_type="cpu",
                        dev_id=0, output_names=None, input_types=None):
        """Load ``prefix-symbol.json`` + ``prefix-%04d.params``.
        ``output_names`` reaches the partial-out feature-extraction
        binding (MXPredCreatePartialOut parity), so internal-layer
        outputs are reachable straight from checkpoint files."""
        sym_json, blob = read_checkpoint(prefix, epoch)
        return Predictor(sym_json, blob, input_shapes, dev_type, dev_id,
                         output_names=output_names, input_types=input_types)


def _load_params(param_blob):
    """Accept a dict, a .params path, or raw bytes of a .params file."""
    import io
    import os
    import tempfile
    if isinstance(param_blob, dict):
        raw = param_blob
    elif isinstance(param_blob, (bytes, bytearray)):
        # nd.load reads from a path; stage the blob
        fd, path = tempfile.mkstemp(suffix=".params")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(param_blob)
            raw = nd.load(path)
        finally:
            os.unlink(path)
    else:
        raw = nd.load(param_blob)
    if not isinstance(raw, dict):
        raise MXNetError(
            "Predictor params must be name-keyed ('arg:name'/'aux:name', "
            "as written by save_checkpoint); got a positional array list")
    arg_params, aux_params = {}, {}
    for k, v in raw.items():
        if k.startswith("arg:"):
            arg_params[k[4:]] = v
        elif k.startswith("aux:"):
            aux_params[k[4:]] = v
        else:
            arg_params[k] = v
    return arg_params, aux_params
