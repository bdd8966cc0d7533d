"""Weight initializers (parity: reference python/mxnet/initializer.py).

Dispatch is by parameter-name suffix exactly as the reference: *_bias/*_gamma/
*_beta/moving_* get fixed defaults, everything else goes to the concrete
initializer's _init_weight.
"""
from __future__ import annotations

import json
import re

import numpy as np

from .base import MXNetError, string_types
from . import ndarray as nd
from . import random as _random

__all__ = ["Initializer", "Uniform", "Normal", "Orthogonal", "Xavier",
           "MSRAPrelu", "Bilinear", "One", "Zero", "Constant", "Load", "Mixed",
           "FusedRNN", "LSTMBias", "InitDesc"]


class InitDesc(str):
    """Parameter name + attrs descriptor (parity: initializer.py InitDesc)."""

    def __new__(cls, name, attrs=None, global_init=None):
        ret = super().__new__(cls, name)
        ret.attrs = attrs or {}
        ret.global_init = global_init
        return ret


class Initializer(object):
    """Base initializer: ``init(name, arr)`` fills arr in place."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs

    def dumps(self):
        return json.dumps([self.__class__.__name__.lower(), self._kwargs])

    def __call__(self, name, arr):
        if not isinstance(name, string_types):
            raise TypeError("name must be string")
        if not isinstance(arr, nd.NDArray):
            raise TypeError("arr must be NDArray")
        # variable-attached initializer wins (parity: reference
        # initializer.py __call__ reading desc.attrs['__init__'], as set by
        # Variable(init=...) — e.g. LSTMCell forget-gate bias)
        init_attr = getattr(name, "attrs", None)
        init_attr = (init_attr or {}).get("__init__", "")
        if init_attr:
            klass, kwargs = json.loads(init_attr)
            _INITIALIZER_REGISTRY[klass.lower()](**kwargs)._init_weight(
                name, arr)
            return
        if name.startswith("upsampling"):
            self._init_bilinear(name, arr)
        elif name.endswith("bias"):
            self._init_bias(name, arr)
        elif name.endswith("gamma"):
            self._init_gamma(name, arr)
        elif name.endswith("beta"):
            self._init_beta(name, arr)
        elif name.endswith("weight"):
            self._init_weight(name, arr)
        elif name.endswith("moving_mean"):
            self._init_zero(name, arr)
        elif name.endswith("moving_var"):
            self._init_one(name, arr)
        elif name.endswith("moving_inv_var"):
            self._init_zero(name, arr)
        elif name.endswith("moving_avg"):
            self._init_zero(name, arr)
        else:
            self._init_default(name, arr)

    def _init_bilinear(self, _, arr):
        weight = np.zeros(arr.shape, dtype="float32").reshape(-1)
        shape = arr.shape
        f = np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(np.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        arr[:] = weight.reshape(shape)

    def _init_zero(self, _, arr):
        arr[:] = 0.0

    def _init_one(self, _, arr):
        arr[:] = 1.0

    def _init_bias(self, _, arr):
        arr[:] = 0.0

    def _init_gamma(self, _, arr):
        arr[:] = 1.0

    def _init_beta(self, _, arr):
        arr[:] = 0.0

    def _init_weight(self, name, arr):
        raise NotImplementedError("must override it")

    def _init_default(self, name, _):
        raise ValueError(
            "Unknown initialization pattern for %s. Default initialization is "
            "now limited to \"weight\", \"bias\", \"gamma\", and \"beta\"."
            % name)


class Load(object):
    """Init from a dict of arrays, fall back to default (parity: Load)."""

    def __init__(self, param, default_init=None, verbose=False):
        if isinstance(param, str):
            param = nd.load(param)
        self.param = {}
        for name, arr in param.items():
            if name.startswith("arg:") or name.startswith("aux:"):
                self.param[name[4:]] = arr
            else:
                self.param[name] = arr
        self.default_init = default_init
        self.verbose = verbose

    def __call__(self, name, arr):
        if name in self.param:
            if arr.shape != self.param[name].shape:
                raise MXNetError("Parameter %s cannot be initialized from "
                                 "loading. Shape mismatch, target %s vs "
                                 "loaded %s" % (name, str(arr.shape),
                                                str(self.param[name].shape)))
            arr[:] = self.param[name]
        else:
            if self.default_init is None:
                raise MXNetError("Cannot Initialize parameter %s; not found "
                                 "and no default initializer" % name)
            self.default_init(name, arr)


class Mixed(object):
    """Regex-pattern-routed initializers (parity: Mixed)."""

    def __init__(self, patterns, initializers):
        assert len(patterns) == len(initializers)
        self.map = list(zip([re.compile(p) for p in patterns], initializers))

    def __call__(self, name, arr):
        for prog, init in self.map:
            if prog.match(name):
                init(name, arr)
                return
        raise ValueError("Parameter name %s did not match any pattern" % name)


class Zero(Initializer):
    def _init_weight(self, _, arr):
        arr[:] = 0.0


class One(Initializer):
    def _init_weight(self, _, arr):
        arr[:] = 1.0


class Constant(Initializer):
    def __init__(self, value=0.0):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, _, arr):
        arr[:] = self.value


class Uniform(Initializer):
    """U(-scale, scale) (parity: Uniform)."""

    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, _, arr):
        tmp = nd.uniform(low=-self.scale, high=self.scale, shape=arr.shape)
        arr._set_value(tmp.value)


class Normal(Initializer):
    """N(0, sigma) (parity: Normal)."""

    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, _, arr):
        tmp = nd.normal(loc=0, scale=self.sigma, shape=arr.shape)
        arr._set_value(tmp.value)


class Orthogonal(Initializer):
    """Orthogonal matrix init (parity: Orthogonal; Saxe et al.)."""

    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, _, arr):
        nout = arr.shape[0]
        nin = int(np.prod(arr.shape[1:]))
        if self.rand_type == "uniform":
            tmp = np.random.uniform(-1.0, 1.0, (nout, nin))
        else:
            tmp = np.random.normal(0.0, 1.0, (nout, nin))
        u, _, v = np.linalg.svd(tmp, full_matrices=False)
        res = u if u.shape == tmp.shape else v
        arr[:] = self.scale * res.reshape(arr.shape)


class Xavier(Initializer):
    """Xavier/Glorot init (parity: Xavier)."""

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type,
                         magnitude=magnitude)
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, name, arr):
        shape = arr.shape
        hw_scale = 1.0
        if len(shape) > 2:
            hw_scale = np.prod(shape[2:])
        fan_in, fan_out = shape[1] * hw_scale, shape[0] * hw_scale
        factor = 1.0
        if self.factor_type == "avg":
            factor = (fan_in + fan_out) / 2.0
        elif self.factor_type == "in":
            factor = fan_in
        elif self.factor_type == "out":
            factor = fan_out
        else:
            raise ValueError("Incorrect factor type")
        scale = np.sqrt(self.magnitude / factor)
        if self.rnd_type == "uniform":
            arr._set_value(nd.uniform(low=-scale, high=scale,
                                      shape=arr.shape).value)
        elif self.rnd_type == "gaussian":
            arr._set_value(nd.normal(loc=0, scale=scale,
                                     shape=arr.shape).value)
        else:
            raise ValueError("Unknown random type")


class MSRAPrelu(Xavier):
    """Kaiming-He init (parity: MSRAPrelu)."""

    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


class Bilinear(Initializer):
    def _init_weight(self, name, arr):
        self._init_bilinear(name, arr)


class FusedRNN(Initializer):
    """Initialize a FusedRNNCell's flat parameter vector by unpacking it,
    applying ``init`` per weight (forget-gate biases to ``forget_bias``),
    and re-packing (parity: reference initializer.py FusedRNN:448-496)."""

    def __init__(self, init, num_hidden, num_layers, mode,
                 bidirectional=False, forget_bias=1.0):
        if init is None:
            raise MXNetError("FusedRNN requires an inner initializer")
        if not isinstance(init, Initializer):
            klass, kwargs = json.loads(init)
            init = _INITIALIZER_REGISTRY[klass.lower()](**kwargs)
        super().__init__(init=init.dumps(),
                         num_hidden=num_hidden, num_layers=num_layers,
                         mode=mode, bidirectional=bidirectional,
                         forget_bias=forget_bias)
        self._init = init
        self._num_hidden = num_hidden
        self._num_layers = num_layers
        self._mode = mode
        self._bidirectional = bidirectional
        self._forget_bias = forget_bias

    def _infer_input_size(self, total):
        """Solve the input size from the flat parameter count."""
        h = self._num_hidden
        d = 2 if self._bidirectional else 1
        g = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[self._mode]
        rest = (self._num_layers - 1) * (h * d + h + 2) + h + 2
        input_size = total // (d * g * h) - rest
        if (input_size + rest) * d * g * h != total:
            raise MXNetError("FusedRNN: cannot infer input size from "
                             "%d parameters" % total)
        return int(input_size)

    def _init_weight(self, _, arr):
        from .rnn import rnn_cell
        cell = rnn_cell.FusedRNNCell(self._num_hidden, self._num_layers,
                                     self._mode, self._bidirectional,
                                     forget_bias=self._forget_bias,
                                     prefix="")
        cell._input_size_hint = self._infer_input_size(arr.size)
        args = cell.unpack_weights({"parameters": arr})
        h = self._num_hidden
        for name in args:
            if name.endswith("_bias"):
                args[name][:] = 0.0
                if self._mode == "lstm":
                    # gate order i,f,c,o: the forget-gate slice gets the bias
                    v = args[name].asnumpy().copy()
                    v[h:2 * h] = self._forget_bias
                    args[name][:] = v
            else:
                self._init(InitDesc(name), args[name])
        arr[:] = cell.pack_weights(args)["parameters"]


class LSTMBias(Initializer):
    """Init LSTM biases with forget-gate bias set (parity: LSTMBias)."""

    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_bias(self, name, arr):
        arr[:] = 0.0
        if arr.shape[0] % 4 == 0:
            num_hidden = arr.shape[0] // 4
            v = arr.asnumpy().copy()
            v[num_hidden:2 * num_hidden] = self.forget_bias
            arr[:] = v

    _init_weight = _init_bias


class LogOfUniform(Initializer):
    """log(U(low, high)): a state-space scan's ``A_log``, whose decay rate
    ``A = exp(A_log)`` the Mamba-2 initialisation draws from U(1, 16)."""

    def __init__(self, low=1.0, high=16.0):
        super().__init__(low=low, high=high)
        self.low, self.high = low, high

    def _init_weight(self, _, arr):
        tmp = nd.uniform(low=self.low, high=self.high, shape=arr.shape)
        arr[:] = np.log(tmp.asnumpy())


class InverseSoftplusLogUniform(Initializer):
    """The bias ``b`` with ``softplus(b)`` log-uniform in [low, high] and at
    least ``floor``: a state-space scan's ``dt_bias`` (Mamba-2: the step
    ``dt`` starts in 0.001-0.1)."""

    def __init__(self, low=0.001, high=0.1, floor=1e-4):
        super().__init__(low=low, high=high, floor=floor)
        self.low, self.high, self.floor = low, high, floor

    def _init_weight(self, _, arr):
        u = nd.uniform(low=0.0, high=1.0, shape=arr.shape).asnumpy()
        dt = np.exp(u * (np.log(self.high) - np.log(self.low))
                    + np.log(self.low))
        dt = np.maximum(dt, self.floor)
        arr[:] = dt + np.log(-np.expm1(-dt))


# registry of initializer classes by lowercase name, used by the
# Variable(init=...) '__init__' attr dispatch and Load/Mixed dumps parity
_INITIALIZER_REGISTRY = {
    k.lower(): v for k, v in list(globals().items())
    if isinstance(v, type) and issubclass(v, Initializer)
}
