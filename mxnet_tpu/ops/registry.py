"""Operator registry — the TPU-native replacement for NNVM's Op registry +
FCompute dispatch (reference: include/mxnet/op_attr_types.h, src/c_api/c_api_ndarray.cc
MXImperativeInvoke, nnvm Op attrs).

Design (tpu-first): an operator is a *pure JAX function* plus metadata.  Imperative
calls jit the function once per (attrs, is_train) and let XLA cache per input shape;
symbolic execution composes the same functions into one traced computation that XLA
fuses and schedules — there is no per-op kernel dispatch, no PlanMemory, no cached-op
engine path, because the XLA compiler owns scheduling/memory on TPU.

Gradient metadata (NNVM FGradient) is unnecessary: backward comes from JAX autodiff of
the composed forward; ops with non-autodiff semantics (SoftmaxOutput & friends) embed a
``jax.custom_vjp``.  Shape/type inference (FInferShape/FInferType) defaults to
``jax.eval_shape`` and is overridden per-op only where MXNet requires *bidirectional*
inference (parameter-bearing ops deduce weight shapes from data).
"""
from __future__ import annotations

import ast
import functools

import numpy as _np

from ..base import MXNetError, Registry

__all__ = ["OpDef", "register", "get_op", "list_ops", "OPS", "attr_key",
           "parse_tuple", "parse_int", "parse_float", "parse_bool", "parse_str",
           "parse_dtype", "normalize_attrs", "eval_shape_infer"]

OPS = Registry("operator")


# ---------------------------------------------------------------- attr parsing
def parse_tuple(v):
    if v is None or isinstance(v, tuple):
        return v
    if isinstance(v, list):
        return tuple(v)
    if isinstance(v, (int, float)):
        return (int(v),)
    v = v.strip()
    out = ast.literal_eval(v)
    if isinstance(out, (int, float)):
        return (int(out),)
    return tuple(int(x) for x in out)


def parse_int(v):
    if v is None:
        return None
    if isinstance(v, str) and v in ("None", ""):
        return None
    return int(v)


def parse_float(v):
    return None if v is None else float(v)


def parse_bool(v):
    if isinstance(v, str):
        return v not in ("0", "False", "false", "")
    return bool(v)


def parse_str(v):
    return None if v is None else str(v)


_DTYPES = {"float32": _np.float32, "float64": _np.float64, "float16": _np.float16,
           "uint8": _np.uint8, "int32": _np.int32, "int8": _np.int8,
           "int64": _np.int64}


def parse_dtype(v):
    """Accept numpy dtypes, jax dtypes, and string names (incl. bfloat16)."""
    if v is None:
        return None
    if isinstance(v, str):
        if v == "bfloat16":
            import jax.numpy as jnp
            return jnp.bfloat16
        return _np.dtype(_DTYPES[v]) if v in _DTYPES else _np.dtype(v)
    return v


def dtype_name(dt):
    return _np.dtype(dt).name if not repr(dt).endswith("bfloat16'>") else "bfloat16"


class OpDef(object):
    """One registered operator.

    Parameters
    ----------
    name : canonical op name (MXNet spelling, e.g. 'FullyConnected', 'broadcast_add')
    fn : fn(*inputs, rng=None, is_train=False, **attrs) -> jnp array | tuple.
        When ``num_aux`` > 0 the tuple carries ``num_outputs`` visible outputs
        followed by ``num_aux`` updated auxiliary-state arrays.
    arg_names : list of input names, or callable(attrs)->list (for variadic ops)
    aux_names : names of auxiliary-state inputs (BatchNorm moving stats); these are
        *trailing* entries of arg_names
    attr_types : dict attr -> parser used for defaults and JSON round-trips
    infer_shape : optional bidirectional callable(attrs, in_shapes)->(in, out, aux)
        where unknown entries are None; default uses jax.eval_shape (forward-only)
    infer_type : optional callable(attrs, in_dtypes)->(in, out, aux)
    needs_rng / train_aware : whether fn takes rng= / is_train=
    key_var_num_args : attr naming the input count for variadic ops ('num_args')
    aliases : extra registered names
    f32_inputs : names of inputs the op wants in float32 whatever the
        compute dtype of a mixed-precision policy
    """

    def __init__(self, name, fn, arg_names=("data",), aux_names=(), num_outputs=1,
                 attr_types=None, defaults=None, infer_shape=None, infer_type=None,
                 infer_shape_backward=None, input_init_attrs=None,
                 needs_rng=False, train_aware=False, key_var_num_args=None,
                 aliases=(), hidden=False, doc=None, is_loss=False,
                 layout_rule=None, layout_inputs=(0,), f32_inputs=()):
        self.name = name
        # inputs that stay float32 under a mixed-precision policy: a leaf
        # that feeds one of them directly is not cast to the compute dtype
        # (a router's weight, a scan's decay rates; TrainStep asks
        # executor._Lowered.f32_leaves)
        self.f32_inputs = tuple(f32_inputs)
        # how the executor's NHWC layout pass treats this op (see
        # executor._Lowered.run): None = rigid (inputs restored to logical
        # NCHW), 'aware' = fn accepts layout='NHWC' and executes channel-last
        # on the inputs listed in layout_inputs, 'aware_all' = same with every
        # input channel-last (Concat), 'transparent' = shape-agnostic, layout
        # flows through.  May be callable(attrs) -> one of those.
        self.layout_rule = layout_rule
        self.layout_inputs = tuple(layout_inputs)
        self.fn = fn
        self.is_loss = is_loss
        self._arg_names = arg_names
        self.aux_names = tuple(aux_names)
        self.num_aux = len(self.aux_names)
        self._num_outputs = num_outputs
        self.attr_types = dict(attr_types or {})
        self.defaults = dict(defaults or {})
        self._infer_shape = infer_shape
        self._infer_type = infer_type
        self.infer_shape_backward = infer_shape_backward
        # {arg_name: '__init__' json} applied to auto-created input variables
        # (parity: nnvm FSetInputVariableAttrs, e.g. LeakyReLU gamma=0.25,
        # reference src/operator/leaky_relu.cc:43-44)
        self.input_init_attrs = dict(input_init_attrs or {})
        self.needs_rng = needs_rng
        self.train_aware = train_aware
        self.key_var_num_args = key_var_num_args
        self.aliases = tuple(aliases)
        self.hidden = hidden
        self.doc = doc or (fn.__doc__ if fn is not None else None)

    # ------------------------------------------------------------------ meta
    def arg_names_for(self, attrs):
        names = self._arg_names(attrs) if callable(self._arg_names) else self._arg_names
        return list(names)

    def num_outputs_for(self, attrs):
        no = self._num_outputs
        return no(attrs) if callable(no) else no

    def normalize_attrs(self, attrs):
        """Apply defaults and parse string-valued attrs (JSON round-trip)."""
        out = dict(self.defaults)
        for k, v in attrs.items():
            if k in self.attr_types and (isinstance(v, str) or v is None
                                         or not isinstance(v, str)):
                try:
                    out[k] = self.attr_types[k](v)
                except (ValueError, SyntaxError, KeyError, TypeError):
                    out[k] = v
            else:
                out[k] = v
        return out

    # ---------------------------------------------------------------- compute
    def make_callable(self, attrs, is_train):
        """A positional-args-only closure over normalized attrs (jit-friendly)."""
        fn = self.fn
        kw = {}
        if self.train_aware:
            kw["is_train"] = is_train
        if self.needs_rng:
            def call(rng, *args):
                return fn(*args, rng=rng, **kw, **attrs)
        else:
            def call(*args):
                return fn(*args, **kw, **attrs)
        return call

    # -------------------------------------------------------------- inference
    def infer_shape(self, attrs, in_shapes):
        if self._infer_shape is not None:
            return self._infer_shape(attrs, list(in_shapes))
        return eval_shape_infer(self, attrs, in_shapes, None)[:2] + (None,)

    def infer_type(self, attrs, in_dtypes):
        if self._infer_type is not None:
            return self._infer_type(attrs, list(in_dtypes))
        known = [d for d in in_dtypes if d is not None]
        d = known[0] if known else _np.float32
        n_in = len(in_dtypes)
        return [d] * n_in, [d] * self.num_outputs_for(attrs), [d] * self.num_aux


def eval_shape_infer(op, attrs, in_shapes, in_dtypes):
    """Forward-only inference via jax.eval_shape (XLA's own shape rules)."""
    import jax
    import jax.numpy as jnp

    if any(s is None for s in in_shapes):
        n_out = op.num_outputs_for(attrs)
        return list(in_shapes), [None] * n_out, [None] * op.num_aux
    dts = in_dtypes or [_np.float32] * len(in_shapes)
    dts = [d if d is not None else _np.float32 for d in dts]
    call = op.make_callable(op.normalize_attrs(attrs), is_train=True)
    specs = [jax.ShapeDtypeStruct(tuple(int(x) for x in s), d)
             for s, d in zip(in_shapes, dts)]
    if op.needs_rng:
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        out = jax.eval_shape(call, key, *specs)
    else:
        out = jax.eval_shape(call, *specs)
    if not isinstance(out, (tuple, list)):
        out = (out,)
    shapes = [tuple(o.shape) for o in out]
    n_out = op.num_outputs_for(attrs)
    return (list(in_shapes), shapes[:n_out],
            shapes[n_out:n_out + op.num_aux] if op.num_aux else None)


def shape_unify(a, b):
    """Merge two partially-known shapes. ``None`` = fully unknown; a 0 entry
    is an unknown dim (MXNet's wildcard, e.g. RNN begin-state batch).  Returns
    the most specific shape, or None if both unknown; raises on conflict."""
    if a is None:
        return None if b is None else tuple(b)
    if b is None:
        return tuple(a)
    if len(a) != len(b):
        raise ValueError("shape rank mismatch %r vs %r" % (a, b))
    out = []
    for x, y in zip(a, b):
        if x == 0:
            out.append(y)
        elif y == 0 or x == y:
            out.append(x)
        else:
            raise ValueError("shape conflict %r vs %r" % (a, b))
    return tuple(out)


def shape_is_complete(s):
    return s is not None and 0 not in tuple(s)


def register(name, **kwargs):
    """Decorator: register ``fn`` as operator ``name``."""

    def deco(fn):
        op = OpDef(name, fn, **kwargs)
        OPS.register(name, op)
        for al in op.aliases:
            OPS.register(al, op)
        return fn

    return deco


def get_op(name):
    return OPS.get(name)


def list_ops():
    return OPS.list_names()


def attr_key(attrs):
    """Hashable canonical key for an attr dict."""
    def freeze(v):
        if isinstance(v, (list, tuple)):
            return tuple(freeze(x) for x in v)
        if isinstance(v, dict):
            return tuple(sorted((k, freeze(x)) for k, x in v.items()))
        if isinstance(v, _np.dtype):
            return v.name
        if isinstance(v, type):
            return v.__name__
        return v

    return tuple(sorted((k, freeze(v)) for k, v in attrs.items()))


# ------------------------------------------------------------- imperative JIT
_JIT_CACHE = {}

# mxsan RECOMPILE instrumentation + jit_cache_size gauge source for the
# imperative dispatch cache (one entry per (op, resolved attrs, is_train,
# sequence mesh))
from .. import sanitize as _san  # noqa: E402 — after _JIT_CACHE exists

_SAN_CACHE = _san.register_cache("ops.registry", kind="op",
                                 sizer=lambda: len(_JIT_CACHE))


def jitted(op, attrs, is_train=False):
    """Return the jit-compiled callable for (op, attrs, is_train)."""
    import jax

    # sequence-parallel mesh changes attention lowering (shard_map ring);
    # key it so toggling set_sequence_mesh never reuses a stale program
    from ..parallel import mesh as _mesh_mod
    seq_mesh, seq_axis = _mesh_mod.sequence_mesh()
    seq_key = None if seq_mesh is None else (
        _mesh_mod.mesh_cache_key(seq_mesh), seq_axis)
    key = (op.name, attr_key(attrs), bool(is_train), seq_key)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(op.make_callable(attrs, is_train))
        if _san._hbm_on or _san._cost_on:
            # per-program HBM/cost attribution: first call captures
            # memory_analysis()/cost_analysis() from the arguments it
            # compiles for; the cached entry keeps the wrapper, whose
            # steady-state cost is one flag read
            fn = _san.program_wrap("op.%s" % op.name, fn, cache=_SAN_CACHE)
        _JIT_CACHE[key] = fn
        _SAN_CACHE.miss({"op": op.name, "attrs": attr_key(attrs),
                         "is_train": bool(is_train), "seq_mesh": seq_key})
    return fn


def imperative_invoke(op_name, inputs, attrs=None, is_train=False, rng=None):
    """Run one op eagerly on jax arrays (parity: MXImperativeInvoke,
    src/c_api/c_api_ndarray.cc:323).  Returns a tuple of jax arrays
    (visible outputs + aux updates).  Under MXNET_ENGINE_TYPE=NaiveEngine
    every op blocks on its result (sync debugging, parity: naive_engine.cc);
    MXNET_ENGINE_NOJIT=1 bypasses the jit cache for op-level bisection."""
    from .. import engine as _engine
    from ..base import get_env
    op = get_op(op_name) if isinstance(op_name, str) else op_name
    attrs = op.normalize_attrs(attrs or {})
    if _engine.is_naive() and get_env("MXNET_ENGINE_NOJIT") == "1":
        fn = op.make_callable(attrs, is_train)
    else:
        fn = jitted(op, attrs, is_train)
    from .. import profiler as _prof
    profiling = _prof.is_running() and \
        _prof._state["mode"] in ("imperative", "all")
    if op.needs_rng:
        if rng is None:
            from .. import random as _random
            rng = _random.next_key()
        args = (rng,) + tuple(inputs)
    else:
        args = tuple(inputs)
    if profiling:
        import jax
        import time as _time
        t0 = _time.time()
        out = fn(*args)
        jax.block_until_ready(out)
        _prof.record_event(op.name, t0 * 1e6, (_time.time() - t0) * 1e6,
                           "imperative")
    else:
        out = fn(*args)
    if not isinstance(out, (tuple, list)):
        out = (out,)
    _engine.maybe_wait(out)
    return tuple(out), op
