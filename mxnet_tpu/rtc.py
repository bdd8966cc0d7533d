"""Runtime kernel compilation (parity: reference python/mxnet/rtc.py MXRtc —
user-supplied CUDA source JIT-compiled and pushed on NDArrays; SURVEY.md §7
maps this to runtime **Pallas** compilation on TPU).

The reference takes CUDA C source strings; TPU-natively the user writes a
Pallas kernel body (a Python function over input/output Refs), which is
vastly safer and composes with jit/vjp.  The ``push`` call mirrors the
reference's: run the kernel on concrete NDArrays, writing the outputs.

Example::

    def kern(x_ref, y_ref, out_ref):
        out_ref[:] = x_ref[:] * 2.0 + y_ref[:]

    rtc = mx.rtc.Rtc("axpb", ["x", "y"], ["out"], kern)
    rtc.push([x_nd, y_nd], [out_nd])
"""
from __future__ import annotations

from .base import MXNetError

__all__ = ["Rtc"]


class Rtc(object):
    """A runtime-compiled Pallas kernel bound to named inputs/outputs."""

    def __init__(self, name, input_names, output_names, kernel,
                 grid=None, interpret=None):
        self.name = name
        self.input_names = list(input_names)
        self.output_names = list(output_names)
        self.kernel = kernel
        self.grid = grid
        self._interpret = interpret
        self._compiled = {}

    def _get(self, out_shapes, out_dtypes, interpret):
        import jax
        from jax.experimental import pallas as pl
        key = (tuple(out_shapes), tuple(str(d) for d in out_dtypes),
               interpret)
        fn = self._compiled.get(key)
        if fn is None:
            shapes = [jax.ShapeDtypeStruct(s, d)
                      for s, d in zip(out_shapes, out_dtypes)]
            kwargs = {}
            if self.grid is not None:
                kwargs["grid"] = self.grid
            call = pl.pallas_call(
                self.kernel,
                out_shape=shapes if len(shapes) > 1 else shapes[0],
                interpret=interpret, **kwargs)
            fn = jax.jit(call)
            self._compiled[key] = fn
        return fn

    def push(self, ins, outs, grid_dim_x=None, grid_dim_y=None,
             grid_dim_z=None, block_dim_x=None, block_dim_y=None,
             block_dim_z=None):
        """Run the kernel (parity: MXRtcPush).  CUDA grid/block arguments
        are accepted for signature compatibility and ignored — Pallas grids
        are set at construction; XLA owns the launch geometry."""
        if len(ins) != len(self.input_names):
            raise MXNetError("%s expects %d inputs, got %d"
                             % (self.name, len(self.input_names), len(ins)))
        if len(outs) != len(self.output_names):
            raise MXNetError("%s expects %d outputs, got %d"
                             % (self.name, len(self.output_names),
                                len(outs)))
        # the kernel runs where its inputs live.  Mosaic compiles for the
        # TPU only, so inputs on a host device (mx.cpu(), or any context
        # under the JAX_PLATFORMS=cpu harness) go through the Pallas
        # interpreter; inputs on a chip are always compiled.
        interpret = self._interpret
        if interpret is None:
            interpret = (list(ins) or list(outs))[0].context \
                .jax_device().platform == "cpu"
        fn = self._get([o.shape for o in outs], [o.dtype for o in outs],
                       bool(interpret))
        res = fn(*[i.value for i in ins])
        if not isinstance(res, (tuple, list)):
            res = (res,)
        for o, v in zip(outs, res):
            o._set_value(v)
        return outs
