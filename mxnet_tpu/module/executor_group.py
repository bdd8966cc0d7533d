"""DataParallelExecutorGroup — the data-parallel heart (parity: reference
python/mxnet/module/executor_group.py:77-655).

TPU mapping: one executor per context; each executor is a single XLA computation
on its device, dispatched asynchronously so devices run concurrently (the
reference gets concurrency from the dependency engine; JAX's async dispatch plays
that role).  Batches are sliced along axis 0 by workload, gradients stay
per-device for the kvstore/updater to aggregate (SURVEY.md §3.1).
"""
from __future__ import annotations

import logging

import numpy as np

from ..base import MXNetError
from .. import ndarray as nd
from ..io import DataDesc

__all__ = ["DataParallelExecutorGroup", "_split_input_slice"]


def _split_input_slice(batch_size, work_load_list):
    """Split batch into per-device slices by workload (parity:
    executor_manager._split_input_slice / executor_group.decide_slices)."""
    total = sum(work_load_list)
    if batch_size < len(work_load_list):
        raise ValueError("batch size must be larger than the device count")
    slices = []
    start = 0
    for i, wl in enumerate(work_load_list):
        if i == len(work_load_list) - 1:
            end = batch_size
        else:
            end = start + int(round(batch_size * wl / total))
        slices.append(slice(start, end))
        start = end
    return slices


class DataParallelExecutorGroup(object):
    def __init__(self, symbol, contexts, workload, data_shapes,
                 label_shapes, param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=logging, fixed_param_names=None,
                 grad_req="write", state_names=None):
        self.symbol = symbol
        self.contexts = contexts
        self.workload = workload if workload else [1] * len(contexts)
        self.param_names = param_names
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.logger = logger
        self.fixed_param_names = set(fixed_param_names or [])
        self.state_names = list(state_names or [])
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()
        self.execs = []
        self.shared_group = shared_group
        self._default_grad_req = grad_req
        self.batch_size = None
        self.slices = None
        self.data_names = None
        self.label_names = None
        self.data_shapes = None
        self.label_shapes = None
        self.bind_exec(data_shapes, label_shapes, shared_group)

    # ------------------------------------------------------------------ bind
    def _grad_req_dict(self):
        req = {}
        for name in self.arg_names:
            if not self.for_training:
                req[name] = "null"
            elif name in self.fixed_param_names:
                req[name] = "null"
            elif name in self.param_names:
                req[name] = self._default_grad_req
            elif name in (self.data_names or []):
                req[name] = self._default_grad_req if self.inputs_need_grad \
                    else "null"
            else:
                req[name] = "null"
        return req

    def bind_exec(self, data_shapes, label_shapes, shared_group=None,
                  reshape=False):
        """Bind one executor per context with sliced shapes (parity:
        executor_group.bind_exec/_bind_ith_exec)."""
        self.data_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                            for d in data_shapes]
        self.label_shapes = None if not label_shapes else \
            [l if isinstance(l, DataDesc) else DataDesc(*l)
             for l in label_shapes]
        self.data_names = [d.name for d in self.data_shapes]
        self.label_names = [] if self.label_shapes is None else \
            [l.name for l in self.label_shapes]
        batch_axis = 0
        self.batch_size = self.data_shapes[0].shape[batch_axis]
        self.slices = _split_input_slice(self.batch_size, self.workload)
        grad_req = self._grad_req_dict()
        # capture before reset: reshape() shares with self's old executors
        shared_execs = shared_group.execs if shared_group is not None else None
        self.execs = []
        for i, ctx in enumerate(self.contexts):
            sl = self.slices[i]
            nrows = sl.stop - sl.start
            shapes = {}
            for d in self.data_shapes:
                shapes[d.name] = (nrows,) + tuple(d.shape[1:])
            if self.label_shapes:
                for l in self.label_shapes:
                    shapes[l.name] = (nrows,) + tuple(l.shape[1:])
            shared_exec = None
            if shared_execs is not None:
                shared_exec = shared_execs[i]
            ex = self.symbol.simple_bind(ctx=ctx, grad_req=grad_req,
                                         shared_exec=shared_exec, **shapes)
            self.execs.append(ex)
        # per-param lists of per-device arrays (parity: param_arrays)
        self.param_arrays = [[ex.arg_dict[name] for ex in self.execs]
                             for name in self.param_names
                             if name in self.execs[0].arg_dict]
        self.grad_arrays = [[ex.grad_dict.get(name) for ex in self.execs]
                            for name in self.param_names
                            if name in self.execs[0].arg_dict]
        self.aux_arrays = [[ex.aux_dict[name] for ex in self.execs]
                           for name in self.aux_names]

    def reshape(self, data_shapes, label_shapes):
        """Re-bind for new batch shapes, sharing parameters (parity:
        executor_group.reshape; XLA recompiles per shape, params shared)."""
        if data_shapes == self.data_shapes and \
                label_shapes == self.label_shapes:
            return
        self.bind_exec(data_shapes, label_shapes, shared_group=self)

    # ------------------------------------------------------------ parameters
    def set_params(self, arg_params, aux_params):
        for ex in self.execs:
            ex.copy_params_from(arg_params, aux_params,
                                allow_extra_params=True)

    def get_params(self, arg_params, aux_params):
        """Weighted-merge per-device params back into dicts (parity:
        executor_group.get_params; devices hold identical copies so take [0])."""
        for name, block in zip(
                [n for n in self.param_names
                 if n in self.execs[0].arg_dict],
                self.param_arrays):
            arg_params[name] = block[0].copy()
        for name, block in zip(self.aux_names, self.aux_arrays):
            aux_params[name] = block[0].copy()

    # ------------------------------------------------------------- computation
    def _load_batch(self, data, label):
        """Stage batch slices into every executor's bound input arrays
        (parity: _load_data/_load_label)."""
        for i, ex in enumerate(self.execs):
            sl = self.slices[i]
            for name, arr in zip(self.data_names, data):
                ex.arg_dict[name]._set_value(
                    arr[sl.start:sl.stop].value
                    if arr.context == ex.arg_dict[name].context else
                    arr[sl.start:sl.stop].copyto(
                        ex.arg_dict[name].context).value)
            if label is not None:
                for name, arr in zip(self.label_names, label):
                    if name in ex.arg_dict:
                        ex.arg_dict[name]._set_value(
                            arr[sl.start:sl.stop].copyto(
                                ex.arg_dict[name].context).value
                            if arr.context != ex.arg_dict[name].context
                            else arr[sl.start:sl.stop].value)

    def forward(self, data_batch, is_train=None):
        """Scatter batch slices and run each device's computation (parity:
        executor_group.forward + _load_data/_load_label).  All slices are
        staged before any device's computation is dispatched."""
        if is_train is None:
            is_train = self.for_training
        data = data_batch.data
        label = data_batch.label if self.label_shapes else None
        self._load_batch(data, label)
        for ex in self.execs:
            ex.forward(is_train=is_train)

    def backward(self, out_grads=None):
        assert self.for_training, "re-bind with for_training=True to backward"
        for i, ex in enumerate(self.execs):
            og = None
            if out_grads is not None:
                og = [g[self.slices[i].start:self.slices[i].stop]
                      for g in out_grads]
            ex.backward(og)

    def get_outputs(self, merge_multi_context=True):
        """Gather outputs (parity: executor_group.get_outputs)."""
        outputs = [[ex.outputs[i] for ex in self.execs]
                   for i in range(len(self.execs[0].outputs))]
        if merge_multi_context:
            return [outs[0] if len(outs) == 1 else nd.concatenate(outs, axis=0)
                    for outs in outputs]
        return outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        grads = [[ex.grad_dict[name] for ex in self.execs]
                 for name in self.data_names]
        if merge_multi_context:
            return [g[0] if len(g) == 1 else nd.concatenate(g, axis=0)
                    for g in grads]
        return grads

    def get_states(self, merge_multi_context=True):
        """Recurrent-state arrays (parity: executor_group get_states)."""
        states = [[ex.arg_dict[name] for ex in self.execs]
                  for name in self.state_names]
        if merge_multi_context:
            return [s[0] if len(s) == 1 else nd.concatenate(s, axis=0)
                    for s in states]
        return states

    def set_states(self, states=None, value=None):
        """Assign recurrent-state inputs: per-device structure, a merged
        full-batch array (sliced across executors like _load_general), or a
        scalar fill (parity: executor_group set_states)."""
        if states is not None:
            assert value is None
            for name, blocks in zip(self.state_names, states):
                if not isinstance(blocks, (list, tuple)):
                    blocks = [blocks]
                if len(blocks) == 1 and len(self.execs) > 1:
                    # merged array: slice the batch across executors
                    merged = blocks[0]
                    for ex, sl in zip(self.execs, self.slices):
                        ex.arg_dict[name][:] = merged[sl.start:sl.stop]
                else:
                    for ex, block in zip(self.execs, blocks):
                        ex.arg_dict[name][:] = block
        else:
            assert value is not None
            for name in self.state_names:
                for ex in self.execs:
                    ex.arg_dict[name][:] = value

    def update_metric(self, eval_metric, labels):
        """(parity: executor_group.update_metric)"""
        outputs = self.get_outputs(merge_multi_context=True)
        eval_metric.update(labels, outputs)

    def install_monitor(self, mon):
        for ex in self.execs:
            mon.install(ex)
