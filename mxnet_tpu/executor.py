"""Executor — lowers a Symbol graph to XLA computations (parity: reference
include/mxnet/executor.h, src/executor/graph_executor.cc, python/mxnet/executor.py).

TPU-first replacement for the GraphExecutor pipeline (SURVEY.md §2.4):
- InitFullGraph/Gradient pass            → jax.vjp over the traced forward
- PlanMemory / InitDataEntryMemory       → XLA buffer assignment
- InitCachedOps / bulk exec segments     → one jit-compiled computation per
                                           (graph, shapes, is_train) — the whole
                                           graph IS one "segment"
- AttachOpExecs / dispatch               → tracing the registered jax op functions
- kWriteTo/kAddTo grad_req               → functional grads written or accumulated
                                           into the bound grad NDArrays
- group2ctx + _CrossDeviceCopy           → eager multi-device walk with device_put
                                           at ctx_group boundaries (model
                                           parallelism without SPMD; the sharded
                                           path lives in mxnet_tpu.parallel)

Training lowers through jax.vjp over the jitted graph: the forward executes
once (saving residuals — the reference's per-op workspaces), and backward runs
only the compiled pullback, for implicit or explicit head gradients alike.
The single-program fused step (forward+backward+update in one XLA computation)
is the TrainStep path in mxnet_tpu/train.py.
"""
from __future__ import annotations

import contextlib

import numpy as _np

from .base import MXNetError, string_types
from .context import Context, current_context
from . import ndarray as nd
from .ndarray import NDArray
from . import random as _random
from . import sanitize as _san

__all__ = ["Executor"]


def _node_uid(node, uid_map):
    u = uid_map.get(id(node))
    if u is None:
        u = len(uid_map)
        uid_map[id(node)] = u
    return u


def _make_scale_backward():
    """Identity forward / cotangent-times-scale backward.

    The loss heads (ops/loss.py) emit their FIXED reference gradient and
    ignore the incoming cotangent (SoftmaxOutput's ``out - onehot``
    semantics), so AMP loss scaling cannot ride the vjp seeds.  Instead
    ``_Lowered.run(head_grad_scale=...)`` wraps each loss head's data
    input in this op: everything BELOW the head — the whole backward
    chain in compute dtype — sees its cotangents multiplied by the traced
    scale, which is exactly "scale the loss before backward" (and the
    TPU-native generalisation of the reference's ``out_grad`` head-grad
    multiplier, softmax_output-inl.h)."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def scale_backward(x, s):
        return x

    def scale_backward_fwd(x, s):
        return x, s

    def scale_backward_bwd(s, g):
        return g * s.astype(g.dtype), jnp.zeros_like(s)

    scale_backward.defvjp(scale_backward_fwd, scale_backward_bwd)
    return scale_backward


# one process-wide instance (built on first use so importing the module
# does not import jax); dict memo, not a `global` rebind — this is reached
# from traced code, which must stay declaration-free
_SCALE_BACKWARD = {}


def _get_scale_backward():
    fn = _SCALE_BACKWARD.get("fn")
    if fn is None:
        fn = _SCALE_BACKWARD["fn"] = _make_scale_backward()
    return fn


class _Stage(object):
    """One pipeline stage of a partitioned symbol graph: a contiguous
    sub-range of the topological op order plus the variables it binds and
    the activation frontier it exchanges with its neighbours (see
    ``_Lowered.stage_partition``)."""

    __slots__ = ("index", "final", "nodes", "params", "aux", "inputs",
                 "carry_in", "carry_out")

    def __init__(self, index, final, nodes, params, aux, inputs,
                 carry_in, carry_out):
        self.index = index
        self.final = final
        self.nodes = nodes          # var + op nodes, original topo order
        self.params = params        # parameter names bound by this stage
        self.aux = aux              # aux (BN moving stat) names
        self.inputs = inputs        # data/label input names consumed here
        self.carry_in = carry_in    # value keys received from earlier stages
        self.carry_out = carry_out  # value keys handed to later stages

    def describe(self):
        return {"index": self.index, "final": self.final,
                "ops": sum(1 for n in self.nodes if not n.is_var),
                "params": list(self.params), "aux": list(self.aux),
                "inputs": list(self.inputs),
                "carry_in": len(self.carry_in),
                "carry_out": len(self.carry_out)}


class _Lowered(object):
    """The pure-functional form of a symbol graph."""

    def __init__(self, symbol):
        from .symbol import _topo
        self.symbol = symbol
        self.order = _topo([n for n, _ in symbol._outputs])
        self.uid = {}
        for n in self.order:
            _node_uid(n, self.uid)
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.out_keys = [(id(n), i) for n, i in symbol._outputs]
        # peephole: BatchNorm whose single consumer is Activation(relu) runs
        # as the fused _BatchNormReLU op (backward recomputes the relu mask
        # instead of saving the BN output — see ops/nn.py)
        consumers = {}
        for n in self.order:
            if n.is_var:
                continue
            for c, i in n.inputs:
                consumers.setdefault((id(c), i), []).append(n)
        outs = set(self.out_keys)
        self.fused_relu = {}
        for n in self.order:
            if n.is_var or n.op.name != "BatchNorm":
                continue
            if n.op.normalize_attrs(n.params).get("output_mean_var"):
                continue
            if (id(n), 0) in outs:
                continue
            cons = consumers.get((id(n), 0), [])
            if len(cons) != 1 or cons[0].is_var:
                continue
            act = cons[0]
            if act.op.name == "Activation" and \
                    act.op.normalize_attrs(act.params).get("act_type") \
                    == "relu":
                self.fused_relu[id(n)] = act
        # peephole: train-mode BatchNorm(fix_gamma) applied directly to a
        # graph input and consumed by exactly one Convolution (the ResNet
        # "bn_data -> conv0" stem) fuses to ops/nn.py input_bn_conv, whose
        # backward computes d(beta) without the backward-data convolution
        # into the C-channel input grid (~14% of the b32 train step; see
        # docs/perf.md).  Fires at run time only when the executor declares
        # the input variable gradient-free.
        self.stem_fuse = {}
        for b in self.order:
            if b.is_var or b.op.name != "BatchNorm":
                continue
            a = b.op.normalize_attrs(b.params)
            if (not a.get("fix_gamma", True) or a.get("output_mean_var")
                    or a.get("use_global_stats")
                    or a.get("layout") not in (None, "NCHW")):
                continue
            src, si = b.inputs[0]
            if not src.is_var or si != 0 or (id(b), 0) in outs:
                continue
            cons = consumers.get((id(b), 0), [])
            if len(cons) != 1 or cons[0].is_var:
                continue
            conv = cons[0]
            if conv.op.name != "Convolution" or conv.inputs[0] != (b, 0):
                continue
            ca = conv.op.normalize_attrs(conv.params)
            kernel = tuple(ca.get("kernel") or ())
            dilate = tuple(ca.get("dilate") or ()) or (1,) * len(kernel)
            if (len(kernel) != 2 or not ca.get("no_bias")
                    or int(ca.get("num_group") or 1) != 1
                    or any(d != 1 for d in dilate)
                    or ca.get("layout") not in (None, "NCHW")):
                continue
            self.stem_fuse[id(b)] = {
                "var": src.name, "conv": conv,
                "eps": float(a.get("eps", 1e-3)),
                "momentum": float(a.get("momentum", 0.9)),
                "kernel": kernel,
                "stride": tuple(ca.get("stride") or ()) or (1, 1),
                "pad": tuple(ca.get("pad") or ()) or (0, 0)}

    # ------------------------------------------------------ pipeline stages
    def _glue_edges(self):
        """Op-order index pairs (lo, hi) that must stay in one stage: the
        fusion peepholes (BN+relu, stem BN+conv) rewrite both members
        together, so a stage cut between them would change which programs
        the single-program step and the pipelined stages trace."""
        op_pos = {}
        for n in self.order:
            if not n.is_var:
                op_pos[id(n)] = len(op_pos)
        edges = []

        def edge(a_id, b_id):
            pa, pb = op_pos.get(a_id), op_pos.get(b_id)
            if pa is not None and pb is not None and pa != pb:
                edges.append((min(pa, pb), max(pa, pb)))
        for bn_id, act in self.fused_relu.items():
            edge(bn_id, id(act))
        for bn_id, info in self.stem_fuse.items():
            edge(bn_id, id(info["conv"]))
        return op_pos, edges

    def stage_partition(self, num_stages, input_names=(), param_sizes=None):
        """Partition the op sequence into ``num_stages`` contiguous stages
        (the GPipe layer split, rebuilt on the nnvm-style graph: PAPER.md
        §4a partitions the executor graph the same way).  The interleaved
        pipeline schedule passes ``num_stages = pp * v`` and assigns chunk
        ``k`` to device slice ``k % pp`` — the cut machinery is identical;
        only the placement convention differs (train.PipelineTrainStep).

        Cuts land only on glue-legal boundaries (no fusion pair straddles a
        stage edge) and balance the per-stage parameter footprint when
        ``param_sizes`` ({name: element count}) is given, op count
        otherwise.  Each variable is assigned to the stage that consumes
        it; a *parameter/aux* consumed by more than one stage has no single
        home device and is rejected (weight sharing across stages needs
        replication the pp axis exists to avoid).  Data/label inputs may
        feed any number of stages.  The activation frontier between stages
        s and s+1 is every value produced at or before s and consumed
        after s (symbol outputs ride the frontier to the final stage)."""
        input_names = set(input_names)
        op_nodes = [n for n in self.order if not n.is_var]
        if num_stages < 1:
            raise MXNetError("stage_partition: num_stages must be >= 1")
        if num_stages > len(op_nodes):
            raise MXNetError(
                "stage_partition: %d stages > %d ops in the graph"
                % (num_stages, len(op_nodes)))
        op_pos, glue = self._glue_edges()
        illegal = set()
        for lo, hi in glue:
            illegal.update(range(lo + 1, hi + 1))

        # per-op weight: parameters first consumed by this op (placement
        # follows first consumption), plus 1 so op-only regions still
        # spread across stages
        first_consumer = {}    # var name -> op position of first consumer
        for n in op_nodes:
            for c, _ in n.inputs:
                if c.is_var and c.name not in first_consumer:
                    first_consumer[c.name] = op_pos[id(n)]
        weights = [1.0] * len(op_nodes)
        if param_sizes:
            for name, pos in first_consumer.items():
                weights[pos] += float(param_sizes.get(name, 0))

        # greedy balanced cut: close each stage at the first legal boundary
        # past its share of the remaining weight, keeping one op per
        # remaining stage
        cuts = []
        pos = 0
        for s in range(num_stages - 1):
            remaining = sum(weights[pos:])
            target = remaining / (num_stages - s)
            acc = 0.0
            cut = None
            for k in range(pos, len(op_nodes) - (num_stages - 1 - s)):
                acc += weights[k]
                if acc >= target and (k + 1) not in illegal:
                    cut = k + 1
                    break
            if cut is None:
                # fall back to the first legal boundary that still leaves
                # enough ops for the remaining stages
                for k in range(pos, len(op_nodes) - (num_stages - 1 - s)):
                    if (k + 1) not in illegal:
                        cut = k + 1
                        break
            if cut is None:
                raise MXNetError(
                    "stage_partition: no legal cut for stage %d of %d "
                    "(fusion glue spans the remaining ops)"
                    % (s + 1, num_stages))
            cuts.append(cut)
            pos = cut
        bounds = [0] + cuts + [len(op_nodes)]

        def stage_of_op(p):
            for s in range(num_stages):
                if bounds[s] <= p < bounds[s + 1]:
                    return s
            raise MXNetError("unreachable")

        # value keys (producer, out_idx) consumed by each op; producer
        # stage for every non-var value
        prod_stage = {}
        for n in op_nodes:
            for i in range(n.op.num_outputs_for(n.params)):
                prod_stage[(id(n), i)] = stage_of_op(op_pos[id(n)])
        consumers = {}      # value key -> set of consuming stages
        var_stages = {}     # var name -> set of consuming stages
        for n in op_nodes:
            s = stage_of_op(op_pos[id(n)])
            for c, i in n.inputs:
                if c.is_var:
                    var_stages.setdefault(c.name, set()).add(s)
                else:
                    consumers.setdefault((id(c), i), set()).add(s)
        # symbol outputs must reach the final stage
        for k in self.out_keys:
            consumers.setdefault(k, set()).add(num_stages - 1)

        aux_set = set(self.aux_names)
        for name, stages in sorted(var_stages.items()):
            if name in input_names or len(stages) == 1:
                continue
            kind = "aux state" if name in aux_set else "parameter"
            raise MXNetError(
                "stage_partition: %s %s is consumed by stages %s — "
                "cross-stage weight sharing is not supported by the "
                "pipeline schedule" % (kind, name, sorted(stages)))

        # frontier after stage s: produced <= s, consumed > s; ordered by
        # producer topo position for a deterministic jit interface
        frontiers = []
        for s in range(num_stages - 1):
            keys = [k for k, cons in consumers.items()
                    if k in prod_stage and prod_stage[k] <= s
                    and any(cs > s for cs in cons)]
            keys.sort(key=lambda k: (self.uid[k[0]]
                                     if k[0] in self.uid else 0, k[1]))
            frontiers.append(keys)

        stages = []
        for s in range(num_stages):
            ops = set(id(n) for n in op_nodes[bounds[s]:bounds[s + 1]])
            svars = {name for name, st in var_stages.items() if s in st}
            nodes = [n for n in self.order
                     if (n.is_var and n.name in svars) or id(n) in ops]
            params = [n for n in self.arg_names
                      if n in svars and n not in input_names]
            aux = [n for n in self.aux_names if n in svars]
            inputs = [n for n in sorted(svars & input_names)]
            stages.append(_Stage(
                index=s, final=(s == num_stages - 1), nodes=nodes,
                params=params, aux=aux, inputs=inputs,
                carry_in=list(frontiers[s - 1]) if s else [],
                carry_out=list(frontiers[s]) if s < num_stages - 1 else []))
        return stages

    def _stem_run(self, node, values, nhwc, aux_updates, skip, arg_vals):
        """Run a fused input-BN + conv pair (see stem_fuse in __init__)."""
        import jax.numpy as jnp
        from .ops.nn import input_bn_conv
        info = self.stem_fuse[id(node)]
        xk = (id(node.inputs[0][0]), node.inputs[0][1])
        x = values[xk]
        if not hasattr(x, "ndim") or x.ndim != 4:
            return False
        x_cl = x if xk in nhwc else jnp.moveaxis(x, 1, -1)
        conv = info["conv"]
        beta = values[(id(node.inputs[2][0]), node.inputs[2][1])]
        # the conv's weight variable sits after the BN in topo order — its
        # values[] entry does not exist yet; resolve it from the arguments
        wvar = conv.inputs[1][0]
        w = values.get((id(wvar), conv.inputs[1][1]))
        if w is None:
            if not wvar.is_var or wvar.name not in arg_vals:
                return False
            w = arg_vals[wvar.name]
        out, mean, var = input_bn_conv(x_cl, beta, w, info["eps"],
                                       info["kernel"], info["stride"],
                                       info["pad"])
        mom = jnp.float32(info["momentum"])
        for pos, stat in ((3, mean), (4, var)):
            child = node.inputs[pos][0]
            if child.is_var:
                prev = values[(id(child), 0)]
                aux_updates[child.name] = prev * mom + \
                    stat.astype(prev.dtype) * (1 - mom)
        values[(id(conv), 0)] = out
        nhwc.add((id(conv), 0))
        skip.add(id(conv))
        return True

    def f32_leaves(self):
        """Names of the variables that feed, directly, an input an op wants
        in float32 under a mixed-precision policy (``OpDef.f32_inputs``)."""
        keep = set()
        for node in self.order:
            if node.is_var or not node.op.f32_inputs:
                continue
            names = node.op.arg_names_for(node.params)
            keep.update(child.name
                        for name, (child, _) in zip(names, node.inputs)
                        if child.is_var and name in node.op.f32_inputs)
        return keep

    def run(self, arg_vals, aux_vals, rng, is_train, collect=False,
            no_grad_inputs=(), head_grad_scale=None, stage=None,
            carry_vals=None):
        """Trace the graph: dict name->array in, (outputs, aux_updates) out.
        With collect=True also returns {internal_name: value} for every op
        output — the monitor's data, gathered from the ONE real execution.

        ``head_grad_scale`` (a traced scalar; AMP loss scaling) wraps every
        loss head's data input in the scale-backward identity so the whole
        backward chain below the heads sees scaled cotangents.

        ``stage`` (a ``_Stage`` from :meth:`stage_partition`) restricts the
        trace to that stage's node sub-range: ``carry_vals`` seeds the
        activation frontier received from the previous stage (logical-NCHW
        arrays, in ``stage.carry_in`` order) and the return becomes the
        3-tuple ``(outputs, aux_updates, carry_out)`` — ``outputs`` only on
        the final stage, ``carry_out`` restored to logical layout so the
        stage boundary is a deterministic interface regardless of the
        layout pass's channel-last tagging inside the stage.

        Layout pass (TPU-native; no reference analogue — the nnvm graph never
        needed one because cuDNN consumed NCHW directly): XLA:TPU inserts
        physical-layout copies around every convolution when the surrounding
        elementwise fusions run in logical NCHW (measured 1.5x step-time
        overhead on ResNet-50).  When MXNET_CONV_LAYOUT=NHWC (the default),
        activations flow channel-last between layout-aware ops (Convolution,
        Pooling, BatchNorm, Concat) and through shape-agnostic ops; rigid ops
        see logical NCHW restored.  Semantics are unchanged — every op's
        logical interface stays NCHW."""
        import jax
        import jax.numpy as jnp
        from .base import get_env
        use_nhwc = get_env("MXNET_CONV_LAYOUT", "NHWC") == "NHWC"
        stem_on = (use_nhwc and is_train and not collect
                   and bool(self.stem_fuse) and no_grad_inputs
                   and get_env("MXNET_STEM_FUSE", "1") == "1")
        values = {}
        nhwc = set()      # value keys currently stored channel-last
        aux_updates = {}
        collected = {}
        order = self.order
        if stage is not None:
            if collect:
                raise MXNetError("monitor collection is not supported on "
                                 "the pipeline stage path")
            order = stage.nodes
            for key, v in zip(stage.carry_in, carry_vals or ()):
                values[key] = v

        def is_arr(v):
            return hasattr(v, "ndim") and v.ndim >= 3

        def to_cl(v):
            return jnp.moveaxis(v, 1, -1)

        def to_cf(v):
            return jnp.moveaxis(v, -1, 1)

        skip = set()
        for node in order:
            if node.is_var:
                if node.name in arg_vals:
                    values[(id(node), 0)] = arg_vals[node.name]
                elif node.name in aux_vals:
                    values[(id(node), 0)] = aux_vals[node.name]
                else:
                    raise MXNetError("unbound variable %s" % node.name)
                continue
            if id(node) in skip:
                continue
            if stem_on and id(node) in self.stem_fuse \
                    and self.stem_fuse[id(node)]["var"] in no_grad_inputs:
                if self._stem_run(node, values, nhwc, aux_updates, skip,
                                  arg_vals):
                    continue
            # monitor mode needs true per-op internals — no fusion there
            fused_act = None if collect else self.fused_relu.get(id(node))
            op = node.op
            if fused_act is not None:
                from .ops.registry import get_op
                op = get_op("_BatchNormReLU")
            in_keys = [(id(c), i) for c, i in node.inputs]
            ins = [values[k] for k in in_keys]
            params = node.params
            out_cl = False
            if use_nhwc:
                rule = op.layout_rule
                if callable(rule):
                    rule = rule(params)
                # never second-guess a user-specified channel-last layout
                if rule in ("aware", "aware_all") and \
                        params.get("layout") not in (None, "NCHW"):
                    rule = None
                if rule in ("aware", "aware_all") and ins and is_arr(ins[0]):
                    li = (set(range(len(ins))) if rule == "aware_all"
                          else set(op.layout_inputs))

                    def place(j, v):
                        if not is_arr(v):
                            return v
                        tagged = in_keys[j] in nhwc
                        if j in li:          # activation input: channel-last
                            return v if tagged else to_cl(v)
                        return to_cf(v) if tagged else v
                    ins = [place(j, v) for j, v in enumerate(ins)]
                    params = dict(params, layout="NHWC")
                    out_cl = True
                elif rule == "transparent":
                    tags = [in_keys[j] in nhwc for j, v in enumerate(ins)
                            if is_arr(v)]
                    if tags and all(tags):
                        out_cl = True      # flow through unchanged
                    elif any(tags):        # mixed: restore logical layout
                        ins = [to_cf(v) if in_keys[j] in nhwc else v
                               for j, v in enumerate(ins)]
                else:
                    ins = [to_cf(v) if in_keys[j] in nhwc else v
                           for j, v in enumerate(ins)]
            if head_grad_scale is not None and is_train \
                    and getattr(op, "is_loss", False) and ins:
                # AMP: scale the gradient the head emits (the heads ignore
                # their incoming cotangent — reference loss semantics)
                ins = [_get_scale_backward()(ins[0], head_grad_scale)] \
                    + ins[1:]
            call = op.make_callable(params, is_train)
            # a node built under ``AttrScope(__scope__=...)`` runs under
            # that ``jax.named_scope``: metadata only, so that its device
            # operations, forward and backward, carry the layer's name
            scope = node.attr.get("__scope__")
            with jax.named_scope(scope) if scope \
                    else contextlib.nullcontext():
                if op.needs_rng:
                    sub = jax.random.fold_in(rng, _node_uid(node, self.uid))
                    out = call(sub, *ins)
                else:
                    out = call(*ins)
            if not isinstance(out, (tuple, list)):
                out = (out,)
            n_vis = op.num_outputs_for(node.params)
            for i in range(n_vis):
                values[(id(node), i)] = out[i]
                if out_cl and is_arr(out[i]):
                    nhwc.add((id(node), i))
                if collect:
                    nm = node.name + ("_output" if n_vis == 1
                                      else "_output%d" % i)
                    collected[nm] = to_cf(out[i]) \
                        if out_cl and is_arr(out[i]) else out[i]
            if fused_act is not None:
                # the relu consumer's value IS the fused output
                values[(id(fused_act), 0)] = out[0]
                if out_cl and is_arr(out[0]):
                    nhwc.add((id(fused_act), 0))
                skip.add(id(fused_act))
            if op.num_aux:
                names = op.arg_names_for(node.params)
                aux_pos = [i for i, nm in enumerate(names)
                           if nm in op.aux_names]
                for k, pos in enumerate(aux_pos):
                    child = node.inputs[pos][0]
                    if child.is_var and is_train:
                        aux_updates[child.name] = out[n_vis + k]
        if stage is not None:
            carry_out = [to_cf(values[k]) if k in nhwc else values[k]
                         for k in stage.carry_out]
            outputs = [to_cf(values[k]) if k in nhwc else values[k]
                       for k in self.out_keys] if stage.final else []
            return outputs, aux_updates, carry_out
        outputs = [to_cf(values[k]) if k in nhwc else values[k]
                   for k in self.out_keys]
        if collect:
            return outputs, aux_updates, collected
        return outputs, aux_updates


class Executor(object):
    """Bound computation (parity: mx.executor.Executor)."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        self._group2ctx = dict(group2ctx or {})
        self._low = _Lowered(symbol)
        self.arg_names = self._low.arg_names
        self.aux_names = self._low.aux_names

        self.arg_dict = self._dictify(args, self.arg_names, "args")
        self.aux_dict = self._dictify(aux_states, self.aux_names, "aux_states",
                                      allow_none=True)
        # grad request per arg
        if isinstance(grad_req, string_types):
            self.grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(self.arg_names, grad_req))
        else:
            self.grad_req = {n: grad_req.get(n, "null") for n in self.arg_names}
        self.grad_dict = self._dictify(args_grad, self.arg_names, "args_grad",
                                       allow_none=True, partial=True)
        for n, req in self.grad_req.items():
            if req == "null":
                self.grad_dict.pop(n, None)

        # pre-allocate output NDArrays (in-place updated on every forward,
        # parity: GraphExecutor output arrays).  Output dtypes follow from the
        # bound argument dtypes (infer_type), so bfloat16/float16 networks get
        # matching cotangent dtypes in the fused fwd+bwd.
        shapes = {n: a.shape for n, a in self.arg_dict.items()}
        _, out_shapes, _ = symbol.infer_shape_partial(**shapes)
        types = {n: a.dtype for n, a in self.arg_dict.items()}
        try:
            _, out_types, _ = symbol.infer_type(**types)
        except Exception:
            out_types = [None] * len(out_shapes)
        self._output_nds = []
        for s, t in zip(out_shapes, out_types):
            self._output_nds.append(
                nd.zeros(s if s else (1,), ctx=self._ctx,
                         dtype=t if t is not None else _np.float32))
        self._jit_cache = {}
        # mxsan RECOMPILE instrumentation + jit_cache_size gauge source:
        # every executor's per-instance cache is visible to the registry
        # (weakref-owned, so dead executors drop out of the gauge)
        self._san_cache = _san.register_cache(
            "executor", kind="executor", owner=self,
            sizer=lambda ex: len(ex._jit_cache),
            # _get_jit's inner jitted bodies (collision-proof names: the
            # raw-jit watcher exempts these process-wide)
            jit_names=("mxtpu_fwd", "mxtpu_grad", "mxtpu_walk_fwd",
                       "mxtpu_walk_grad"))
        self._monitor_cb = None
        self._pullback = None
        self._warned_default_heads = False
        self._multi_device = self._detect_multi_device()

    # ------------------------------------------------------------- bind utils
    def _dictify(self, data, names, what, allow_none=False, partial=False):
        if data is None:
            if allow_none:
                return {}
            raise MXNetError("%s must be provided" % what)
        if isinstance(data, dict):
            out = {}
            for n in names:
                if n in data:
                    out[n] = data[n]
                elif not (allow_none or partial):
                    raise MXNetError("missing %s entry %s" % (what, n))
            return out
        data = list(data)
        if len(data) != len(names) and not partial:
            raise MXNetError("%s length %d != expected %d"
                             % (what, len(data), len(names)))
        return {n: a for n, a in zip(names, data) if a is not None}

    def _detect_multi_device(self):
        if self._group2ctx:
            ctxs = set(self._group2ctx.values())
            if len(ctxs) > 1:
                return True
        devs = set()
        for a in list(self.arg_dict.values()) + list(self.aux_dict.values()):
            devs.add(a.context)
        return len(devs) > 1

    @staticmethod
    def simple_bind(symbol, ctx, grad_req="write", type_dict=None,
                    group2ctx=None, shared_exec=None, **kwargs):
        """Allocate argument/grad/aux arrays from inferred shapes and bind
        (parity: symbol.simple_bind / MXExecutorSimpleBind)."""
        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**kwargs)
        if arg_shapes is None:
            raise MXNetError("simple_bind: could not infer all shapes from %s"
                             % kwargs)
        arg_types = dict(type_dict or {})
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        shared_args = shared_exec.arg_dict if shared_exec else {}
        shared_grads = shared_exec.grad_dict if shared_exec else {}
        shared_aux = shared_exec.aux_dict if shared_exec else {}

        def node_ctx(name):
            if group2ctx:
                # find the variable's ctx_group attribute
                from .symbol import _topo
                for n in _topo([x for x, _ in symbol._outputs]):
                    if n.is_var and n.name == name:
                        grp = n.attr.get("ctx_group") or n.attr.get("__ctx_group__")
                        if grp and grp in group2ctx:
                            return group2ctx[grp]
            return ctx

        args = {}
        grads = {}
        for name, shape in zip(arg_names, arg_shapes):
            dt = arg_types.get(name, _np.float32)
            c = node_ctx(name)
            if name in shared_args and shared_args[name].shape == shape:
                args[name] = shared_args[name]
            else:
                args[name] = nd.zeros(shape, ctx=c, dtype=dt)
            req = grad_req if isinstance(grad_req, string_types) else \
                (grad_req[arg_names.index(name)]
                 if isinstance(grad_req, (list, tuple))
                 else grad_req.get(name, "null"))
            if req != "null":
                if name in shared_grads and shared_grads[name].shape == shape:
                    grads[name] = shared_grads[name]
                else:
                    grads[name] = nd.zeros(shape, ctx=c, dtype=dt)
        try:
            _, _, aux_types = symbol.infer_type(
                **{n: arg_types.get(n, _np.float32) for n in arg_names})
        except Exception:
            aux_types = [None] * len(aux_names)
        auxs = {}
        for name, shape, at in zip(aux_names, aux_shapes, aux_types):
            if name in shared_aux and shared_aux[name].shape == shape:
                auxs[name] = shared_aux[name]
            else:
                auxs[name] = nd.zeros(shape, ctx=ctx,
                                      dtype=at if at is not None else _np.float32)
        return Executor(symbol, ctx, args, grads, grad_req, auxs,
                        group2ctx=group2ctx, shared_exec=shared_exec)

    # -------------------------------------------------------------- properties
    @property
    def outputs(self):
        return self._output_nds

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self.arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self.arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self.aux_names]

    # ------------------------------------------------------------------ compute
    def _grad_arg_names(self):
        return [n for n in self.arg_names
                if self.grad_req.get(n, "null") != "null" and n in self.grad_dict]

    def _get_jit(self, kind):
        """kind: 'fwd_test' | 'fwd_train' (+ '_mon' suffix = monitor collect);
        'grad' | 'grad_mon' = the differentiated forward used under jax.vjp."""
        import jax
        # the sequence-parallel mesh is baked into traced programs (the
        # attention op lowers to shard_map over it), so it must key the cache:
        # toggling set_sequence_mesh would otherwise reuse stale lowerings
        from .parallel import mesh as mesh_mod
        from .base import get_env, trace_env_key
        seq_mesh, seq_axis = mesh_mod.sequence_mesh()
        # mirror flags are read at trace time, so they key the cache too —
        # toggling MXNET_BACKWARD_DO_MIRROR after an OOM must take effect
        mirror_key = (get_env("MXNET_BACKWARD_DO_MIRROR", "0"),
                      get_env("MXNET_BACKWARD_MIRROR_POLICY", ""))
        seq_key = None if seq_mesh is None else \
            (mesh_mod.mesh_cache_key(seq_mesh), seq_axis)
        # every env flag _Lowered.run consults while tracing
        # (layout/fusion passes, op A/B levers) — one shared registry,
        # base.TRACE_ENV_DEFAULTS, so a new lever can't forget to key
        # the cache
        env_key = trace_env_key()
        cache_key = (kind, seq_key, mirror_key, env_key)
        from . import telemetry as _tel
        fn = self._jit_cache.get(cache_key)
        if fn is not None:
            if _tel._enabled:
                _tel.counter("jit_cache_hit", kind=kind)
            return fn
        self._jit_last = "miss"
        if _tel._enabled:
            _tel.counter("jit_cache_miss", kind=kind)
        low = self._low
        collect = kind.endswith("_mon")

        if kind.startswith("walk"):
            # group2ctx multi-device walk, jitted (the placement transfers
            # lower to device-placement annotations inside ONE program).
            # Shapes are fixed after bind, so each kind traces once — the
            # model-parallel path stops paying per-batch retrace/dispatch
            # (parity: reference cached cross-device ops,
            # graph_executor.cc:544-676).
            if kind == "walk_grad":
                def f(gargs, oargs, aux, rng):
                    merged = dict(oargs)
                    merged.update(gargs)
                    o, aux_upd = self._walk(merged, aux, rng, True, False)
                    return tuple(o), aux_upd
                f.__name__ = "mxtpu_walk_grad"
                fn = jax.jit(f)
            else:
                is_train = kind == "walk_fwd_train"

                def fwd(args, aux, rng):
                    o, aux_upd = self._walk(args, aux, rng, is_train, False)
                    return tuple(o), aux_upd
                fwd.__name__ = "mxtpu_walk_fwd"
                fn = jax.jit(fwd)
        elif kind.startswith("fwd"):
            is_train = kind.startswith("fwd_train")

            def fwd(args, aux, rng):
                return low.run(args, aux, rng, is_train, collect=collect)
            # collision-proof program name: mxsan's raw-jit watcher
            # exempts this cache's inner names process-wide, so a bare
            # 'fwd'/'f' would also blind it to same-named user functions
            fwd.__name__ = "mxtpu_fwd"
            fn = jax.jit(fwd)
        else:
            # Differentiated forward: jax.vjp over this jitted function runs
            # the forward ONCE (with residuals saved) and hands back a
            # compiled pullback — backward never re-executes the forward,
            # matching the reference's stored-workspace semantics.
            def f(gargs, oargs, aux, rng):
                all_args = dict(oargs)
                all_args.update(gargs)
                res = low.run(all_args, aux, rng, True, collect=collect,
                              no_grad_inputs=frozenset(oargs))
                outs, aux_upd = res[0], res[1]
                coll = res[2] if collect else {}
                return tuple(outs), (aux_upd, coll)
            f.__name__ = "mxtpu_grad"
            from .base import get_env
            if get_env("MXNET_BACKWARD_DO_MIRROR", "0") == "1":
                # gradient mirroring -> rematerialisation: drop (some)
                # forward activations and recompute them in the pullback
                # (parity: reference graph_executor.cc:205-218 mirror pass;
                # TPU-natively this is jax.checkpoint trading FLOPs for HBM)
                policy = None
                if get_env("MXNET_BACKWARD_MIRROR_POLICY", "") == "dots":
                    policy = \
                        jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                f = jax.checkpoint(f, policy=policy)
            fn = jax.jit(f)
        if _san._hbm_on or _san._cost_on:
            # per-program HBM/cost attribution: the first call's concrete
            # arguments drive one lower+compile whose executable the
            # dispatch reuses; grad kinds first fire under jax.vjp with
            # tracers, where program_capture degrades to a silent skip
            fn = self._hbm_first_call(fn, kind)
        self._jit_cache[cache_key] = fn
        # named key fields make mxsan's RECOMPILE diff readable (built
        # from the SAME locals as cache_key, so key and report can never
        # diverge); the call also refreshes the registry-sourced
        # jit_cache_size gauge
        self._san_cache.miss({"kind": kind, "seq_mesh": seq_key,
                              "mirror": mirror_key, "trace_env": env_key})
        return fn

    def _hbm_first_call(self, fn, kind):
        """Wrap a fresh jit so its first invocation records the compiled
        program's memory analysis and/or cost analysis into mxsan's
        ledgers (best-effort: tracer arguments or lowering errors degrade
        to a skip), then step out of the way."""
        state = {"done": False}

        def hbm_first_call(*args):
            if not state["done"]:
                state["done"] = True
                _san.program_capture("executor.%s" % kind, fn, args)
            return fn(*args)
        return hbm_first_call

    def _check_default_heads(self):
        """Warn when implicit all-ones head gradients reach non-loss outputs
        (the reference errors unless every head is a loss op whose backward
        ignores the head gradient — ADVICE r1)."""
        if self._warned_default_heads:
            return
        def exempt(node):
            # loss heads define their own backward; BlockGrad's is identically
            # zero — implicit ones are harmless for both
            if node.is_var:
                return False
            return getattr(node.op, "is_loss", False) or \
                node.op.name == "BlockGrad"
        bad = [node.name for node, _ in self._symbol._outputs
               if not exempt(node)]
        if bad:
            import warnings
            warnings.warn(
                "backward() without out_grads on non-loss output(s) %s: "
                "gradients use implicit all-ones head gradients (the "
                "reference requires explicit out_grads here)" % bad,
                stacklevel=3)
        self._warned_default_heads = True

    @staticmethod
    def _mesh_replicate(nds):
        """With a sequence-parallel mesh active the jitted graph contains a
        shard_map over that mesh, so every input must live on the mesh:
        replicate single-device-committed values (attention shards them).
        The replicated array is written back into the NDArray, so steady-state
        steps pay no re-broadcast (device_put is a no-op once resident)."""
        from .parallel import mesh as mesh_mod
        mesh, _ = mesh_mod.sequence_mesh()
        if mesh is None:
            return {n: a.value for n, a in nds.items()}
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        rep = NamedSharding(mesh, PartitionSpec())
        out = {}
        for n, a in nds.items():
            v = jax.device_put(a.value, rep)
            if a._base is None:
                a._data = v  # commit: later forwards skip the broadcast
            out[n] = v
        return out

    def _arg_values(self):
        return self._mesh_replicate(dict(self.arg_dict))

    def _aux_values(self):
        return self._mesh_replicate(dict(self.aux_dict))

    def forward(self, is_train=False, **kwargs):
        """Run forward (parity: Executor::Forward).  With is_train=True the fused
        forward+backward computation runs (one XLA program for the whole step);
        gradients are cached for the subsequent backward() call."""
        from . import profiler as _profiler
        from . import telemetry as _tel
        mode = "train" if is_train else "test"
        with _profiler.Scope("executor.forward[%s]" % mode, "symbolic"), \
                _san.hot_region("executor.forward"), \
                _tel.span("executor.forward", cat="executor",
                          mode=mode) as sp:
            # jit="miss" on the registry's event marks the call that paid
            # trace+compile; steady-state calls run the cached computation
            self._jit_last = "hit"
            out = self._forward_impl(is_train, **kwargs)
            sp.tags["jit"] = self._jit_last
            return out

    def _forward_impl(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown forward input %s" % k)
            if isinstance(v, NDArray):
                self.arg_dict[k]._set_value(v.value)
            else:
                self.arg_dict[k][:] = v
        import jax
        rng = _random.next_key()
        self._pullback = None
        monitor = self._monitor_cb is not None
        collected = {}
        if self._multi_device:
            outs, aux_upd = self._forward_eager(is_train, rng,
                                                monitor=monitor)
        elif is_train and self._grad_arg_names():
            gnames = self._grad_arg_names()
            argv = self._arg_values()
            gargs = {n: argv[n] for n in gnames}
            oargs = {n: v for n, v in argv.items() if n not in gargs}
            fn = self._get_jit("grad_mon" if monitor else "grad")
            aux_vals = self._aux_values()
            outs, pullback, (aux_upd, collected) = jax.vjp(
                lambda ga: fn(ga, oargs, aux_vals, rng), gargs, has_aux=True)
            self._pullback = pullback
        else:
            fn = self._get_jit(("fwd_train" if is_train else "fwd_test")
                               + ("_mon" if monitor else ""))
            res = fn(self._arg_values(), self._aux_values(), rng)
            outs, aux_upd = res[0], res[1]
            if monitor:
                collected = res[2]
        # actual output devices (group2ctx outputs may live off the bind ctx;
        # backward() must place cotangents where the pullback residuals are)
        self._out_devices = [next(iter(v.devices()))
                             if hasattr(v, "devices") else None for v in outs]
        for ndarr, v in zip(self._output_nds, outs):
            ndarr._set_value(v)
        if is_train:
            for name, v in aux_upd.items():
                if name in self.aux_dict:
                    self.aux_dict[name]._set_value(v)
        if collected:
            # monitor collection is an opt-in diagnostic — its callback
            # may sync freely (mxsan: a planned transfer, not a finding)
            with _san.allow_sync("monitor collection"):
                for name, val in collected.items():
                    self._monitor_cb(name, NDArray(val))
        from . import engine as _engine
        from . import profiler as _profiler
        from . import telemetry as _tel
        if _engine.is_naive() or _profiler.is_running() or _tel._enabled:
            # sync so errors surface here (NaiveEngine) and the profiler/
            # telemetry spans reflect device time, not dispatch time
            import jax as _jax
            with _san.allow_sync("telemetry/naive-engine device sync"):
                _jax.block_until_ready(outs)
        return self._output_nds

    def backward(self, out_grads=None):
        """Accumulate gradients into bound grad arrays (parity:
        Executor::Backward; grad_req write/add semantics).  Runs only the
        pullback of the last forward(is_train=True) — the forward is never
        re-executed, and stochastic ops (Dropout) reuse the masks saved in
        the forward's residuals, whether out_grads is implicit or explicit."""
        from . import profiler as _profiler
        from . import telemetry as _tel
        with _profiler.Scope("executor.backward", "symbolic"), \
                _san.hot_region("executor.backward"), \
                _tel.span("executor.backward", cat="executor"):
            return self._backward_impl(out_grads)

    def _backward_impl(self, out_grads=None):
        gnames = self._grad_arg_names()
        if not gnames:
            return
        if out_grads is None:
            self._check_default_heads()
            import jax
            devs = getattr(self, "_out_devices", None) or \
                [None] * len(self._output_nds)
            ogs = tuple(
                jax.device_put(_ones_like_val(o), dev) if dev is not None
                else _ones_like_val(o)
                for o, dev in zip(self._output_nds, devs))
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            # cotangents must live on their output's device (group2ctx
            # model parallelism: outputs may sit on different devices)
            import jax
            devs = getattr(self, "_out_devices", None) or \
                [None] * len(out_grads)
            ogs = []
            for g, dev in zip(out_grads, devs):
                gv = g.value
                if dev is not None and hasattr(gv, "devices") \
                        and dev not in gv.devices():
                    gv = jax.device_put(gv, dev)
                ogs.append(gv)
            ogs = tuple(ogs)
        if self._pullback is None:
            raise MXNetError(
                "backward() requires a preceding forward(is_train=True)")
        grads = self._pullback(ogs)[0]
        for name in gnames:
            req = self.grad_req[name]
            tgt = self.grad_dict[name]
            g = grads[name]
            if req == "add":
                # sequence-mesh training hands back mesh-committed grads;
                # bring them to the accumulator's device before mixing
                tv = tgt.value
                if hasattr(g, "devices") and hasattr(tv, "devices") \
                        and g.devices() != tv.devices():
                    import jax as _jax
                    g = _jax.device_put(g, next(iter(tv.devices())))
                tgt._set_value(tv + g)
            elif req == "write":
                tgt._set_value(g)
        from . import engine as _engine
        from . import profiler as _profiler
        from . import telemetry as _tel
        if _engine.is_naive() or _profiler.is_running() or _tel._enabled:
            import jax as _jax
            with _san.allow_sync("telemetry/naive-engine device sync"):
                _jax.block_until_ready([g for g in grads.values()])

    def _forward_eager(self, is_train, rng, monitor=False):
        """Eager multi-device walk for group2ctx model parallelism: every op runs
        on the device of its (committed) inputs; ctx_group changes insert
        device transfers (parity: PlaceDevice + _CrossDeviceCopy)."""
        import jax
        vals = self._arg_values()
        aux_vals = self._aux_values()
        gnames = self._grad_arg_names() if is_train else []
        if gnames and not monitor:
            # one walk only: jax.vjp over the JITTED walk evaluates the
            # primal (device-placed, incl. the _CrossDeviceCopy transfers)
            # once compiled and hands back the pullback for backward() —
            # no per-batch retrace (VERDICT r3 weak-item 4)
            primals = {n: vals[n] for n in gnames}
            oargs = {n: v for n, v in vals.items() if n not in primals}
            fn = self._get_jit("walk_grad")
            outs, vjp_fn, aux_updates = jax.vjp(
                lambda ga: fn(ga, oargs, aux_vals, rng), primals,
                has_aux=True)
            self._pullback = vjp_fn
            return list(outs), aux_updates
        if not monitor:
            fn = self._get_jit("walk_fwd_train" if is_train
                               else "walk_fwd_test")
            outs, aux_updates = fn(vals, aux_vals, rng)
            outs = list(outs)
        else:
            outs, aux_updates = self._walk(vals, aux_vals, rng, is_train,
                                           monitor)
        if gnames:
            # monitor attached: the monitored walk ran eagerly above; trace
            # a second walk for the pullback
            def f(gargs):
                merged = dict(vals)
                merged.update(gargs)
                o, _ = self._walk(merged, aux_vals, rng, True, False)
                return tuple(o)
            primals = {n: vals[n] for n in gnames}
            _, vjp_fn = jax.vjp(f, primals)
            self._pullback = vjp_fn
        return outs, aux_updates

    def _walk(self, vals, aux_vals, rng, is_train, monitor):
        """Topo walk executing each op on its ctx_group's device, inserting
        transfers at group boundaries.  Works on concrete arrays (eager
        forward) and under jax tracing (the vjp closure)."""
        import jax
        low = self._low

        def want_dev(node):
            grp = node.attr.get("ctx_group") or node.attr.get("__ctx_group__")
            if grp and grp in self._group2ctx:
                return self._group2ctx[grp].jax_device()
            return None

        values = {}
        aux_updates = {}
        for node in low.order:
            if node.is_var:
                if node.name in vals:
                    values[(id(node), 0)] = vals[node.name]
                elif node.name in aux_vals:
                    values[(id(node), 0)] = aux_vals[node.name]
                else:
                    raise MXNetError("unbound variable %s" % node.name)
                continue
            tgt = want_dev(node)
            ins = []
            for c, i in node.inputs:
                v = values[(id(c), i)]
                if tgt is not None:
                    if isinstance(v, jax.core.Tracer):
                        # under the vjp trace: always constrain placement
                        v = jax.device_put(v, tgt)
                    elif hasattr(v, "devices") and tgt not in v.devices():
                        v = jax.device_put(v, tgt)
                ins.append(v)
            call = node.op.make_callable(node.params, is_train)
            if node.op.needs_rng:
                out = call(jax.random.fold_in(rng, _node_uid(node, low.uid)),
                           *ins)
            else:
                out = call(*ins)
            if not isinstance(out, (tuple, list)):
                out = (out,)
            n_vis = node.op.num_outputs_for(node.params)
            for i in range(n_vis):
                values[(id(node), i)] = out[i]
                if monitor:
                    nm = node.name + ("_output" if n_vis == 1
                                      else "_output%d" % i)
                    self._monitor_cb(nm, NDArray(out[i]))
            if node.op.num_aux and is_train:
                names = node.op.arg_names_for(node.params)
                aux_pos = [i for i, nm in enumerate(names)
                           if nm in node.op.aux_names]
                for k, pos in enumerate(aux_pos):
                    child = node.inputs[pos][0]
                    if child.is_var:
                        aux_updates[child.name] = out[n_vis + k]
        return [values[k] for k in low.out_keys], aux_updates

    # ---------------------------------------------------------------- utility
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                self.arg_dict[name]._set_value(
                    nd.array(arr).astype(self.arg_dict[name].dtype).value
                    if not isinstance(arr, NDArray) else arr.value)
            elif not allow_extra_params:
                raise MXNetError("unknown arg %s" % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    self.aux_dict[name]._set_value(
                        arr.value if isinstance(arr, NDArray)
                        else nd.array(arr).value)
                elif not allow_extra_params:
                    raise MXNetError("unknown aux %s" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind with new input shapes, sharing parameter arrays (parity:
        executor.reshape; XLA recompiles per shape, parameters are shared)."""
        new_shapes = {n: a.shape for n, a in self.arg_dict.items()}
        new_shapes.update(kwargs)
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        if arg_shapes is None:
            raise MXNetError("reshape: cannot infer shapes")
        args = {}
        for name, shape in zip(self.arg_names, arg_shapes):
            cur = self.arg_dict[name]
            args[name] = cur if tuple(cur.shape) == tuple(shape) else \
                nd.zeros(shape, ctx=cur.context, dtype=cur.dtype)
        grads = {}
        for name, arr in self.grad_dict.items():
            shape = arg_shapes[self.arg_names.index(name)]
            grads[name] = arr if tuple(arr.shape) == tuple(shape) else \
                nd.zeros(shape, ctx=arr.context, dtype=arr.dtype)
        auxs = {}
        for name, shape in zip(self.aux_names, aux_shapes):
            cur = self.aux_dict[name]
            auxs[name] = cur if tuple(cur.shape) == tuple(shape) else \
                nd.zeros(shape, ctx=cur.context, dtype=cur.dtype)
        return Executor(self._symbol, self._ctx, args, grads, self.grad_req,
                        auxs, group2ctx=self._group2ctx)

    def set_monitor_callback(self, callback):
        """Install per-op output monitor (parity: MXExecutorSetMonitorCallback).
        Stats are collected from the one real execution (the lowered graph
        returns every internal op output alongside the heads) — no second
        pass, no divergent RNG."""
        self._monitor_cb = callback

    def debug_str(self):
        return self._symbol.debug_str()


def _ones_like_val(ndarr):
    import jax.numpy as jnp
    v = ndarr.value if isinstance(ndarr, NDArray) else ndarr
    return jnp.ones(v.shape, v.dtype)
