"""Fused SPMD training step — the TPU-native execution core.

The reference trains by dispatching per-op kernels through the threaded engine
and synchronising gradients through a parameter server (push/pull:
src/kvstore/kvstore_dist.h:28-318, device reduce: src/kvstore/comm.h:200-320,
optimizer step: python/mxnet/optimizer.py).  On TPU the whole training step —
forward, backward, optimizer update, AND the cross-device gradient reduction —
is ONE jit-compiled XLA computation over a ``jax.sharding.Mesh``:

- gradient pass:  ``jax.vjp`` over the lowered symbol graph (the reference's
  nnvm Gradient pass, executed symbolically at trace time);
- reduction:      batch inputs are sharded over the ``dp`` mesh axis and
  parameters are replicated (or sharded over ``tp``); XLA inserts the
  all-reduce over ICI automatically — no host transfers, no parameter server;
- update:         the fused optimizer math from ops/optimizer_ops.py is inlined
  into the same computation, so weights never leave HBM between steps;
- memory:         parameter/optimizer/aux buffers are donated (the XLA-level
  analogue of the reference's in-place kWriteInplace update), and optional
  rematerialisation (``remat=True``) trades FLOPs for HBM — the TPU-native
  ``MXNET_BACKWARD_DO_MIRROR`` (reference src/executor/graph_executor.cc:205-218).

The Module/Executor layer remains the API-compatible surface; TrainStep is the
performance path used by bench.py, examples, and the dist_tpu kvstore.
"""
from __future__ import annotations

import numpy as _np

from .base import MXNetError, trace_env_key
from . import ndarray as nd
from . import random as _random
from . import sanitize as _san
from . import telemetry as _tel
from .parallel.placement import PlacementPlan, normalize_zero
from .parallel import placement as _placement

__all__ = ["TrainStep", "EvalStep", "PipelineTrainStep",
           "pipeline_bubble_fraction"]


def pipeline_bubble_fraction(pp, microbatches, interleave=1):
    """Idle-slot share of the executed pipeline schedule under the
    equal-cost slot model.  GPipe and 1F1B both pay the fill/drain ramp
    once per wave — ``(pp - 1) / (pp - 1 + M)``, shrinking as the
    microbatch count grows (1F1B's win is activation memory, not the
    bubble).  The interleaved schedule cuts ``v = interleave`` virtual
    chunks per device slice, so each ramp costs one chunk (1/v of a
    stage) and the bubble drops to ``(pp - 1) / ((pp - 1) + v * M)``.
    The executed dispatch schedule is asserted against this closed form
    at plan-build time (parallel/schedule.py simulate)."""
    return float(pp - 1) / float(pp - 1 + interleave * microbatches)


def _pspec(*names):
    from jax.sharding import PartitionSpec
    return PartitionSpec(*names)


# flat (dp, chunk) layout of the gradient bucket and the level-3
# parameters: one implementation, in the placement plan module
# (parallel/placement.py)
_chunk_rows = _placement.chunk_rows
_flat_shards = _placement.flat_shards
_from_flat_shards = _placement.from_flat


def _host_init(symbol, low, param_names, aux_names, data_shapes,
               label_shapes, initializer, seed, who):
    """Host-side parameter/aux initialisation shared by TrainStep and
    PipelineTrainStep.init: initialise on the cpu context (each
    initializer is a handful of tiny imperative ops, not worth a device
    dispatch apiece) — the finished tensors move to the devices in one
    hop at placement time."""
    from . import initializer as init_mod
    if initializer is None:
        initializer = init_mod.Xavier(magnitude=2.0)
    shapes = dict(data_shapes)
    if label_shapes:
        shapes.update(label_shapes)
    arg_shapes, _, aux_shapes = symbol.infer_shape(**shapes)
    if arg_shapes is None:
        raise MXNetError("%s.init: shape inference incomplete" % who)
    name2shape = dict(zip(low.arg_names, arg_shapes))
    _random.seed(seed)
    params = {}
    from .context import cpu as _cpu_ctx
    attrs = symbol.attr_dict()
    with _cpu_ctx():
        for n in param_names:
            arr = nd.zeros(name2shape[n])
            initializer(init_mod.InitDesc(n, attrs.get(n)), arr)
            params[n] = arr.value
    aux = {}
    for n, shape in zip(aux_names, aux_shapes):
        aux[n] = _np.ones(shape, _np.float32) \
            if ("moving_var" in n or "_var" in n) \
            else _np.zeros(shape, _np.float32)
    return params, aux


_flat_np = _placement.flat_np


def _scale_state_to_host(step):
    """Loss-scale state as host scalars (checkpoint export), or None
    without a policy — shared by TrainStep and PipelineTrainStep.
    Syncs three scalars; checkpoint-time only."""
    if not step._has_scale:
        return None
    import jax
    state = step._scale_state_dev()
    with _san.allow_sync("checkpoint loss-scale export"):
        host = jax.device_get(state)
    return {k: float(v) if k == "scale" else int(v)
            for k, v in host.items()}


def _seq_replicated_sharding():
    """Replicated NamedSharding on the active sequence mesh, or None when
    sequence parallelism is off (the attention op shards inside)."""
    from .parallel import mesh as mesh_mod
    seq_mesh, _ = mesh_mod.sequence_mesh()
    if seq_mesh is None:
        return None
    from jax.sharding import NamedSharding
    return NamedSharding(seq_mesh, _pspec())


class _FunctionalOptimizer(object):
    """Pure-function view of an Optimizer instance: (w, g, state, hyper) ->
    (new_w, new_state).  Hyper-params that change across steps (lr, Adam bias
    correction) arrive as traced scalars so XLA never recompiles on lr decay."""

    def __init__(self, optimizer, param_names):
        self.opt = optimizer
        self.names = list(param_names)
        # static per-param multipliers (parity: set_lr_mult/set_wd_mult;
        # reference decays only *_weight / *_gamma by default)
        self.lr_mult = {}
        self.wd_mult = {}
        for n in self.names:
            self.lr_mult[n] = optimizer.lr_mult.get(n, 1.0)
            default_wm = 1.0 if n.endswith(("_weight", "_gamma")) else 0.0
            self.wd_mult[n] = optimizer.wd_mult.get(n, default_wm)
        self.kind = type(optimizer).__name__.lower()
        if self.kind not in ("sgd", "ccsgd", "nag", "adam", "rmsprop",
                             "adagrad", "adadelta", "sgld", "dcasgd",
                             "test"):
            raise MXNetError(
                "TrainStep supports sgd/nag/adam/rmsprop/adagrad/adadelta/"
                "sgld/dcasgd/test; got %s (use the Module path for others)"
                % self.kind)

    # ------------------------------------------------------------------ state
    def init_state(self, params):
        # host-side zeros: one transfer at placement time, no per-shape
        # accelerator compiles
        zeros = lambda w: _np.zeros(w.shape, w.dtype)
        state = {}
        for n, w in params.items():
            if self.kind in ("sgd", "ccsgd", "nag"):
                state[n] = (zeros(w),) if self.opt.momentum else ()
            elif self.kind == "adam":
                state[n] = (zeros(w), zeros(w))
            elif self.kind == "rmsprop":
                state[n] = (zeros(w), zeros(w), zeros(w)) \
                    if getattr(self.opt, "centered", False) else (zeros(w),)
            elif self.kind == "adagrad":
                state[n] = (zeros(w),)
            elif self.kind == "adadelta":
                state[n] = (zeros(w), zeros(w))
            elif self.kind == "sgld":
                state[n] = ()
            elif self.kind == "dcasgd":
                # (momentum?, previous_weight) — prev starts AT the weight
                prev = _np.array(w, copy=True)
                state[n] = (zeros(w), prev) if self.opt.momentum else (prev,)
            elif self.kind == "test":
                state[n] = (zeros(w),)
        return state

    # ------------------------------------------------------------------ hyper
    def hyper(self, num_update):
        """Traced scalars computed host-side per call (the lr *schedule* is
        sampled here; Adam's per-step bias correction is computed on-device
        from the traced step count so fused multi-step chunks stay exact)."""
        o = self.opt
        lr = o.lr
        if getattr(o, "lr_scheduler", None) is not None:
            lr = o.lr_scheduler(num_update)
        return {"lr": _np.float32(lr)}

    # ----------------------------------------------------------------- update
    def update(self, name, w, g, state, hyper, t, rng=None):
        """One optimizer step; ``t`` is the 1-based traced update count;
        ``rng`` seeds stochastic rules (SGLD's Langevin noise)."""
        import jax.numpy as jnp
        from .ops.registry import OPS
        o = self.opt
        lr = hyper["lr"] * self.lr_mult[name]
        if self.kind == "adam":
            tf = jnp.asarray(t, jnp.float32)
            coef1 = 1.0 - o.beta1 ** tf
            coef2 = 1.0 - o.beta2 ** tf
            lr = lr * jnp.sqrt(coef2) / coef1
        wd = o.wd * self.wd_mult[name]
        clip = -1.0 if o.clip_gradient is None else o.clip_gradient
        common = dict(lr=lr, wd=wd, rescale_grad=o.rescale_grad,
                      clip_gradient=clip)
        if self.kind in ("sgd", "ccsgd"):
            if state:
                nw, nm = OPS.get("sgd_mom_update").fn(
                    w, g, state[0], momentum=o.momentum, **common)
                return nw, (nm,)
            return OPS.get("sgd_update").fn(w, g, **common), ()
        if self.kind == "nag":
            grad = g * o.rescale_grad
            if o.clip_gradient is not None:
                grad = jnp.clip(grad, -o.clip_gradient, o.clip_gradient)
            if state:
                mom = state[0] * o.momentum
                grad = grad + wd * w
                mom = mom + grad
                grad = grad + o.momentum * mom
                return w - lr * grad, (mom,)
            return w - lr * (grad + wd * w), ()
        if self.kind == "adam":
            nw, nm, nv = OPS.get("adam_update").fn(
                w, g, state[0], state[1], beta1=o.beta1, beta2=o.beta2,
                epsilon=o.epsilon, **common)
            return nw, (nm, nv)
        if self.kind == "rmsprop":
            cw = getattr(o, "clip_weights", None)
            if getattr(o, "centered", False):
                nw, nn, ng, ndl = OPS.get("rmspropalex_update").fn(
                    w, g, state[0], state[1], state[2], gamma1=o.gamma1,
                    gamma2=o.gamma2, epsilon=o.epsilon,
                    clip_weights=-1.0 if cw is None else cw, **common)
                return nw, (nn, ng, ndl)
            nw, nn = OPS.get("rmsprop_update").fn(
                w, g, state[0], gamma1=o.gamma1, epsilon=o.epsilon,
                clip_weights=-1.0 if cw is None else cw, **common)
            return nw, (nn,)
        if self.kind == "adagrad":
            grad = g * o.rescale_grad
            if o.clip_gradient is not None:
                grad = jnp.clip(grad, -o.clip_gradient, o.clip_gradient)
            hist = state[0] + jnp.square(grad)
            return w - lr * (grad / jnp.sqrt(hist + o.float_stable_eps)
                             + wd * w), (hist,)
        if self.kind == "adadelta":
            grad = g * o.rescale_grad
            if o.clip_gradient is not None:
                grad = jnp.clip(grad, -o.clip_gradient, o.clip_gradient)
            acc_g = o.rho * state[0] + (1.0 - o.rho) * jnp.square(grad)
            delta = (jnp.sqrt(state[1] + o.epsilon)
                     / jnp.sqrt(acc_g + o.epsilon)) * grad
            acc_d = o.rho * state[1] + (1.0 - o.rho) * jnp.square(delta)
            return w - delta - wd * w, (acc_g, acc_d)
        if self.kind == "sgld":
            import jax
            import zlib
            grad = g * o.rescale_grad
            if o.clip_gradient is not None:
                grad = jnp.clip(grad, -o.clip_gradient, o.clip_gradient)
            # crc32, not hash(): python's per-process hash salt would draw
            # different noise on each worker of a data-parallel run
            key = jax.random.fold_in(
                jax.random.fold_in(rng, zlib.crc32(name.encode())
                                   & 0x7FFFFFFF), t)
            noise = jnp.sqrt(lr) * jax.random.normal(key, w.shape, w.dtype)
            return w - lr / 2 * (grad + wd * w) + noise, ()
        if self.kind == "dcasgd":
            grad = g * o.rescale_grad
            if o.clip_gradient is not None:
                grad = jnp.clip(grad, -o.clip_gradient, o.clip_gradient)
            prev = state[-1]
            comp = grad + wd * w + o.lamda * grad * grad * (w - prev)
            if len(state) == 2:
                mon = state[0] * o.momentum - lr * comp
                return w + mon, (mon, w)
            return w - lr * comp, (w,)
        if self.kind == "test":
            nw = w + g * o.rescale_grad
            return nw, (nw,)
        raise MXNetError("unreachable")


# Device counters (``telemetry.device_counter``) that a graph's ops hand in
# while the step traces: they ride out of the forward beside the aux updates
# under this key, and out of the step program as a dict after its outputs.
_COUNTERS = "__device_counters__"


def _with_counters(outs, aux_upd):
    counters = aux_upd.get(_COUNTERS)
    return outs + (counters,) if counters else outs


def _split_counters(outs):
    """(the graph's outputs, the counters' dict or None)."""
    if outs and isinstance(outs[-1], dict):
        return outs[:-1], outs[-1]
    return outs, None


def _sum_counters(outs, counted):
    """The last step's outputs with its counters summed with those the scan
    stacked over the steps before it."""
    outs, last = _split_counters(outs)
    if last is None:
        return outs
    return outs + ({k: v + counted[k].sum(axis=0)
                    for k, v in last.items()},)


def _publish_counters(outs, steps):
    """Hand a step program's counters, still on the device, to telemetry;
    the caller sees the graph's outputs alone."""
    outs, counters = _split_counters(outs)
    if counters is not None:
        _tel.publish_device_counters(counters, steps)
    return outs


class TrainStep(object):
    """Compile a Symbol + Optimizer into one donated, sharded XLA train step.

    Parameters
    ----------
    symbol : the loss-topped Symbol (e.g. SoftmaxOutput head)
    optimizer : mxnet_tpu.optimizer.Optimizer instance
    data_names / label_names : input variable names (not trained)
    mesh : optional jax.sharding.Mesh with a 'dp' axis (and optionally 'tp');
        None = single device
    param_shardings : {param_name: PartitionSpec} for tensor-parallel params
        (default: replicated)
    remat : False | True | 'dots' — rematerialisation policy for the backward
        pass (True = save nothing, 'dots' = save matmul outputs only)
    dtype : compute dtype for the lowered graph; params stay float32, inputs
        and the graph run in this dtype (bfloat16 recommended on TPU).
        Pure cast mode — no loss scaling; superseded by ``policy``.
    policy : amp.Policy | True | dtype-str — full mixed-precision policy:
        compute dtype + f32 master weights + (dynamic) loss scaling.  The
        loss-scale state (current scale, good-step counter, overflow
        count) is carried INSIDE the donated step jit — the scale is
        injected at the loss heads (executor scale-backward identity, so
        the whole backward chain sees it), non-finite grads are detected
        on device, and the update is skipped in a ``lax.cond`` — so the
        hot path stays sync-free.  Resolve env levers with
        ``amp.resolve_policy()`` at construction time.
    """

    def __init__(self, symbol, optimizer, data_names=("data",),
                 label_names=("softmax_label",), mesh=None,
                 param_shardings=None, remat=False, dtype=None, zero=False,
                 policy=None):
        import jax
        from .executor import _Lowered
        if policy is not None:
            from . import amp as _amp
            if dtype is not None:
                raise MXNetError(
                    "TrainStep: pass either dtype= (pure cast) or policy= "
                    "(cast + loss scaling), not both")
            policy = _amp.resolve_policy(policy)
            if policy.compute_dtype != "float32":
                dtype = policy.compute_dtype
        self.policy = policy
        self._has_scale = policy is not None
        self._scale_state = None
        self._scale_device = None
        self._overflow_seen = 0
        # who stamps the loss_scale gauge/overflow counter under
        # telemetry: standalone TrainStep users get it from __call__;
        # the fused fit loop takes ownership (one sampled sync, plus the
        # train_loss_scale curve) and flips this off
        self._amp_emit = True
        self.symbol = symbol
        self.mesh = mesh
        self.param_shardings = dict(param_shardings or {})
        self._low = _Lowered(symbol)
        self.data_names = tuple(data_names)
        self.label_names = tuple(label_names)
        inputs = set(self.data_names) | set(self.label_names)
        self.param_names = [n for n in self._low.arg_names if n not in inputs]
        self.aux_names = list(self._low.aux_names)
        self.fopt = _FunctionalOptimizer(optimizer, self.param_names)
        self.optimizer = optimizer
        self.num_update = 0
        self._dtype = dtype
        # MXNET_CHECK_NUMERICS hook; Module.fit's fused driver flips this
        # off because the fit loop re-checks with epoch/nbatch context
        self.check_numerics = True
        # ZeRO levels (opt-in; docs/distributed.md "ZeRO levels"): the
        # dp-axis sharding ladder as one explicit placement plan.  Level 1
        # shards the optimizer step (gradients reach the update as
        # reduce-scattered 1/dp shards, state lives permanently sharded,
        # updated params all-gather back).  Level 2 makes the flat
        # (dp, chunk) bucket the ONLY gradient residency (the full tree
        # folds into it straight off the vjp) and replaces the gradient
        # gather with one all-gather of *updated* parameters.  Level 3
        # additionally shards the parameters themselves — full weights
        # are gathered just-in-time inside the step and freed after use,
        # so per-device model footprint scales ~1/dp (1/(pp*dp) composed
        # with pipeline stages).  The reference's PS design
        # (src/kvstore/kvstore_dist.h:28-318) has no analogue — its
        # servers hold whole key ranges; this is the TPU-native ICI shape
        # of the same aggregation.  ``zero=True`` keeps its historical
        # level-1 meaning.
        self.zero = normalize_zero(zero)
        if self.zero:
            if mesh is None or "dp" not in mesh.axis_names:
                raise MXNetError(
                    "TrainStep(zero=%d) needs a mesh with a 'dp' axis"
                    % self.zero)
            if any(n in self.param_shardings for n in self.param_names):
                raise MXNetError(
                    "TrainStep(zero=%d) shards the optimizer over dp; "
                    "combine it with tensor-parallel param_shardings is "
                    "not supported yet" % self.zero)
        self._dp = int(mesh.shape["dp"]) if self.zero else 1
        self.plan = PlacementPlan(zero=self.zero, dp=self._dp,
                                  who="TrainStep")
        self._zb_cache = None   # zero_*_bytes gauge memo (step-invariant)
        self._gather_fn = None
        if self.zero >= 3:
            # the params all-gather program (gather_params): registered
            # like every jit cache (CKEY001 CACHES row; the program reads
            # no env levers — pure reshape + sharding constraint)
            self._san_gather = _san.register_cache(
                "zero.gather", kind="zero_gather", owner=self,
                sizer=lambda ts: 1 if ts._gather_fn is not None else 0,
                warmup=1, jit_names=("mxtpu_zero_gather",))
        low = self._low
        f32_leaves = low.f32_leaves()

        def fwd(params, aux, batch, rng, head_scale=None):
            vals = dict(batch)
            if dtype is not None:
                # cast only the data inputs — labels carry class ids that
                # bfloat16 would round (997 -> 996), silently corrupting the
                # one-hot targets
                vals = {k: (v.astype(dtype)
                            if k not in self.label_names
                            and v.dtype == _np.float32 else v)
                        for k, v in vals.items()}
                # leaves an op wants in float32 (a router's weight, a
                # scan's decay rates) stay as they are
                params = {k: v if k in f32_leaves else v.astype(dtype)
                          for k, v in params.items()}
            vals.update(params)
            with _tel.collect_device_counters() as bag:
                outs, aux_upd = low.run(vals, aux, rng, True,
                                        no_grad_inputs=inputs,
                                        head_grad_scale=head_scale)
            counters = bag.stacked()
            if counters:
                # device counters ride beside the aux updates (no aux state
                # has this name) and leave the step as a last output
                aux_upd = dict(aux_upd, **{_COUNTERS: counters})
            return tuple(outs), aux_upd

        if remat:
            policy = None
            if remat == "dots":
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            fwd = jax.checkpoint(fwd, policy=policy)

        def update_all(params, grads, opt_state, hyper, t, rng):
            new_params, new_state = {}, {}
            for n in self.param_names:
                g = grads[n].astype(params[n].dtype)
                new_params[n], new_state[n] = self.fopt.update(
                    n, params[n], g, opt_state[n], hyper, t, rng=rng)
            return new_params, new_state

        plan = self.plan

        def update_zero(params, grads, opt_state, hyper, t, rng):
            """ZeRO-1 update: the plan's sharded step over each leaf's
            shard, in the leaf's own shape where dp divides its leading
            axis (PlacementPlan.update_shards)."""
            return plan.update_shards(self.fopt, self.param_names, params,
                                      grads, opt_state, hyper, t, rng, mesh)

        def bucket_update(params, grads, opt_state, hyper, t, rng):
            """ZeRO-2/3 update: ``grads`` is the (layout, bucket) pair —
            the folded flat (dp, chunk) residency — and the plan's
            sharded update consumes the rows (level 2 re-materialises
            replicated params with ONE all-gather of the updated rows;
            level 3 keeps them sharded)."""
            layout, bucket = grads
            return plan.shard_update(self.fopt, params, bucket, layout,
                                     opt_state, hyper, t, rng, mesh)

        def fold_grads(params, gtree):
            """Gradient residency per the plan: level >= 2 folds the vjp
            tree into ONE dp-sharded bucket immediately (each per-param
            view lowers its reduction as a reduce-scatter; the full tree
            never persists past this fold), below it the tree IS the
            residency."""
            if not plan.bucket_grads:
                return gtree
            layout = plan.bucket_layout(params, self.param_names)
            return (layout, plan.fold_bucket(gtree, params, layout, mesh))

        def _step_core(want_stats, params, opt_state, aux, batch, rng,
                       hyper, t):
            import jax.numpy as jnp
            # ZeRO-3: gather the flat parameter shards to full tensors
            # just-in-time (identity below level 3); XLA frees the
            # gathered weights when their last use retires
            fullp = plan.gather_params(params, mesh)

            def f(p):
                return fwd(p, aux, batch, rng)
            # named scopes: metadata only, so that each device operation's
            # name in a trace says which part of the step it belongs to
            with jax.named_scope("forward"):
                outs, vjp_fn, aux_upd = jax.vjp(f, fullp, has_aux=True)
            ones = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
            with jax.named_scope("backward"):
                grads = fold_grads(params, vjp_fn(ones)[0])
            if plan.bucket_grads:
                upd = bucket_update
            else:
                upd = update_zero if self.zero else update_all
            with jax.named_scope("optimizer_update"):
                new_params, new_state = upd(params, grads, opt_state, hyper,
                                            t, rng)
            new_aux = dict(aux)
            new_aux.update({k: v.astype(aux[k].dtype)
                            for k, v in aux_upd.items() if k in aux})
            if not want_stats:
                return new_params, new_state, new_aux, \
                    _with_counters(outs, aux_upd)
            stats = self._monitor_stats(params, grads, new_params, outs)
            return new_params, new_state, new_aux, \
                _with_counters(outs, aux_upd), stats

        def step(params, opt_state, aux, batch, rng, hyper, t):
            return _step_core(False, params, opt_state, aux, batch, rng,
                              hyper, t)

        def step_mon(params, opt_state, aux, batch, rng, hyper, t):
            """MXNET_MONITOR sampled-step twin: identical update math
            plus the on-device numerics stats pytree as a FIFTH output
            (built lazily — monitor-off never traces it)."""
            return _step_core(True, params, opt_state, aux, batch, rng,
                              hyper, t)

        def _amp_core(want_stats, params, opt_state, aux, lsc, batch, rng,
                      hyper, t):
            """Loss-scaled step: the scale state ``lsc`` rides donated in
            the jit (and through run_steps' scan carry) — no host syncs."""
            import jax.numpy as jnp

            scale = lsc["scale"]
            fullp = plan.gather_params(params, mesh)

            def f(p):
                # the scale is injected at the loss heads (executor's
                # scale-backward identity): the heads ignore incoming
                # cotangents, so seeding would not reach the chain
                return fwd(p, aux, batch, rng, scale)
            with jax.named_scope("forward"):
                outs, vjp_fn, aux_upd = jax.vjp(f, fullp, has_aux=True)
            ones = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
            with jax.named_scope("backward"):
                gtree = vjp_fn(ones)[0]
                grads = fold_grads(params, gtree)
            with jax.named_scope("overflow_check"):
                if plan.bucket_grads:
                    # overflow detection on the bucket — the only gradient
                    # residency (an inf/nan survives the reduce-scatter sum)
                    _layout, bucket = grads
                    finite = jnp.isfinite(bucket).all() \
                        if bucket is not None else jnp.bool_(True)
                else:
                    # overflow detection on the SCALED f32 grads, on device
                    finite = jnp.stack(
                        [jnp.isfinite(g).all()
                         for g in jax.tree_util.tree_leaves(gtree)]).all()
            if plan.bucket_grads:
                upd = bucket_update
            else:
                upd = update_zero if self.zero else update_all
            inv = jnp.float32(1.0) / scale

            def do_update(_):
                # unscale by 1/S exactly once; the optimizer's own
                # rescale_grad (1/batch) applies inside the rule as always
                with jax.named_scope("optimizer_update"):
                    if plan.bucket_grads:
                        layout, bucket = grads
                        grads_u = (layout,
                                   bucket * inv.astype(bucket.dtype)
                                   if bucket is not None else None)
                    else:
                        grads_u = {n: g * inv.astype(g.dtype)
                                   for n, g in grads.items()}
                    new_params, new_state = upd(params, grads_u, opt_state,
                                                hyper, t, rng)
                new_aux = dict(aux)
                new_aux.update({k: v.astype(aux[k].dtype)
                                for k, v in aux_upd.items() if k in aux})
                return new_params, new_state, new_aux

            def skip_update(_):
                # overflow step: weights, optimizer state AND the BN
                # moving stats all stay put (inf activations must not
                # poison running statistics; ZeRO-3 master shards are
                # returned untouched — test-pinned)
                return params, opt_state, dict(aux)

            new_params, new_state, new_aux = jax.lax.cond(
                finite, do_update, skip_update, None)
            new_lsc = self.policy.next_state(lsc, finite)
            # the loss surface crosses back in f32 (metrics, sentinels)
            outs = tuple(o.astype(jnp.float32) for o in outs)
            if not want_stats:
                return new_params, new_state, new_aux, new_lsc, \
                    _with_counters(outs, aux_upd)
            # stats OUTSIDE the overflow cond: the scaled grads exist on
            # skip steps too (that step's inf IS the finding); the
            # squared sums unscale by inv**2 so published norms are in
            # unscaled units
            stats = self._monitor_stats(params, grads, new_params, outs,
                                        inv=inv)
            return new_params, new_state, new_aux, new_lsc, \
                _with_counters(outs, aux_upd), stats

        def step_amp(params, opt_state, aux, lsc, batch, rng, hyper, t):
            return _amp_core(False, params, opt_state, aux, lsc, batch,
                             rng, hyper, t)

        def step_amp_mon(params, opt_state, aux, lsc, batch, rng, hyper,
                         t):
            """MXNET_MONITOR sampled-step twin of the loss-scaled step:
            the stats pytree rides as a SIXTH output."""
            return _amp_core(True, params, opt_state, aux, lsc, batch,
                             rng, hyper, t)

        # collision-proof program names: mxsan's raw-jit watcher exempts
        # this cache's inner names process-wide, so bare 'step'/'many'
        # would also blind it to same-named user functions
        step.__name__ = "mxtpu_step"
        step_amp.__name__ = "mxtpu_step_amp"
        step_mon.__name__ = "mxtpu_step_mon"
        step_amp_mon.__name__ = "mxtpu_step_amp_mon"
        self._step_fn = step_amp if self._has_scale else step
        self._mon_fn = step_amp_mon if self._has_scale else step_mon
        self._donate = (0, 1, 2, 3) if self._has_scale else (0, 1, 2)
        self._multi_cache = {}
        # MXNET_MONITOR: monitored-step programs keyed on the trace-env
        # snapshot (the spec itself rides in TRACE_ENV_DEFAULTS, so a
        # toggle rebuilds cleanly); built lazily on the first sampled
        # step — monitor-off never jits a monitored variant
        self._mon_cache = {}
        self._mon_force = False      # legacy Monitor.tic() force-sample
        self._last_mon_entry = None  # last published ring entry
        self._san_mon_cache = _san.register_cache(
            "train_step.monitor", kind="train_monitor", owner=self,
            sizer=lambda ts: len(ts._mon_cache), warmup=4,
            jit_names=("mxtpu_step_mon", "mxtpu_step_amp_mon"))
        self._hbm_done = False   # step program's HBM/cost capture (once)
        self._cost_row = None    # step program's cost ledger row (MFU)
        # mxsan: run_steps' chunk programs are a jit cache too (keyed on
        # (num_steps, stacked, trace-env snapshot) below)
        self._san_cache = _san.register_cache(
            "train_step.run_steps", kind="train_multi", owner=self,
            sizer=lambda ts: len(ts._multi_cache),
            # this instance's step jit ('step'/'step_amp') and the chunk
            # program ('many') belong to tracked caches — the raw-jit
            # watcher must not double-count their compiles
            jit_names=("mxtpu_step", "mxtpu_step_amp", "mxtpu_many"))
        self._in_shardings = None
        self._out_shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            ps = dict(param_shardings or {})
            rep = NamedSharding(mesh, _pspec())

            def par_shard(n):
                return NamedSharding(mesh, ps[n]) if n in ps else rep
            param_sh = {n: par_shard(n) for n in self.param_names}
            if self.zero >= 3:
                # ZeRO-3: the resident parameter buffers ARE the flat
                # (dp, chunk) shards — dp-sharded in, dp-sharded out
                sh_dp3 = NamedSharding(mesh, _pspec("dp"))
                param_sh = {n: sh_dp3 for n in self.param_names}
            batch_sh = {n: NamedSharding(mesh, _pspec("dp"))
                        for n in inputs}
            state_sh = NamedSharding(mesh, _pspec("dp")) if self.zero \
                else None
            if self._has_scale:
                self._in_shardings = (param_sh, state_sh, None, rep,
                                      batch_sh, rep, None, None)
                # the lax.cond (skip-on-overflow) defeats GSPMD's output
                # sharding propagation — pin the outputs to the input
                # layout so the carried pytrees re-enter the next step
                # without resharding
                state_out = NamedSharding(mesh, _pspec("dp")) if self.zero \
                    else param_sh
                self._out_shardings = (param_sh, state_out, rep, rep, None)
                self._step = jax.jit(
                    step_amp,
                    in_shardings=self._in_shardings,
                    out_shardings=self._out_shardings,
                    donate_argnums=(0, 1, 2, 3))
            else:
                self._in_shardings = (param_sh, state_sh, None, batch_sh,
                                      rep, None, None)
                self._step = jax.jit(
                    step,
                    in_shardings=self._in_shardings,
                    donate_argnums=(0, 1, 2))
        elif self._has_scale:
            self._step = jax.jit(step_amp, donate_argnums=(0, 1, 2, 3))
        else:
            self._step = jax.jit(step, donate_argnums=(0, 1, 2))

    # ---------------------------------------------------------- ZeRO views
    def unflatten_host(self, name, arr):
        """Host array of a sharded leaf (optimizer state; a level-3
        parameter) -> the logical tensor (the sync-back/export half of
        the plan's layouts)."""
        return self.plan.unflatten_host(name, arr)

    def gather_params(self, params):
        """Materialise logical, REPLICATED parameters from the ZeRO-3
        flat shards: one jitted all-gather program (the registered
        ``zero.gather`` cache; ``zero.gather`` telemetry span; a
        collective-ledger entry under mxsan).  Identity below level 3 —
        callers that need full weights (sync-back, eval hand-off) use
        this unconditionally."""
        if self.zero < 3:
            return params
        import jax
        if self._gather_fn is None:
            plan, mesh = self.plan, self.mesh
            from jax.sharding import NamedSharding
            rep = NamedSharding(mesh, _pspec())

            def gather(params):
                return plan.gather_params(params, mesh)
            gather.__name__ = "mxtpu_zero_gather"
            self._gather_fn = jax.jit(gather, out_shardings=rep)
            self._san_gather.miss({"params": len(self.param_names)})
            if _san._hbm_on or _san._cost_on:
                # HBM/cost attribution for the gather program (compile
                # reuse: the first call below hits the cached executable)
                _san.program_capture("zero.gather", self._gather_fn,
                                     (params,), cache=self._san_gather)
        if _san._collective_on:
            # ledger entry at dispatch, from shape metadata (no sync)
            _san.note_collective(
                "mxtpu_zero_gather", name="params",
                sig=("%d tensors" % len(params),), axes="dp")
        if _san._collective_on or _tel._enabled:
            # the ledger sig above is not shape-typed; the gathered
            # payload is the full logical parameter set — account it
            # explicitly (shape metadata only, no sync)
            _san.record_wire_bytes(
                "mxtpu_zero_gather", axes="dp",
                nbytes=sum(_tel.nbytes_of(v) for v in params.values()))
        if _tel._enabled:
            with _tel.span("zero.gather", cat="distributed",
                           level=self.zero, tensors=len(params)):
                out = self._gather_fn(params)
                with _san.allow_sync("zero.gather telemetry span"):
                    jax.block_until_ready(out)
            return out
        return self._gather_fn(params)

    def zero_bytes(self, params, opt_state=None):
        """Per-device {param, grad, opt} byte residency of this step's
        placement plan — shape metadata only, readable with telemetry
        off (the ``zero_param_bytes``/``zero_grad_bytes`` gauge source
        and the dryrun ladder's memory stamp)."""
        return self.plan.per_device_bytes(params, opt_state)

    # ----------------------------------------------------------- checkpoint
    def checkpoint_topology(self):
        """Shard-ownership description for the sharded checkpoint writer
        (mxnet_tpu/checkpoint.py): which stage owns each parameter/aux
        tensor (all stage 0 here — one program), and how the optimizer
        state is laid out (ZeRO shards, ``dp`` parts of each leaf, or
        replicated; ``zero`` carries the LEVEL — at level 3 the
        parameters themselves are flat rows and ``param_shapes`` records
        their logical shapes for the writer/reader).  The writer turns
        this into one shard file per ownership group instead of N ranks
        racing to clobber one monolithic ``.params``."""
        topo = {"pp": 1,
                "dp": self._dp,
                "zero": self.zero,
                "microbatches": None,
                "stage_of": {n: 0 for n in self.param_names
                             + self.aux_names}}
        if self.zero >= 3:
            topo["param_shapes"] = {n: list(self.plan.shape_of(n))
                                    for n in self.param_names}
        return topo

    def place_checkpoint(self, host_params, host_state, host_aux,
                         device=None):
        """Place restored HOST pytrees onto this step's topology (the
        restore half of any-topology resume: ``host_state`` leaves arrive
        in the LOGICAL parameter shape and are re-sharded here —
        ``zero=True`` cuts them over this mesh's ``dp`` in the plan's
        form of each leaf, whatever topology saved them).  ``device``
        pins the no-mesh placement (the fused fit's module device);
        default is the ambient context or the first LOCAL device — never
        a peer rank's."""
        import jax
        params = {n: _np.asarray(host_params[n]) for n in self.param_names}
        aux = {n: _np.asarray(host_aux[n]) for n in self.aux_names}
        self.plan.note_host(params)
        if self.zero:
            state = {n: tuple(self.plan.shards_np(s)
                              for s in host_state[n])
                     for n in self.param_names}
        else:
            state = {n: tuple(_np.asarray(s) for s in host_state[n])
                     for n in self.param_names}
        if self.mesh is None:
            rep = device if device is not None \
                else _seq_replicated_sharding()
            if rep is None:
                from .context import Context
                ambient = getattr(Context._default_ctx, "value", None)
                # local_devices: under a multi-process world devices()[0]
                # is rank 0's device — non-addressable from other ranks
                rep = (ambient.jax_device() if ambient is not None
                       else jax.local_devices()[0])
            params = {n: jax.device_put(v, rep) for n, v in params.items()}
            state = {n: tuple(jax.device_put(s, rep) for s in st)
                     for n, st in state.items()}
            aux = {n: jax.device_put(v, rep) for n, v in aux.items()}
            return params, state, aux
        from jax.sharding import NamedSharding
        rep = NamedSharding(self.mesh, _pspec())

        def shard_of(n):
            if n in self.param_shardings:
                return NamedSharding(self.mesh, self.param_shardings[n])
            return rep
        if self.zero >= 3:
            # ZeRO-3: parameters live as flat (dp, chunk) shards —
            # re-chunked to THIS mesh's dp, whatever topology saved them
            sh_dp = NamedSharding(self.mesh, _pspec("dp"))
            params = {n: jax.device_put(_flat_np(v, self._dp), sh_dp)
                      for n, v in params.items()}
        else:
            params = {n: jax.device_put(v, shard_of(n))
                      for n, v in params.items()}
        if self.zero:
            sh_dp = NamedSharding(self.mesh, _pspec("dp"))
            state = {n: tuple(jax.device_put(s, sh_dp) for s in st)
                     for n, st in state.items()}
        else:
            state = {n: tuple(jax.device_put(s, shard_of(n)) for s in st)
                     for n, st in state.items()}
        aux = {n: jax.device_put(v, rep) for n, v in aux.items()}
        return params, state, aux

    def scale_state_host(self):
        """Loss-scale state as host scalars (checkpoint export), or None
        without a policy.  Syncs three scalars — checkpoint-time only."""
        return _scale_state_to_host(self)

    def export_host(self, params, opt_state, aux):
        """LOGICAL host export of a live training state: ``(manifest,
        params, opt_state, aux)`` exactly as a checkpoint save + load of
        this step would produce, without touching disk — one batched
        device→host fetch through the checkpoint writer's snapshot
        layout, reassembled by the restore path's group math.  The live
        resize (parallel/resize.py) feeds this straight into
        ``checkpoint.restore_loaded`` on a step built for the NEW
        topology, which makes the in-place re-shard bitwise equal to a
        save/restore round trip by construction."""
        from . import checkpoint as _ckpt
        return _ckpt.reassemble(_ckpt.snapshot(self, params, opt_state,
                                               aux))

    def load_scale_state(self, host):
        """Restore the loss-scale automaton from checkpointed host scalars
        (no-op without a policy: an f32 restore of an AMP checkpoint
        simply drops the scale)."""
        if not self._has_scale or host is None:
            return
        self._scale_state = None            # next _scale_state_dev places it
        base = self.policy.init_state()
        merged = {k: _np.asarray(host.get(k, base[k]), base[k].dtype)
                  for k in base}
        # place through the lazy path, then overwrite the values
        dev = self._scale_state_dev()
        import jax
        self._scale_state = {k: jax.device_put(merged[k], v.sharding)
                             if hasattr(v, "sharding")
                             else jax.device_put(merged[k])
                             for k, v in dev.items()}
        self._overflow_seen = int(merged["overflow"])

    # ------------------------------------------------------------ loss scale
    def _scale_state_dev(self):
        """Current loss-scale state as device arrays (lazy first placement:
        replicated on the mesh / sequence mesh, else the ambient or
        explicitly-set compute device).  Donated into every step; the
        returned state replaces it."""
        if self._scale_state is not None:
            return self._scale_state
        import jax
        host = self.policy.init_state()
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            dst = NamedSharding(self.mesh, _pspec())
        else:
            dst = _seq_replicated_sharding()
            if dst is None:
                if self._scale_device is not None:
                    dst = self._scale_device
                else:
                    from .context import Context
                    ambient = getattr(Context._default_ctx, "value", None)
                    if ambient is not None:
                        dst = ambient.jax_device()
        # with nowhere named, the state goes to the default device
        # UNCOMMITTED, as a caller's own jit-made parameters are: a
        # committed scalar beside uncommitted parameters commits the
        # step's outputs, and the second call then lowers and compiles
        # the whole program again for committed inputs
        self._scale_state = {
            k: jax.device_put(v, dst) if dst is not None
            else jax.numpy.asarray(v) for k, v in host.items()}
        return self._scale_state

    def _donate_pairs(self, args):
        """Labelled leaves of the donated argument pytrees, in donate_argnums
        order (params, opt_state, aux[, loss-scale state]) — the mxsan
        DONATE checker's naming source.  Built only while that checker is
        armed."""
        import jax
        for name, tree in zip(("params", "opt_state", "aux",
                               "loss_scale_state"), args):
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
                yield name + jax.tree_util.keystr(path), leaf

    def amp_stats(self):
        """Host view of the loss-scale state: ``(scale, overflow_delta)``
        with the overflow (skipped-update) count as a delta since the
        previous call, or None without a policy.  Syncs two scalars —
        call only under a telemetry/diagnostics gate, never per hot-path
        step."""
        if not self._has_scale or self._scale_state is None:
            return None
        import jax
        with _san.allow_sync("amp loss-scale telemetry"):
            host = jax.device_get(self._scale_state)
        total = int(host["overflow"])
        delta = total - self._overflow_seen
        self._overflow_seen = total
        return float(host["scale"]), delta

    # ------------------------------------------------------------------- init
    def init(self, data_shapes, label_shapes=None, initializer=None, seed=0):
        """Infer shapes, initialise params/aux with `initializer`, build
        optimizer state.  Returns (params, opt_state, aux) pytrees of
        jax.Arrays, placed according to the mesh."""
        import jax
        params, aux = _host_init(self.symbol, self._low, self.param_names,
                                 self.aux_names, data_shapes, label_shapes,
                                 initializer, seed, "TrainStep")
        self.plan.note_host(params)
        if self.zero:
            # optimizer state is born sharded over dp
            opt_state = self.plan.state_host(self.fopt, params)
        else:
            opt_state = self.fopt.init_state(params)
        if self.mesh is None:
            rep = _seq_replicated_sharding()
            if rep is not None:
                # sequence parallelism without an explicit dp/tp mesh: the
                # step contains a shard_map over the sequence mesh, so all
                # buffers must live replicated on it (attention shards them)
                params = {n: jax.device_put(v, rep)
                          for n, v in params.items()}
                opt_state = {n: tuple(jax.device_put(s, rep) for s in st)
                             for n, st in opt_state.items()}
                aux = {n: jax.device_put(v, rep) for n, v in aux.items()}
                return params, opt_state, aux
            # commit everything to the compute device in one hop so the fused
            # step runs there (host-committed params would drag the whole
            # computation onto the CPU backend); an explicitly-entered
            # context (``with mx.tpu(1):``) picks the device, otherwise the
            # process default accelerator
            from .context import Context
            ambient = getattr(Context._default_ctx, "value", None)
            dev = (ambient.jax_device() if ambient is not None
                   else jax.devices()[0])
            params = {n: jax.device_put(v, dev) for n, v in params.items()}
            opt_state = {n: tuple(jax.device_put(s, dev) for s in st)
                         for n, st in opt_state.items()}
            aux = {n: jax.device_put(v, dev) for n, v in aux.items()}
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            rep = NamedSharding(self.mesh, _pspec())

            def shard_of(n):
                if n in self.param_shardings:
                    return NamedSharding(self.mesh, self.param_shardings[n])
                return rep
            if self.zero >= 3:
                # ZeRO-3: parameters are born as flat (dp, chunk) shards
                sh_dp3 = NamedSharding(self.mesh, _pspec("dp"))
                params = {n: jax.device_put(_flat_np(v, self._dp), sh_dp3)
                          for n, v in params.items()}
            else:
                params = {n: jax.device_put(v, shard_of(n))
                          for n, v in params.items()}
            if self.zero:
                # ZeRO: optimizer state lives permanently sharded over dp
                sh_dp = NamedSharding(self.mesh, _pspec("dp"))
                opt_state = {n: tuple(jax.device_put(s, sh_dp) for s in st)
                             for n, st in opt_state.items()}
            else:
                # optimizer state tensors follow their parameter's sharding
                opt_state = {n: tuple(jax.device_put(s, shard_of(n))
                                      for s in st)
                             for n, st in opt_state.items()}
            aux = jax.device_put(aux, rep)
        return params, opt_state, aux

    def shard_batch(self, batch):
        """Place a host batch dict on the mesh, sharded along 'dp' (axis 0)."""
        import jax
        from jax.sharding import NamedSharding
        if self.mesh is None:
            rep = _seq_replicated_sharding()
            if rep is not None:
                return {k: jax.device_put(v, rep) for k, v in batch.items()}
            return {k: jax.numpy.asarray(v) for k, v in batch.items()}
        sh = NamedSharding(self.mesh, _pspec("dp"))
        return {k: jax.device_put(v, sh) for k, v in batch.items()}

    # ------------------------------------------------------------- multi-step
    def run_steps(self, params, opt_state, aux, batch, num_steps, rng=None,
                  stacked=False):
        """Run ``num_steps + 1`` fused update steps as ONE XLA program
        (lax.scan over the step body) — the TPU-idiomatic training loop: no
        host dispatch between steps, weights never leave HBM.

        Data semantics — choose explicitly:
        - ``stacked=False`` (default): ``batch`` is ONE minibatch applied to
          every step.  That is full-batch training / benchmarking; it is NOT
          one-update-per-minibatch SGD.
        - ``stacked=True``: every leaf of ``batch`` has a leading
          ``num_steps + 1`` axis; step i consumes slice i (stage your loader
          output with ``np.stack``), giving exact minibatch-SGD semantics.

        The lr *schedule* is sampled once per chunk (host-side); Adam's
        bias correction advances per step on-device, so results match
        sequential stepping exactly.  Returns (params, opt_state, aux,
        last_outputs)."""
        import jax
        if stacked:
            for k, v in batch.items():
                if v.shape[0] != num_steps + 1:
                    raise MXNetError(
                        "run_steps(stacked=True): %s has leading axis %d, "
                        "need num_steps + 1 = %d (one minibatch per step)"
                        % (k, v.shape[0], num_steps + 1))
        if rng is None:
            rng = _random.next_key()
        hyper = self.fopt.hyper(self.num_update)
        t0 = self.num_update
        self.num_update += num_steps + 1
        # the chunk body traces executor._Lowered.run, which consults the
        # TRACE_ENV_DEFAULTS levers — key them (CKEY001) so toggling e.g.
        # MXNET_STEM_FUSE between run_steps calls retraces instead of
        # silently reusing the stale program
        cache_key = (num_steps, stacked, trace_env_key())
        fn = self._multi_cache.get(cache_key)
        if fn is None:
            step = self._step_fn
            if self._has_scale:
                # the loss-scale state rides in the scan carry: overflow
                # steps inside a fused chunk skip their update and halve
                # the scale exactly like sequential stepping
                def many(params, opt_state, aux, lsc, batch, rng, hyper,
                         t0):
                    def body(carry, i):
                        p, s, a, l = carry
                        sub = jax.random.fold_in(rng, i)
                        b = jax.tree_util.tree_map(lambda x: x[i], batch) \
                            if stacked else batch
                        p, s, a, l, outs = step(p, s, a, l, b, sub, hyper,
                                                t0 + i + 1)
                        return (p, s, a, l), _split_counters(outs)[1]
                    (p, s, a, l), counted = jax.lax.scan(
                        body, (params, opt_state, aux, lsc),
                        jax.numpy.arange(num_steps))
                    last = jax.tree_util.tree_map(
                        lambda x: x[num_steps], batch) if stacked else batch
                    res = step(p, s, a, l, last, rng, hyper,
                               t0 + num_steps + 1)
                    return res[:4] + (_sum_counters(res[4], counted),)
            else:
                def many(params, opt_state, aux, batch, rng, hyper, t0):
                    def body(carry, i):
                        p, s, a = carry
                        sub = jax.random.fold_in(rng, i)
                        b = jax.tree_util.tree_map(lambda x: x[i], batch) \
                            if stacked else batch
                        p, s, a, outs = step(p, s, a, b, sub, hyper,
                                             t0 + i + 1)
                        return (p, s, a), _split_counters(outs)[1]
                    (p, s, a), counted = jax.lax.scan(
                        body, (params, opt_state, aux),
                        jax.numpy.arange(num_steps))
                    # one extra step emitting outputs (keeps scan carry
                    # lean)
                    last = jax.tree_util.tree_map(
                        lambda x: x[num_steps], batch) if stacked else batch
                    res = step(p, s, a, last, rng, hyper,
                               t0 + num_steps + 1)
                    return res[:3] + (_sum_counters(res[3], counted),)

            many.__name__ = "mxtpu_many"
            if self.mesh is not None:
                shardings = self._in_shardings
                bi = 4 if self._has_scale else 3   # batch slot
                if stacked:
                    # batch leaves carry a leading step axis; dp shards axis 1
                    from jax.sharding import NamedSharding
                    batch_sh = {n: NamedSharding(self.mesh,
                                                 _pspec(None, "dp"))
                                for n in shardings[bi]}
                    shardings = shardings[:bi] + (batch_sh,) \
                        + shardings[bi + 1:]
                fn = jax.jit(many, in_shardings=shardings,
                             out_shardings=self._out_shardings,
                             donate_argnums=self._donate)
            else:
                fn = jax.jit(many, donate_argnums=self._donate)
            self._multi_cache[cache_key] = fn
            self._san_cache.miss({"num_steps": num_steps,
                                  "stacked": stacked,
                                  "trace_env": cache_key[2]})
            if _san._hbm_on or _san._cost_on:
                # HBM/cost attribution for the fresh chunk program,
                # captured BEFORE the first call (the arguments are still
                # alive — the call below donates them) from the very
                # values it will compile for; lower().compile() here is
                # the compile, the dispatch below reuses the executable
                cargs = (params, opt_state, aux)
                if self._has_scale:
                    cargs = cargs + (self._scale_state_dev(),)
                _san.program_capture(
                    "train_step.run_steps[n=%d%s]"
                    % (num_steps, ",stacked" if stacked else ""),
                    fn, cargs + (batch, rng, hyper, _np.int32(t0)),
                    cache=self._san_cache)
        args = (params, opt_state, aux)
        if self._has_scale:
            args = args + (self._scale_state_dev(),)
        if _san._donate_on:
            _san.check_donated("run_steps", self._donate_pairs(args))
        with _san.hot_region("run_steps"), \
                _tel.span("train_chunk", cat="executor",
                          num_update=self.num_update, num_steps=num_steps):
            res = fn(*(args + (batch, rng, hyper, _np.int32(t0))))
        if _san._donate_on:
            _san.note_donated("run_steps", self._donate_pairs(args),
                              step=self.num_update)
        if self._has_scale:
            self._scale_state = res[3]
            res = (res[0], res[1], res[2], res[4])
        return res[:3] + (_publish_counters(res[3], num_steps + 1),)

    def step_flops(self):
        """Model FLOPs of one fused step, from the cost row captured at
        the step program's compile — the MFU numerator.  None before the
        first dispatch or while cost attribution is disarmed."""
        row = self._cost_row
        return row.get("flops") if row else None

    # ------------------------------------------------------- numerics monitor
    def _monitor_stats(self, params, grads, new_params, outs, inv=None):
        """Trace-time numerics stats pytree (MXNET_MONITOR): squared
        sums reduced ON DEVICE — the host takes square roots after the
        one planned fetch.  ``grads`` is whatever the step's gradient
        residency is: the ``(layout, bucket)`` pair under ZeRO>=2 (the
        per-parameter stats slice the dp-sharded bucket columns, exactly
        like ``plan.shard_update`` — flat-shard padding is zeros, so the
        L2 sums are exact), the vjp tree otherwise.  ``inv`` (AMP)
        unscales the squared sums by ``inv**2`` so published norms are
        in unscaled units."""
        import jax.numpy as jnp
        from . import numerics as _num
        spec = _num.spec()
        stats_on = spec.stats if spec is not None else ("grad", "update")

        def up(x):
            # promote, never demote: bf16 grads reduce in f32, and an
            # f64 parity run keeps f64 exactness (the MULTICHIP_NUM
            # record gates the monitored norm against the replicated
            # one at 1e-9 — an f32 reduction only reaches ~1e-7)
            return x.astype(jnp.promote_types(x.dtype, jnp.float32))

        def sq(x):
            return jnp.sum(jnp.square(up(x)))
        inv2 = None if inv is None else jnp.square(inv.astype(jnp.float32))
        grad_sq = {}
        if self.plan.bucket_grads:
            layout, bucket = grads
            if bucket is not None:
                off = 0
                for n, c in layout:
                    s = sq(bucket[:, off:off + c])
                    grad_sq[n] = s if inv2 is None else s * inv2
                    off += c
        else:
            for n in self.param_names:
                s = sq(grads[n])
                grad_sq[n] = s if inv2 is None else s * inv2
        total = jnp.float32(0.0)
        for s in grad_sq.values():
            total = total + s
        stats = {"grad_sq_global": total,
                 "heads_finite": tuple(jnp.isfinite(o).all()
                                       for o in outs)}
        if "grad" in stats_on:
            stats["grad_sq"] = grad_sq
        if "update" in stats_on:
            # ZeRO-3 flat rows are elementwise-valid here: padding is
            # zeros in both the old and the new parameters
            stats["param_sq"] = {n: sq(params[n])
                                 for n in self.param_names}
            stats["upd_sq"] = {
                n: sq(up(new_params[n]) - up(params[n]))
                for n in self.param_names}
        if "act" in stats_on:
            stats["act_rms"] = {
                "head%d" % i: jnp.sqrt(jnp.mean(jnp.square(up(o))))
                for i, o in enumerate(outs)}
        return stats

    def _monitored_step(self):
        """The monitored-step program for the CURRENT trace env, built
        lazily on the first sampled step (monitor-off never reaches
        this, so the unmonitored program stays byte-identical)."""
        import jax
        key = trace_env_key()
        fn = self._mon_cache.get(key)
        if fn is not None:
            return fn
        if self.mesh is not None:
            if self._has_scale:
                fn = jax.jit(self._mon_fn,
                             in_shardings=self._in_shardings,
                             out_shardings=self._out_shardings + (None,),
                             donate_argnums=(0, 1, 2, 3))
            else:
                fn = jax.jit(self._mon_fn,
                             in_shardings=self._in_shardings,
                             donate_argnums=(0, 1, 2))
        else:
            fn = jax.jit(self._mon_fn, donate_argnums=self._donate)
        self._mon_cache[key] = fn
        self._san_mon_cache.miss({"trace_env": key})
        return fn

    def _publish_monitor(self, stats_dev, res, batch, rng, upd_idx, mspec):
        """Fetch the sampled step's stats (the ONE planned d2h), publish
        them to telemetry + the history ring, and — on non-finite
        dynamics — run the provenance replay, write the ``numerics``
        post-mortem bundle, and escalate per the spec."""
        import jax
        import warnings
        from . import numerics as _num
        with _san.allow_sync("numerics monitor fetch"):
            host = jax.device_get(stats_dev)
        entry = _num.publish(host, upd_idx, mspec, who="train_step")
        self._last_mon_entry = entry
        if not _num.entry_bad(entry):
            return entry
        prov = self._numerics_provenance(res, batch, rng, upd_idx)
        path, msg = _num.postmortem(prov, entry=entry)
        if mspec is not None and mspec.raise_on_nonfinite:
            raise _num.NumericsError(msg)
        warnings.warn("mxnet_tpu numerics monitor: %s" % msg)
        return entry

    def _numerics_provenance(self, res, batch, rng, upd_idx):
        """Host replay of a bad step through ``executor._Lowered.run``
        (stage-by-stage, then op-by-op).  The step's inputs are donated,
        so the replay uses the RETURNED params — exactly the pre-step
        weights when AMP's overflow skip fired (the common non-finite
        trigger), post-update otherwise (the bundle says which)."""
        import jax
        from . import numerics as _num
        params_state = "pre-update (AMP overflow skip)" \
            if self._has_scale else "post-update"
        with _san.allow_sync("numerics provenance host pull"):
            params = {n: _np.asarray(jax.device_get(v))
                      for n, v in self.gather_params(res[0]).items()}
            aux = {n: _np.asarray(jax.device_get(v))
                   for n, v in res[2].items()}
            vals = {k: _np.asarray(jax.device_get(v))
                    for k, v in batch.items()}
        if self._dtype is not None:
            vals = {k: (v.astype(self._dtype)
                        if k not in self.label_names
                        and v.dtype == _np.float32 else v)
                    for k, v in vals.items()}
            params = {k: v.astype(self._dtype) for k, v in params.items()}
        arg_vals = dict(vals)
        arg_vals.update(params)
        inputs = set(self.data_names) | set(self.label_names)
        return _num.investigate(self._low, arg_vals, aux, rng,
                                update=upd_idx, input_names=inputs,
                                params_state=params_state)

    # ------------------------------------------------------------------- call
    def __call__(self, params, opt_state, aux, batch, rng=None):
        """One fused step.  Returns (params, opt_state, aux, outputs)."""
        from . import profiler as _profiler
        from . import diagnostics as _diag
        from . import numerics as _num
        if rng is None:
            rng = _random.next_key()
        upd_idx = self.num_update
        hyper = self.fopt.hyper(self.num_update)
        self.num_update += 1
        mspec = _num.spec()
        # the legacy Monitor bridge force-samples even with MXNET_MONITOR
        # unset (the stats trace then uses the default grad+update set)
        sample = self._mon_force or (mspec is not None
                                     and mspec.due(upd_idx))
        if self._mon_force:
            self._mon_force = False
        step_prog = self._monitored_step() if sample else self._step
        if sample and self.plan.bucket_grads \
                and (_san._collective_on or _tel._enabled):
            # the per-parameter squared sums reduce across the
            # dp-sharded bucket rows inside the monitored program — a
            # psum the collective ledger should see
            n_scalars = len(self.param_names) + 1
            if _san._collective_on:
                _san.note_collective(
                    "mxtpu_monitor_psum", name="grad_stats",
                    sig=("%d scalars" % n_scalars,), axes="dp")
            _san.record_wire_bytes("mxtpu_monitor_psum", axes="dp",
                                   nbytes=4 * n_scalars)
        args = (params, opt_state, aux)
        if self._has_scale:
            args = args + (self._scale_state_dev(),)
        if (_san._hbm_on or _san._cost_on) and not self._hbm_done:
            # HBM/cost attribution for the step program — once per
            # instance, BEFORE the first (donating) dispatch so the
            # captured arguments are still alive.  The jitted callable
            # itself is NOT wrapped: __graft_entry__ AOT-lowers
            # self._step directly
            self._hbm_done = True
            cap = _san.program_capture(
                "train_step[%s]" % self._step_fn.__name__, self._step,
                args + (batch, rng, hyper, _np.int32(self.num_update)),
                cache=self._san_cache)
            if cap and cap.get("cost"):
                self._cost_row = cap["cost"]
        if _san._donate_on:
            # a buffer donated by an earlier step re-entering here is the
            # delete-on-donate bug — name it before XLA crashes cryptically
            _san.check_donated("train_step", self._donate_pairs(args))
        # the span is the host's launch of the step program: nothing here
        # waits for the device, whose time is the device trace's to give
        with _profiler.Scope("train_step[%d]" % self.num_update,
                             "symbolic"), \
                _san.hot_region("train_step"), \
                _tel.span("train_step", cat="executor",
                          num_update=self.num_update):
            res = step_prog(*args, batch, rng, hyper,
                            _np.int32(self.num_update))
        if _san._donate_on:
            _san.note_donated("train_step", self._donate_pairs(args),
                              step=self.num_update)
        stats_dev = None
        if sample:
            stats_dev = res[-1]
            res = res[:-1]
        if self._has_scale:
            self._scale_state = res[3]
            res = (res[0], res[1], res[2], res[4])
        res = res[:3] + (_publish_counters(res[3], 1),)
        if self._has_scale:
            if _tel._enabled and self._amp_emit \
                    and _tel.scalar_due(self.num_update):
                # bounded telemetry sync: scale gauge + overflow counter
                scale, overflow = self.amp_stats()
                _tel.gauge("loss_scale", scale)
                if overflow:
                    _tel.counter("amp_overflow_steps", overflow)
        if _tel._enabled and self.zero:
            # per-device residency per the placement plan — shape
            # metadata only, no syncs (strict no-op with telemetry off);
            # invariant for a step instance, so walked once and cached
            zb = self._zb_cache
            if zb is None:
                zb = self._zb_cache = self.zero_bytes(res[0], res[1])
            _tel.gauge("zero_param_bytes", zb["param"], level=self.zero)
            _tel.gauge("zero_grad_bytes", zb["grad"], level=self.zero)
        if _diag._armed:
            _diag.heartbeat(train_step=self.num_update)
        mode = _diag.check_numerics_mode() if self.check_numerics else None
        if mode is not None:
            # grads/updates live inside the donated XLA program — the
            # outputs (loss heads) are the observable surface here
            _diag.check_outputs(res[3], mode, where="train_step",
                                num_update=self.num_update)
        if stats_dev is not None:
            self._publish_monitor(stats_dev, res, batch, rng, upd_idx,
                                  mspec)
        return res


class EvalStep(object):
    """Jitted forward-only step (inference path; parity: the predict API's
    forward-only executor, reference src/c_api/c_predict_api.cc)."""

    def __init__(self, symbol, mesh=None, dtype=None,
                 label_names=("softmax_label",), policy=None):
        import jax
        from .executor import _Lowered
        if policy is not None:
            # forward-only: the policy contributes its compute dtype (no
            # loss scaling without a backward pass)
            from . import amp as _amp
            if dtype is not None:
                raise MXNetError(
                    "EvalStep: pass either dtype= or policy=, not both")
            policy = _amp.resolve_policy(policy)
            if policy.compute_dtype != "float32":
                dtype = policy.compute_dtype
        low = _Lowered(symbol)
        self._low = low
        self.mesh = mesh
        label_names = tuple(label_names)

        def fwd(params, aux, batch, rng):
            vals = dict(batch)
            if dtype is not None:
                # labels keep their dtype (bfloat16 rounds class ids)
                vals = {k: (v.astype(dtype) if k not in label_names
                            and v.dtype == _np.float32 else v)
                        for k, v in vals.items()}
                params = {k: v.astype(dtype) for k, v in params.items()}
            vals.update(params)
            outs, _ = low.run(vals, aux, rng, False)
            return tuple(outs)

        if mesh is not None:
            from jax.sharding import NamedSharding
            rep = NamedSharding(mesh, _pspec())
            data_sh = NamedSharding(mesh, _pspec("dp"))
            self._fwd = jax.jit(fwd, in_shardings=(None, None, data_sh, rep))
        else:
            self._fwd = jax.jit(fwd)

    def __call__(self, params, aux, batch, rng=None):
        if rng is None:
            rng = _random.next_key()
        return self._fwd(params, aux, batch, rng)


class PipelineTrainStep(object):
    """Stage-partitioned, microbatched training over the ``pp`` mesh axis
    (GPipe rebuilt TPU-natively; parity: the reference's executor graph
    partitioning for model parallelism, PAPER.md §4a).

    The symbol's op sequence is cut into ``pp`` contiguous stages
    (``executor._Lowered.stage_partition`` — fusion-glue-legal cuts,
    parameter-footprint balanced), stage ``s`` living on slice ``s`` of the
    mesh's ``pp`` axis (``parallel.mesh.pp_submeshes``); each global batch
    splits into ``M`` microbatches and runs the configured dispatch
    schedule (per-stage jitted programs dispatched in dependency order —
    stages on disjoint device slices overlap through XLA's async
    dispatch), then one optimizer update per stage.  Activations cross
    stage boundaries as explicit resharding transfers
    (``jax.device_put`` onto the next stage's sub-mesh, dp-sharded), so the
    runtime inserts the device-to-device copies.

    Schedules (``schedule=`` / ``MXNET_PP_SCHEDULE``; parallel/schedule.py
    generates and scores the dispatch orders, and the executed order is
    asserted against :func:`pipeline_bubble_fraction` at plan build):

    - ``'gpipe'`` (default): forward wave then backward wave.  Idle share
      ``(pp-1)/(pp-1+M)``; every in-flight microbatch's boundary
      activations stay stashed through the forward wave (memory grows
      with M).
    - ``'1f1b'``: per-stage warm-up forwards, then the steady state
      interleaves one forward with one backward — same bubble, but at
      most ``min(M, pp)`` microbatches' boundary activations are ever
      live per slice (bounded by pp, not M).
    - ``'interleaved'``: the symbol is cut into ``pp x v`` *virtual*
      stages (``interleave=`` / ``MXNET_PP_INTERLEAVE``, default v=2) and
      slice ``d`` owns chunks ``{d, d+pp, ...}``; each fill/drain ramp
      costs one chunk, so the bubble drops to ``(pp-1)/((pp-1)+v*M)``.
      Needs ``M % pp == 0``.

    On a ``dp x pp`` mesh the v2 schedules (1f1b/interleaved) also overlap
    the dp gradient communication: per-stage gradients accumulate as flat
    ``(dp, chunk)`` bucket shards (each microbatch backward pays a
    reduce-scatter instead of a full all-reduce) and the stage's one
    bucketed all-gather is issued the moment its backward wave completes,
    hiding under the other slices' compute; ZeRO updates consume the
    shards directly and skip the gather entirely.  GPipe keeps PR 10's
    byte-identical in-program reduction.

    Composition:
    - **dp**: a ``dp x pp`` mesh shards every microbatch over the stage
      sub-mesh's ``dp`` axis; XLA reduces the per-stage gradients over dp
      inside each stage program.
    - **AMP** (``policy=``): the loss scale is injected at the final
      stage's loss heads (the executor scale-backward identity), rides the
      carry cotangents through every stage, and the loss-scale state lives
      donated on the final stage's sub-mesh; per-stage finite flags
      combine there ON DEVICE, and each stage's update skips in a
      ``lax.cond`` on overflow — no host syncs.
    - **ZeRO levels** (``zero=0|1|2|3``; bool accepted — ``True`` is
      level 1): the placement plan applies per stage over its sub-mesh's
      dp axis exactly like ``TrainStep``.  Level 1 shards each stage's
      optimizer step; level 2 makes the stage's flat ``(dp, chunk)``
      gradient bucket the ONLY gradient residency on every schedule
      (one all-gather of updated params per stage per step); level 3
      shards the stage's parameters themselves — the stage fwd/bwd
      programs gather full weights just-in-time and free them when the
      program retires, so per-device model footprint scales
      ~1/(pp*dp).  See docs/distributed.md "ZeRO levels".
    - **donation**: per-stage params/optimizer state (and the loss-scale
      state) are donated to their update programs; gradient accumulators
      are donated through the backward wave.

    Semantics vs the single-program ``TrainStep`` (same global batch, same
    update count): per-sample loss heads (``normalization='null'``, the
    default) accumulate to the identical gradient; ``'batch'``-normalized
    heads are compensated exactly by folding ``1/M`` into the head-grad
    scale; ``'valid'`` is rejected under M>1.  BatchNorm batch statistics
    are computed per microbatch (the moving stats chain through the
    microbatches in order), so BN nets match the single-program step
    exactly only at M=1 — the standard gradient-accumulation caveat; see
    docs/distributed.md "Pipeline parallelism".  The backward wave
    rematerialises each stage's forward (GPipe's memory-lean schedule):
    only the boundary activations of in-flight microbatches are stashed.

    Call :meth:`init` (or the ``place_*`` helpers) before stepping — the
    stage plan is balanced from real parameter sizes and every buffer is
    placed on its stage's sub-mesh.
    """

    def __init__(self, symbol, optimizer, data_names=("data",),
                 label_names=("softmax_label",), mesh=None,
                 num_microbatches=None, zero=False, policy=None, dtype=None,
                 schedule=None, interleave=None):
        from .base import get_env
        from .executor import _Lowered
        from .parallel import schedule as _sched
        if mesh is None or "pp" not in mesh.axis_names:
            raise MXNetError(
                "PipelineTrainStep needs a mesh with a 'pp' axis "
                "(parallel.mesh.make_pp_mesh)")
        extra = set(mesh.axis_names) - {"dp", "pp"}
        if extra:
            raise MXNetError(
                "PipelineTrainStep composes with dp only; mesh axes %s "
                "are not supported yet" % sorted(extra))
        if policy is not None:
            from . import amp as _amp
            if dtype is not None:
                raise MXNetError(
                    "PipelineTrainStep: pass either dtype= (pure cast) or "
                    "policy= (cast + loss scaling), not both")
            policy = _amp.resolve_policy(policy)
            if policy.compute_dtype != "float32":
                dtype = policy.compute_dtype
        self.policy = policy
        self._has_scale = policy is not None
        self._scale_state = None
        self._scale_device = None     # _FusedFit compat (placement is
        self._overflow_seen = 0       # per-stage here, not device-pinned)
        self._amp_emit = True
        self.symbol = symbol
        self.mesh = mesh
        shape = dict(mesh.shape)
        self._pp = int(shape["pp"])
        self._dp = int(shape.get("dp", 1))
        self._micro = int(num_microbatches) if num_microbatches is not None \
            else self._pp
        if self._micro < 1:
            raise MXNetError("PipelineTrainStep: num_microbatches must be "
                             ">= 1, got %d" % self._micro)
        # schedule layer (docs/distributed.md "Pipeline schedules"):
        # gpipe (fill/drain), 1f1b (steady-state one-forward-one-backward;
        # boundary-activation stash bounded by pp, not M), interleaved
        # (pp x v virtual stages per 1F1B slot; bubble / v).  Arguments
        # default to the MXNET_PP_SCHEDULE / MXNET_PP_INTERLEAVE levers —
        # dispatch-time reads (the fused-fit cache keys on them).
        if schedule is None:
            schedule = get_env("MXNET_PP_SCHEDULE", "gpipe")
        if interleave is None:
            interleave = get_env("MXNET_PP_INTERLEAVE", None, typ=int)
            if interleave is None:
                interleave = 2 if str(schedule).lower() == "interleaved" \
                    else 1
        self._schedule, self._v = _sched.validate_schedule(
            schedule, self._pp, self._micro, interleave)
        # virtual stage count: device slice d owns the v non-contiguous
        # chunks {d, d+pp, ...}; v == 1 keeps physical stages
        self._V = self._pp * self._v
        # overlapped dp gradient communication (v2 schedules on a dp x pp
        # mesh): gradients accumulate as flat (dp, chunk) bucket shards —
        # each microbatch backward pays a reduce-scatter instead of a full
        # all-reduce — and the one bucketed all-gather per stage is issued
        # as soon as that stage's backward wave completes, hiding under
        # the other slices' compute (ZeRO updates consume the shards
        # directly; no gather at all).  GPipe keeps PR 10's byte-identical
        # in-program reduction.
        self._overlap = self._dp > 1 and self._schedule != "gpipe"
        # ZeRO levels compose with every schedule (the placement plan is
        # a schedule-orthogonal knob — docs/distributed.md "ZeRO
        # levels"): level >= 2 makes the per-stage flat (dp, chunk)
        # bucket the ONLY gradient residency on every schedule (not just
        # the overlapped v2 paths), level 3 shards each stage's
        # parameters over its sub-mesh's dp and gathers them
        # just-in-time inside the stage's fwd/bwd programs — per-device
        # model footprint scales ~1/(pp*dp).
        self.zero = normalize_zero(zero)
        if self.zero and "dp" not in mesh.axis_names:
            raise MXNetError(
                "PipelineTrainStep(zero=%d) needs a mesh with a 'dp' "
                "axis to shard over" % self.zero)
        self._bucket = self.zero >= 2 or self._overlap
        self.plan = PlacementPlan(zero=self.zero, dp=self._dp,
                                  who="PipelineTrainStep")
        self._zb_cache = None   # zero_*_bytes gauge memo (step-invariant)
        self._dtype = dtype
        self._low = _Lowered(symbol)
        self.data_names = tuple(data_names)
        self.label_names = tuple(label_names)
        self._inputs_all = set(self.data_names) | set(self.label_names)
        self.param_names = [n for n in self._low.arg_names
                            if n not in self._inputs_all]
        self.aux_names = list(self._low.aux_names)
        self.fopt = _FunctionalOptimizer(optimizer, self.param_names)
        self.optimizer = optimizer
        self.num_update = 0
        self.check_numerics = True
        from .parallel import mesh as mesh_mod
        self._subs = mesh_mod.pp_submeshes(mesh)
        # stage plan is finalised lazily with real parameter sizes (init/
        # place_params) so the cut balances the per-stage footprint
        self._stages = None
        self._var_stage = {}
        self._stage_has_loss = None
        self._micro_comp = False
        self._progs = {}
        # per-step live-byte accounting (params/state/aux plus the PEAK
        # boundary-activation stash per device slice, tracked at dispatch
        # time from shape metadata — no syncs); mirrors the
        # pp_stage<N>_live_bytes gauges, readable with telemetry off
        self.last_live_bytes = None
        # MXNET_MONITOR state (mirrors TrainStep): force-sample hook for
        # the legacy Monitor bridge + the last published ring entry
        self._mon_force = False
        self._last_mon_entry = None
        # mxsan RECOMPILE: the per-(kind, stage, trace-env) program cache
        # (CKEY001 CACHES entry: tools/mxlint/rule_ckey.py).  One env
        # snapshot costs at most fwd/bwd/upd/zeros per virtual stage plus
        # the AMP fin/auxsel/scale and overlap gather programs — and,
        # under MXNET_MONITOR, a stats program per virtual stage plus the
        # final stage's loss-head finite/RMS program.
        self._san_cache = _san.register_cache(
            "pipeline.stages", kind="pipeline", owner=self,
            sizer=lambda ps: len(ps._progs), warmup=9 * self._V + 3,
            jit_names=("mxtpu_pp_fwd", "mxtpu_pp_bwd", "mxtpu_pp_upd",
                       "mxtpu_pp_zeros", "mxtpu_pp_fin", "mxtpu_pp_scale",
                       "mxtpu_pp_auxsel", "mxtpu_pp_gather",
                       "mxtpu_pp_stats", "mxtpu_pp_headsfin"))
        # the dispatch-plan cache: per-(schedule, interleave, M, trace-env)
        # merged work-item order + its simulated bubble (CKEY001 CACHES
        # entry; pure host-side python — the plan's stage programs land in
        # the pipeline.stages cache above, keyed by the same trace env)
        self._plans = {}
        self._san_plan_cache = _san.register_cache(
            "pipeline.schedule", kind="pipeline_plan", owner=self,
            sizer=lambda ps: len(ps._plans), warmup=2)

    # ------------------------------------------------------------- planning
    def _ensure_plan(self, param_sizes=None):
        if self._stages is not None:
            return
        # pp x v chunks: the interleaved schedule's virtual stages are
        # plain stage_partition cuts; chunk k runs on device slice k % pp
        self._stages = self._low.stage_partition(
            self._V, input_names=self._inputs_all, param_sizes=param_sizes)
        for st in self._stages:
            for n in list(st.params) + list(st.aux):
                self._var_stage[n] = st.index
        has_loss = [False] * self._V
        norm_modes = set()
        for st in self._stages:
            for n in st.nodes:
                if not n.is_var and getattr(n.op, "is_loss", False):
                    has_loss[st.index] = True
                    norm_modes.add(n.op.normalize_attrs(n.params)
                                   .get("normalization") or "null")
        self._stage_has_loss = has_loss
        if self._micro > 1 and "valid" in norm_modes:
            raise MXNetError(
                "pipeline microbatching: a loss head uses "
                "normalization='valid' — its per-microbatch valid count "
                "cannot be folded into a constant head-grad scale; use "
                "'null'/'batch' normalization or num_microbatches=1")
        if self._micro > 1 and "batch" in norm_modes and len(norm_modes) > 1:
            raise MXNetError(
                "pipeline microbatching: loss heads mix 'batch' and "
                "per-sample normalization — one head-grad scale cannot "
                "compensate both")
        # 'batch'-normalized heads divide by the MICROBATCH size, so the
        # accumulated gradient needs an exact 1/M on the head scale
        self._micro_comp = (self._micro > 1 and norm_modes == {"batch"})

    def stages(self):
        """The stage plan (list of executor._Stage; finalised lazily).
        ``pp * interleave`` virtual stages; stage ``k`` lives on device
        slice ``k % pp``."""
        return self._stages

    def _sub(self, k):
        """Device-slice sub-mesh of virtual stage ``k`` (round-robin:
        slice ``k % pp`` owns chunks {d, d+pp, ...})."""
        return self._subs[k % self._pp]

    def schedule(self):
        """(schedule_name, interleave) of this step's dispatch plan."""
        return self._schedule, self._v

    def _get_plan(self):
        """The merged dispatch plan for this step's (schedule, interleave,
        M): work items in simulated-slot order plus the executed bubble
        fraction, asserted against the closed form.  Keyed on
        ``trace_env_key()`` for contract uniformity with the stage-program
        cache it drives (CKEY001) — a rebuild is pure host-side python."""
        from .parallel import schedule as _sched
        key = (self._schedule, self._v, self._micro, trace_env_key())
        plan = self._plans.get(key)
        if plan is not None:
            return plan
        orders = _sched.stage_orders(self._pp, self._micro, self._schedule,
                                     self._v)
        if self._schedule == "gpipe":
            # PR 10's literal dispatch order (m-major waves) — the
            # MXNET_PP_SCHEDULE-unset path stays byte-identical; the
            # simulation still scores the per-slice order
            sim = _sched.simulate(orders, self._pp, self._v)
            items = [("fwd", m, k) for m in range(self._micro)
                     for k in range(self._V)]
            items += [("bwd", m, k) for m in reversed(range(self._micro))
                      for k in reversed(range(self._V))]
        else:
            items, sim = _sched.dispatch_order(orders, self._pp, self._v)
        want = pipeline_bubble_fraction(self._pp, self._micro, self._v)
        if abs(sim["bubble"] - want) > 1e-9:
            raise MXNetError(
                "pipeline schedule %s: executed idle share %.6f does not "
                "match pipeline_bubble_fraction(pp=%d, M=%d, v=%d)=%.6f"
                % (self._schedule, sim["bubble"], self._pp, self._micro,
                   self._v, want))
        # last backward per virtual stage: where the overlap path issues
        # the stage's bucketed gradient gather
        last_bwd = {}
        for i, (kind, m, k) in enumerate(items):
            if kind == "bwd":
                last_bwd[k] = i
        plan = {"items": items, "bubble": sim["bubble"],
                "last_bwd": last_bwd}
        self._plans[key] = plan
        self._san_plan_cache.miss({"schedule": self._schedule,
                                   "interleave": self._v,
                                   "microbatches": self._micro,
                                   "trace_env": key[3]})
        return plan

    # ----------------------------------------------------------- placement
    def _stage_of_var(self, name):
        if self._stages is None:
            raise MXNetError(
                "PipelineTrainStep: call init() or place_params() before "
                "placing %s — the stage plan is balanced from parameter "
                "sizes" % name)
        return self._var_stage[name]

    def param_sharding(self, name):
        """NamedSharding of ``name``'s RESIDENT parameter buffer on its
        stage sub-mesh: replicated below ZeRO level 3, flat dp-sharded
        at level 3 (the placement plan's spec)."""
        from jax.sharding import NamedSharding
        return NamedSharding(self._sub(self._stage_of_var(name)),
                             self.plan.param_spec(name))

    def _rep_sharding(self, name):
        """Replicated NamedSharding on ``name``'s stage sub-mesh (aux
        state stays replicated at every ZeRO level)."""
        from jax.sharding import NamedSharding
        return NamedSharding(self._sub(self._stage_of_var(name)), _pspec())

    def place_params(self, host_params):
        """Host {name: array} -> per-stage device placement (finalising
        the stage plan from the real parameter sizes on first use;
        ZeRO-3 flattens each tensor to its (dp, chunk) shards)."""
        import jax
        self._ensure_plan({n: int(_np.asarray(v).size)
                           for n, v in host_params.items()})
        self.plan.note_host(host_params)
        if self.zero >= 3:
            return {n: jax.device_put(_flat_np(v, self._dp),
                                      self.param_sharding(n))
                    for n, v in host_params.items()}
        return {n: jax.device_put(_np.asarray(v), self.param_sharding(n))
                for n, v in host_params.items()}

    def place_aux(self, host_aux):
        import jax
        if self._stages is None:
            raise MXNetError("PipelineTrainStep: place_params() first")
        return {n: jax.device_put(_np.asarray(v), self._rep_sharding(n))
                for n, v in host_aux.items()}

    def unflatten_host(self, name, arr):
        """Host array of a sharded leaf -> the logical tensor (sync-back/
        export half of the plan's layouts)."""
        return self.plan.unflatten_host(name, arr)

    def zero_bytes(self, params, opt_state=None):
        """Worst-slice per-device {param, grad, opt} byte residency of
        the placement plan — shape metadata only (the ``zero_*_bytes``
        gauge source; readable with telemetry off)."""
        per = {}
        for st in self._stages:
            d = st.index % self._pp
            sub_p = {n: params[n] for n in st.params}
            sub_s = {n: opt_state[n] for n in st.params} \
                if opt_state is not None else None
            zb = self.plan.per_device_bytes(sub_p, sub_s)
            acc = per.setdefault(d, {"param": 0, "grad": 0, "opt": 0})
            for k in acc:
                acc[k] += zb[k]
        out = {"param": 0, "grad": 0, "opt": 0}
        for d, zb in per.items():
            for k in out:
                out[k] = max(out[k], zb[k])
        return out

    def place_state(self, host_state):
        """Host optimizer state {name: tuple(arrays)} -> stage placement
        (replicated mode; ``zero=True`` state is born sharded in init())."""
        import jax
        if self.zero:
            raise MXNetError("PipelineTrainStep(zero=True): optimizer "
                             "state is born dp-sharded — use init()")
        if self._stages is None:
            raise MXNetError("PipelineTrainStep: place_params() first")
        return {n: tuple(jax.device_put(_np.asarray(s),
                                        self.param_sharding(n))
                         for s in st)
                for n, st in host_state.items()}

    def init(self, data_shapes, label_shapes=None, initializer=None, seed=0):
        """Infer shapes, initialise params/aux, build optimizer state and
        place every pytree on its stage's sub-mesh (mirrors
        ``TrainStep.init``)."""
        import jax
        from jax.sharding import NamedSharding
        params, aux = _host_init(self.symbol, self._low, self.param_names,
                                 self.aux_names, data_shapes, label_shapes,
                                 initializer, seed, "PipelineTrainStep")
        self._ensure_plan({n: int(v.size) for n, v in params.items()})
        dev_params = self.place_params(params)
        dev_aux = self.place_aux(aux)
        if self.zero:
            host_state = self.plan.state_host(self.fopt, params)
            dev_state = {}
            for n, st in host_state.items():
                sh = NamedSharding(self._sub(self._var_stage[n]),
                                   _pspec("dp"))
                dev_state[n] = tuple(jax.device_put(s, sh) for s in st)
        else:
            dev_state = self.place_state(self.fopt.init_state(params))
        return dev_params, dev_state, dev_aux

    def shard_batch(self, batch):
        """Pipeline batches stay on the host: __call__ splits them into
        microbatches and stages each slice onto its consuming stage's
        sub-mesh itself (API parity with TrainStep.shard_batch)."""
        return {k: _np.asarray(v) if not hasattr(v, "devices") else v
                for k, v in batch.items()}

    def output_sharding(self):
        """Replicated sharding on the FINAL stage's sub-mesh — where the
        step's outputs live (fit stages labels here so the metric's
        same-device lazy reduction engages)."""
        from jax.sharding import NamedSharding
        return NamedSharding(self._subs[-1], _pspec())

    # ----------------------------------------------------------- checkpoint
    def checkpoint_topology(self):
        """Shard ownership for the sharded checkpoint writer: each
        parameter/aux tensor belongs to its pipeline stage (the stage
        partition map rides in the manifest so restore can re-shard onto
        a different stage count), optimizer state is per-stage —
        dp-sharded under ``zero=True``.  Requires the stage plan
        (call init()/place_params() first)."""
        if self._stages is None:
            raise MXNetError(
                "PipelineTrainStep.checkpoint_topology: call init() or "
                "place_params() first — the stage plan is balanced from "
                "parameter sizes")
        topo = {"pp": self._pp,
                "dp": self._dp,
                "zero": self.zero,
                "microbatches": self._micro,
                "schedule": self._schedule,
                "interleave": self._v,
                "stage_of": dict(self._var_stage)}
        if self.zero >= 3:
            # level 3 param buffers are flat rows — the writer needs the
            # logical shapes to stamp the manifest restore contract
            topo["param_shapes"] = {n: list(self.plan.shape_of(n))
                                    for n in self.param_names}
        return topo

    def place_checkpoint(self, host_params, host_state, host_aux,
                         device=None):
        """Place restored HOST pytrees onto this pipeline's stages
        (``host_state`` leaves arrive in the LOGICAL parameter shape;
        ``zero=True`` cuts them over each stage sub-mesh's dp in the
        plan's form of each leaf).
        ``device`` is accepted for TrainStep API parity and ignored —
        placement here is per stage sub-mesh."""
        import jax
        from jax.sharding import NamedSharding
        self._ensure_plan({n: int(_np.asarray(v).size)
                           for n, v in host_params.items()})
        params = self.place_params(host_params)
        aux = self.place_aux(host_aux)
        if self.zero:
            state = {}
            for n, st in host_state.items():
                sh = NamedSharding(self._sub(self._var_stage[n]),
                                   _pspec("dp"))
                state[n] = tuple(jax.device_put(self.plan.shards_np(s), sh)
                                 for s in st)
        else:
            state = self.place_state(host_state)
        return params, state, aux

    def scale_state_host(self):
        """Loss-scale state as host scalars, or None without a policy
        (mirrors TrainStep.scale_state_host)."""
        return _scale_state_to_host(self)

    def export_host(self, params, opt_state, aux):
        """LOGICAL host export of a live pipelined training state
        (mirrors TrainStep.export_host — same snapshot/reassemble round
        trip, with the stage partition merged away; the live-resize
        re-shard path)."""
        from . import checkpoint as _ckpt
        return _ckpt.reassemble(_ckpt.snapshot(self, params, opt_state,
                                               aux))

    def load_scale_state(self, host):
        """Restore the loss-scale automaton onto the final stage's
        sub-mesh (no-op without a policy)."""
        if not self._has_scale or host is None:
            return
        import jax
        from jax.sharding import NamedSharding
        base = self.policy.init_state()
        dst = NamedSharding(self._subs[-1], _pspec())
        self._scale_state = {
            k: jax.device_put(_np.asarray(host.get(k, base[k]),
                                          base[k].dtype), dst)
            for k in base}
        self._overflow_seen = int(host.get("overflow", 0))

    # ------------------------------------------------------------ programs
    def _get_prog(self, kind, stage):
        """Per-(kind, stage) jitted program; every program traces
        ``executor._Lowered.run`` (layout/fusion env levers), so the cache
        keys on ``trace_env_key()`` — toggling e.g. MXNET_STEM_FUSE between
        steps retraces instead of reusing the stale program (CKEY001)."""
        env_key = trace_env_key()
        key = (kind, stage, env_key)
        fn = self._progs.get(key)
        if fn is not None:
            return fn
        fn = self._build_prog(kind, stage)
        self._progs[key] = fn
        self._san_cache.miss({"kind": kind, "stage": stage,
                              "trace_env": env_key})
        return fn

    def _carry_spec(self, x, sub):
        """dp-shard a carried activation's leading (microbatch) axis when
        it divides, replicate otherwise — the one deterministic boundary
        interface both the producing constraint and the hand-off
        device_put use."""
        dp = int(dict(sub.shape).get("dp", 1))
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] % dp == 0:
            return _pspec("dp")
        return _pspec()

    def _build_prog(self, kind, s):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        stage = self._stages[s]
        sub = self._sub(s)
        low = self._low
        dtype = self._dtype
        label_names = set(self.label_names)
        rep = NamedSharding(sub, _pspec())
        micro = self._micro

        plan = self.plan
        zero3 = self.zero >= 3

        def run_fwd(params, aux, carry, extra, rng, scale=None):
            if zero3:
                # ZeRO-3: the stage's resident params are flat (dp,
                # chunk) shards — gather the full weights just-in-time
                # (freed when the stage program retires; the bwd vjp
                # transposes this gather into the reduce-scatter that
                # lands each device's gradient shard)
                params = plan.gather_params(params, sub)
            vals = dict(extra)
            if dtype is not None:
                # data inputs cast, labels kept (bfloat16 rounds class
                # ids); carried activations arrive already in compute
                # dtype from the previous stage
                vals = {k: (v.astype(dtype)
                            if k not in label_names
                            and v.dtype == _np.float32 else v)
                        for k, v in vals.items()}
                params = {k: v.astype(dtype) for k, v in params.items()}
            vals.update(params)
            return low.run(vals, aux, rng, True,
                           no_grad_inputs=self._inputs_all,
                           head_grad_scale=scale, stage=stage,
                           carry_vals=list(carry))

        def sub_rng(rng, m):
            # M=1 keeps the base key so a one-microbatch pipeline matches
            # the single-program step bit-for-bit through stochastic ops
            return rng if micro == 1 else jax.random.fold_in(rng, m)

        def carry_pin(x):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(sub, self._carry_spec(x, sub)))

        names = list(stage.params)
        dp = self._dp
        sh_dp = NamedSharding(sub, _pspec("dp"))
        # the flat (dp, chunk) bucket is the gradient residency when the
        # overlapped dp comm engages (v2 schedules, dp > 1) OR at ZeRO
        # level >= 2 on ANY schedule (the bucket is then the only place
        # gradients ever live)
        overlap = self._bucket

        def bucket_chunks(params):
            """Static (name, chunk_rows) layout of this stage's flat
            gradient bucket: per-param ZeRO-flat ``(dp, chunk)`` views
            concatenated along the chunk axis, so row ``d`` holds device
            ``d``'s shard of every parameter contiguously.  Widths come
            from ``_chunk_rows`` — the same helper ``_flat_shards`` uses
            to BUILD the views ``accumulate`` concatenates, so the
            gather/update offsets can never drift from the layout."""
            out = []
            for n in names:
                size = 1
                for dim in params[n].shape:
                    size *= dim
                out.append((n, _chunk_rows(size, dp)))
            return out

        if kind == "fwd":
            def fwd(params, aux, carry, extra, rng, m):
                outs, aux_upd, carry_out = run_fwd(params, aux, carry,
                                                   extra, sub_rng(rng, m))
                new_aux = dict(aux)
                new_aux.update({k: v.astype(aux[k].dtype)
                                for k, v in aux_upd.items() if k in aux})
                carry_out = tuple(carry_pin(c) for c in carry_out)
                if stage.final and self._has_scale:
                    # the loss surface crosses back f32 under a policy
                    # (metrics, sentinels) — mirrors TrainStep
                    outs = tuple(o.astype(jnp.float32) for o in outs)
                return new_aux, tuple(outs), carry_out
            fwd.__name__ = "mxtpu_pp_fwd"
            return jax.jit(fwd)

        if kind == "bwd":
            # backward = rematerialised stage forward under jax.vjp (the
            # memory-lean GPipe schedule: only boundary activations are
            # stashed between the waves); gradients accumulate into the
            # donated per-stage accumulator
            scaled = self._stage_has_loss[s] and \
                (self._has_scale or self._micro_comp)
            comp = jnp.float32(1.0 / micro) if self._micro_comp else None

            if overlap:
                def accumulate(params, gp, acc):
                    # overlapped dp comm: fold this microbatch's gradients
                    # into the flat (dp, chunk) bucket — the dp-sharded
                    # constraint lowers the reduction as a reduce-scatter
                    # (half an all-reduce per microbatch); the gather half
                    # is issued once, when the stage's backward wave
                    # completes
                    if not names:
                        return acc
                    flat = jnp.concatenate(
                        [_flat_shards(gp[n].astype(acc.dtype), dp)
                         for n in names], axis=1)
                    return acc + jax.lax.with_sharding_constraint(flat,
                                                                  sh_dp)
            else:
                def accumulate(params, gp, acc):
                    return {n: acc[n] + gp[n].astype(acc[n].dtype)
                            for n in acc}

            def bwd_core(params, carry, aux, extra, gout, acc, rng, m,
                         scale):
                def f(p, c):
                    outs, _aux, carry_out = run_fwd(p, aux, c, extra,
                                                    sub_rng(rng, m), scale)
                    return tuple(carry_out), tuple(outs)
                (co, outs), vjp_fn = jax.vjp(f, params, tuple(carry))
                cot = (tuple(gout),
                       tuple(jnp.ones(o.shape, o.dtype) for o in outs))
                gp, gc = vjp_fn(cot)
                return gc, accumulate(params, gp, acc)

            if scaled and self._has_scale:
                def bwd(params, carry, aux, extra, gout, acc, rng, m,
                        scale):
                    hs = scale * comp if comp is not None else scale
                    return bwd_core(params, carry, aux, extra, gout, acc,
                                    rng, m, hs)
            elif scaled:
                def bwd(params, carry, aux, extra, gout, acc, rng, m):
                    return bwd_core(params, carry, aux, extra, gout, acc,
                                    rng, m, comp)
            else:
                def bwd(params, carry, aux, extra, gout, acc, rng, m):
                    return bwd_core(params, carry, aux, extra, gout, acc,
                                    rng, m, None)
            bwd.__name__ = "mxtpu_pp_bwd"
            return jax.jit(bwd, donate_argnums=(5,))

        if kind == "zeros":
            if overlap:
                def zeros(params):
                    chunks = bucket_chunks(params)
                    width = sum(c for _, c in chunks)
                    dt = jnp.result_type(*[params[n].dtype
                                           for n in names]) \
                        if names else jnp.float32
                    # a constraint, not out_shardings: a parameter-less
                    # stage's bucket is (dp, 0), which XLA replicates, and
                    # jit asserts on an output sharding XLA overrode
                    return jax.lax.with_sharding_constraint(
                        jnp.zeros((dp, width), dt), sh_dp)
                zeros.__name__ = "mxtpu_pp_zeros"
                return jax.jit(zeros)

            def zeros(params):
                return {n: jnp.zeros(v.shape, v.dtype)
                        for n, v in params.items()}
            zeros.__name__ = "mxtpu_pp_zeros"
            return jax.jit(zeros, out_shardings=rep)

        if kind == "gather":
            # the stage's bucketed gradient reduction: one all-gather of
            # the accumulated flat shards back to full-shape gradients,
            # dispatched as soon as the stage's backward wave completes so
            # the collective hides under the other slices' compute (the
            # ZeRO update skips this — it consumes the shards directly)
            def gather(params, acc):
                out = {}
                off = 0
                for n, c in bucket_chunks(params):
                    out[n] = _from_flat_shards(acc[:, off:off + c],
                                               params[n].shape)
                    off += c
                return out
            gather.__name__ = "mxtpu_pp_gather"
            # the bucket is NOT donated: its (dp, chunk) layout can never
            # back the replicated outputs (XLA would warn and ignore);
            # __call__ drops its reference instead, freeing it on execute
            return jax.jit(gather, out_shardings=rep)

        if kind == "upd":
            zero = self.zero
            # ZeRO + bucket: the update consumes the flat (dp, chunk)
            # gradient bucket directly — the reduce-scatters inside the
            # backward wave already placed each device's shard, so the
            # stage's dp communication is DONE when its backward finishes
            bucket = overlap and zero

            def upd_math(params, grads, opt_state, hyper, t, rng):
                if zero >= 2:
                    # levels 2/3: the plan's sharded update over the
                    # stage bucket — level 2 re-materialises replicated
                    # params with ONE all-gather of the updated rows,
                    # level 3 keeps params as resident flat shards
                    return plan.shard_update(
                        self.fopt, params, grads, bucket_chunks(params),
                        opt_state, hyper, t, rng, sub)
                if zero:
                    # level 1: the plan's sharded step over each leaf's
                    # shard — from the bucket's rows where the backward
                    # wave already reduce-scattered them
                    if bucket:
                        acc, grads, off = grads, {}, 0
                        for n, c in bucket_chunks(params):
                            grads[n] = acc[:, off:off + c]
                            off += c
                    return plan.update_shards(
                        self.fopt, names, params, grads, opt_state, hyper,
                        t, rng, sub)
                new_p, new_s = {}, {}
                for n in names:
                    g = grads[n].astype(params[n].dtype)
                    new_p[n], new_s[n] = self.fopt.update(
                        n, params[n], g, opt_state[n], hyper, t, rng=rng)
                return new_p, new_s

            if self._has_scale:
                def upd(params, opt_state, acc, hyper, t, rng, finite,
                        inv):
                    def do(_):
                        if bucket:
                            grads = acc * inv.astype(acc.dtype)
                        else:
                            grads = {n: acc[n] * inv.astype(acc[n].dtype)
                                     for n in acc}
                        return upd_math(params, grads, opt_state, hyper,
                                        t, rng)

                    def skip(_):
                        # overflow: this stage's weights and optimizer
                        # state stay put
                        return params, opt_state
                    return jax.lax.cond(finite, do, skip, None)
            else:
                def upd(params, opt_state, acc, hyper, t, rng):
                    return upd_math(params, acc, opt_state, hyper, t, rng)
            upd.__name__ = "mxtpu_pp_upd"
            state_sh = sh_dp if zero else rep
            # ZeRO-3: updated params stay resident as flat shards
            param_sh = sh_dp if zero >= 3 else rep
            # the lax.cond defeats GSPMD output-sharding propagation —
            # pin outputs to the carried layout (mirrors TrainStep)
            return jax.jit(upd, donate_argnums=(0, 1),
                           out_shardings=(param_sh, state_sh))

        if kind == "fin":
            def fin(acc):
                leaves = jax.tree_util.tree_leaves(acc)
                if not leaves:      # parameter-less stage (bare loss head)
                    return jnp.bool_(True)
                return jnp.stack([jnp.isfinite(g).all()
                                  for g in leaves]).all()
            fin.__name__ = "mxtpu_pp_fin"
            return jax.jit(fin)

        if kind == "scale":
            policy = self.policy

            def scale_upd(lsc, fins):
                finite = jnp.stack(list(fins)).all()
                inv = jnp.float32(1.0) / lsc["scale"]
                return policy.next_state(lsc, finite), finite, inv
            scale_upd.__name__ = "mxtpu_pp_scale"
            return jax.jit(scale_upd, donate_argnums=(0,),
                           out_shardings=(rep, rep, rep))

        if kind == "auxsel":
            def auxsel(finite, aux_new, aux_old):
                # overflow steps must not poison the BN moving stats —
                # scalar-pred where instead of cond keeps shardings
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(finite, a, b), aux_new, aux_old)
            auxsel.__name__ = "mxtpu_pp_auxsel"
            return jax.jit(auxsel, out_shardings=rep)

        if kind == "stats":
            # MXNET_MONITOR: this stage's numerics stats on its sub-mesh
            # — squared sums of whatever the gradient residency is when
            # the stats dispatch runs (the flat (dp, chunk) bucket when
            # ZeRO keeps it, the gathered/accumulated tree otherwise);
            # the dp-sharded bucket reduction crosses ranks in-program.
            # The update/param ratio is structurally unavailable here:
            # the pre-update params are donated into the stage update
            # programs, so old and new params never coexist.
            from . import numerics as _num
            flat = overlap and self.zero
            spec_ = _num.spec()
            want_upd = spec_ is None or "update" in spec_.stats

            def stats_core(params, grads, inv=None):
                def sq(x):
                    # promote, never demote (f64 parity runs stay exact)
                    return jnp.sum(jnp.square(x.astype(
                        jnp.promote_types(x.dtype, jnp.float32))))
                inv2 = None if inv is None \
                    else jnp.square(inv.astype(jnp.float32))
                grad_sq = {}
                if flat:
                    off = 0
                    for n, c in bucket_chunks(params):
                        gs = sq(grads[:, off:off + c])
                        grad_sq[n] = gs if inv2 is None else gs * inv2
                        off += c
                else:
                    for n in names:
                        gs = sq(grads[n])
                        grad_sq[n] = gs if inv2 is None else gs * inv2
                out = {"grad_sq": grad_sq}
                if want_upd:
                    # ZeRO-3 flat rows are elementwise-valid (padding is
                    # zeros), so the squared sums are exact
                    out["param_sq"] = {n: sq(params[n]) for n in names}
                return out

            if self._has_scale:
                def stats(params, grads, inv):
                    return stats_core(params, grads, inv)
            else:
                def stats(params, grads):
                    return stats_core(params, grads)
            stats.__name__ = "mxtpu_pp_stats"
            return jax.jit(stats)

        if kind == "headsfin":
            # MXNET_MONITOR: loss-head finite flags (+ optional RMS) on
            # the final stage's sub-mesh, over the concatenated outputs
            from . import numerics as _num
            spec_ = _num.spec()
            want_act = spec_ is not None and "act" in spec_.stats

            def headsfin(outs):
                out = {"heads_finite": tuple(jnp.isfinite(o).all()
                                             for o in outs)}
                if want_act:
                    out["act_rms"] = {
                        "head%d" % i: jnp.sqrt(jnp.mean(jnp.square(
                            o.astype(jnp.promote_types(o.dtype,
                                                       jnp.float32)))))
                        for i, o in enumerate(outs)}
                return out
            headsfin.__name__ = "mxtpu_pp_headsfin"
            return jax.jit(headsfin)

        raise MXNetError("unknown pipeline program kind %r" % kind)

    # ------------------------------------------------------------ transfers
    def _put_carry(self, arrs, s):
        """Hand a stage-boundary tuple (activations forward, cotangents
        backward) to stage ``s``'s sub-mesh — the explicit resharding that
        makes the runtime insert the device-to-device transfers."""
        import jax
        from jax.sharding import NamedSharding
        sub = self._sub(s)
        return tuple(jax.device_put(
            a, NamedSharding(sub, self._carry_spec(a, sub)))
            for a in arrs)

    def _put_batch(self, host, s):
        import jax
        from jax.sharding import NamedSharding
        sub = self._sub(s)
        return jax.device_put(host,
                              NamedSharding(sub, self._carry_spec(host,
                                                                  sub)))

    # ------------------------------------------------------------ loss scale
    def _scale_state_dev(self):
        """Loss-scale state, living replicated on the FINAL stage's
        sub-mesh (where the loss heads are); donated into every step's
        scale-update program."""
        if self._scale_state is not None:
            return self._scale_state
        import jax
        from jax.sharding import NamedSharding
        dst = NamedSharding(self._subs[-1], _pspec())
        self._scale_state = {k: jax.device_put(v, dst)
                             for k, v in self.policy.init_state().items()}
        return self._scale_state

    def amp_stats(self):
        """(scale, overflow_delta) — two-scalar sync; telemetry-gated
        callers only (mirrors TrainStep.amp_stats)."""
        if not self._has_scale or self._scale_state is None:
            return None
        import jax
        with _san.allow_sync("amp loss-scale telemetry"):
            host = jax.device_get(self._scale_state)
        total = int(host["overflow"])
        delta = total - self._overflow_seen
        self._overflow_seen = total
        return float(host["scale"]), delta

    def _donate_pairs(self, args):
        """Labelled leaves of the donated pytrees (params, opt_state[,
        loss-scale state]) for the mxsan DONATE ledger.  aux is NOT
        donated on the pipeline path (the overflow select needs the
        pre-step values)."""
        import jax
        for name, tree in zip(("params", "opt_state", "loss_scale_state"),
                              args):
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
                yield name + jax.tree_util.keystr(path), leaf

    def _timed(self, busy, s, fn, *args):
        """Run one stage program; with telemetry on, block and charge the
        device time to stage ``s`` (the pp.stage spans / per-stage skew
        source — measurement serialises the schedule, exactly like the
        executor's telemetry-mode device syncs)."""
        if busy is None:
            return fn(*args)
        import jax
        import time as _time
        t0 = _time.perf_counter()
        out = fn(*args)
        with _san.allow_sync("pipeline stage telemetry timing"):
            jax.block_until_ready(out)
        busy[s] += _time.perf_counter() - t0
        return out

    # ----------------------------------------------------- numerics monitor
    def _publish_monitor(self, stats_s, heads_stats, new_params, new_aux,
                         batch, rng, upd_idx, mspec):
        """Merge the per-stage stats pytrees (fetched in ONE planned
        d2h), publish them, and on non-finite dynamics run the
        provenance replay + ``numerics`` post-mortem.  No update/param
        ratio on this path — the stage updates donate the pre-update
        params before the post-update ones exist."""
        import jax
        import warnings
        from . import numerics as _num
        with _san.allow_sync("numerics monitor fetch"):
            host_s, host_h = jax.device_get((stats_s, heads_stats))
        grad_sq, param_sq = {}, {}
        for st in host_s:
            grad_sq.update(st.get("grad_sq") or {})
            param_sq.update(st.get("param_sq") or {})
        host = {"grad_sq": grad_sq}
        if grad_sq:
            host["grad_sq_global"] = float(sum(
                float(v) for v in grad_sq.values()))
        if param_sq:
            host["param_sq"] = param_sq
        if host_h:
            host["heads_finite"] = host_h.get("heads_finite")
            if host_h.get("act_rms"):
                host["act_rms"] = host_h["act_rms"]
        entry = _num.publish(host, upd_idx, mspec, who="pipeline_step")
        self._last_mon_entry = entry
        if not _num.entry_bad(entry):
            return entry
        prov = self._numerics_provenance(new_params, new_aux, batch, rng,
                                         upd_idx)
        path, msg = _num.postmortem(prov, entry=entry)
        if mspec is not None and mspec.raise_on_nonfinite:
            raise _num.NumericsError(msg)
        warnings.warn("mxnet_tpu numerics monitor: %s" % msg)
        return entry

    def _numerics_provenance(self, new_params, new_aux, batch, rng,
                             upd_idx):
        """Host replay through the stage partition, then op-by-op.  The
        pre-update params were donated into the stage update programs,
        so the replay uses the RETURNED ones — exactly the pre-step
        weights when AMP's overflow skip fired (the common non-finite
        trigger), post-update otherwise (the bundle says which)."""
        import jax
        from . import numerics as _num
        params_state = "pre-update (AMP overflow skip)" \
            if self._has_scale else "post-update"
        with _san.allow_sync("numerics provenance host pull"):
            host_p = {n: _np.asarray(jax.device_get(v))
                      for n, v in new_params.items()}
            host_aux = {n: _np.asarray(jax.device_get(v))
                        for n, v in new_aux.items()}
            host_b = {k: _np.asarray(jax.device_get(v))
                      for k, v in batch.items()}
        if self.zero >= 3:
            host_p = {n: self.plan.unflatten_host(n, v)
                      for n, v in host_p.items()}
        if self._dtype is not None:
            host_b = {k: (v.astype(self._dtype)
                          if k not in self.label_names
                          and v.dtype == _np.float32 else v)
                      for k, v in host_b.items()}
            host_p = {k: v.astype(self._dtype)
                      for k, v in host_p.items()}
        arg_vals = dict(host_b)
        arg_vals.update(host_p)
        return _num.investigate(self._low, arg_vals, host_aux, rng,
                                update=upd_idx,
                                input_names=self._inputs_all,
                                params_state=params_state,
                                num_stages=self._V,
                                extra={"pp": self._pp, "dp": self._dp,
                                       "schedule": self._schedule,
                                       "interleave": self._v})

    # ------------------------------------------------------------------ call
    def __call__(self, params, opt_state, aux, batch, rng=None):
        """One pipelined, microbatched global step under the configured
        schedule (gpipe / 1f1b / interleaved).  Returns
        (params, opt_state, aux, outputs) — outputs are the loss heads
        over the full global batch (microbatch results concatenated in
        order)."""
        import jax
        import time as _time
        from jax.sharding import NamedSharding
        from . import profiler as _profiler
        from . import diagnostics as _diag
        if self._stages is None:
            raise MXNetError(
                "PipelineTrainStep: call init() (or place_params/"
                "place_state/place_aux) before stepping")
        if rng is None:
            rng = _random.next_key()
        M, P, V = self._micro, self._pp, self._V
        for n in self.data_names + self.label_names:
            if n not in batch:
                raise MXNetError("pipeline step: missing input %s" % n)
        b0 = batch[self.data_names[0]].shape[0]
        if b0 % M:
            raise MXNetError(
                "pipeline step: global batch %d is not divisible by "
                "num_microbatches=%d" % (b0, M))
        mb = b0 // M
        if mb % self._dp:
            raise MXNetError(
                "pipeline step: microbatch %d (batch %d / M=%d) is not "
                "divisible by dp=%d" % (mb, b0, M, self._dp))
        plan = self._get_plan()
        from . import numerics as _num
        upd_idx = self.num_update
        mspec = _num.spec()
        # the legacy Monitor bridge force-samples even with MXNET_MONITOR
        # unset (the stats trace then uses the default grad+update set)
        sample = self._mon_force or (mspec is not None
                                     and mspec.due(upd_idx))
        if self._mon_force:
            self._mon_force = False
        hyper = self.fopt.hyper(self.num_update)
        self.num_update += 1
        t = _np.int32(self.num_update)
        telem = _tel._enabled
        busy = [0.0] * P if telem else None
        wall0 = _time.time() if telem else 0.0
        t0 = _time.perf_counter() if telem else 0.0
        args_led = (params, opt_state) + \
            ((self._scale_state_dev(),) if self._has_scale else ())
        if _san._donate_on:
            _san.check_donated("pipeline_step", self._donate_pairs(args_led))
        nbytes = _tel.nbytes_of
        gather_grads = self._bucket and not self.zero
        with _profiler.Scope("pipeline_step[%d]" % self.num_update,
                             "symbolic"), \
                _san.hot_region("pipeline_step"):
            rep_rngs = [jax.device_put(rng, NamedSharding(sub, _pspec()))
                        for sub in self._subs]
            p_s = [{n: params[n] for n in st.params} for st in self._stages]
            st_s = [{n: opt_state[n] for n in st.params}
                    for st in self._stages]
            aux_s = [{n: aux[n] for n in st.aux} for st in self._stages]
            aux_pre = [dict(a) for a in aux_s] if self._has_scale else None
            acc = [self._timed(busy, k % P, self._get_prog("zeros", k),
                               p_s[k]) for k in range(V)]
            scale_s = {}
            if self._has_scale:
                # one scale transfer per loss-bearing device slice (the
                # scale cannot change during the waves), not one per
                # microbatch — done up front because 1f1b/interleaved
                # dispatch backwards before the forward wave drains
                scale_op = self._scale_state["scale"]
                sc_d = {}
                for k in range(V):
                    if not self._stage_has_loss[k]:
                        continue
                    d = k % P
                    if d not in sc_d:
                        sc_d[d] = scale_op if d == P - 1 else \
                            self._put_carry((scale_op,), d)[0]
                    scale_s[k] = sc_d[d]
            # ---- dispatch the planned schedule: work items run on their
            # virtual stage's device slice in dispatch order, slices
            # overlap through XLA's async dispatch.  stash holds each
            # in-flight microbatch's boundary activations from its
            # forward until its backward — the per-slice peak is the
            # schedule's activation-memory signature (gpipe: grows with
            # M; 1f1b: bounded by pp).
            stash = {}
            fwd_carry = {}     # (m, consumer stage) -> activation tuple
            bwd_carry = {}     # (m, consumer stage) -> cotangent tuple
            outs_m = [None] * M
            grads_full = [None] * V
            stash_nb = [0] * P
            peak_nb = [0] * P
            last_bwd = plan["last_bwd"]
            for i, (kind, m, k) in enumerate(plan["items"]):
                d = k % P
                st = self._stages[k]
                if kind == "fwd":
                    ex = {n: self._put_batch(batch[n][m * mb:(m + 1) * mb],
                                             k)
                          for n in st.inputs}
                    cin = self._put_carry(fwd_carry.pop((m, k), ()), k)
                    stash[(m, k)] = (cin, ex)
                    stash_nb[d] += sum(nbytes(a) for a in cin) \
                        + sum(nbytes(v) for v in ex.values())
                    peak_nb[d] = max(peak_nb[d], stash_nb[d])
                    aux_new, o, c = self._timed(
                        busy, d, self._get_prog("fwd", k),
                        p_s[k], aux_s[k], cin, ex, rep_rngs[d],
                        _np.int32(m))
                    aux_s[k] = aux_new
                    if k == V - 1:
                        outs_m[m] = o
                    else:
                        fwd_carry[(m, k + 1)] = c
                else:
                    cin, ex = stash.pop((m, k))
                    gout = self._put_carry(bwd_carry.pop((m, k), ()), k)
                    call = [p_s[k], cin, aux_s[k], ex, gout, acc[k],
                            rep_rngs[d], _np.int32(m)]
                    if k in scale_s:
                        call.append(scale_s[k])
                    g, acc[k] = self._timed(busy, d,
                                            self._get_prog("bwd", k), *call)
                    if k > 0:
                        bwd_carry[(m, k - 1)] = g
                    stash_nb[d] -= sum(nbytes(a) for a in cin) \
                        + sum(nbytes(v) for v in ex.values())
                    if gather_grads and i == last_bwd[k] and st.params:
                        # the stage's backward wave is complete: issue its
                        # bucketed gradient all-gather NOW, so the dp
                        # collective overlaps the other slices' remaining
                        # compute instead of waiting inside the update
                        if _san._collective_on or _tel._enabled:
                            gsig = _san.collective_sig((acc[k],))
                            _san.record_wire_bytes("mxtpu_pp_gather",
                                                   gsig, axes="dp")
                            if _san._collective_on:
                                # ledger entry at dispatch, from the
                                # bucket's shape metadata (no sync): a
                                # rank whose schedule diverges is named
                                # by stage + sig at the next hash-chain
                                # exchange
                                _san.note_collective(
                                    "mxtpu_pp_gather", name="stage%d" % k,
                                    sig=gsig, axes="dp")
                        grads_full[k] = self._timed(
                            busy, d, self._get_prog("gather", k),
                            p_s[k], acc[k])
                        acc[k] = None   # drop the bucket reference
            # ---- loss-scale automaton + combined finite flag, on device
            fin_d = inv_d = None
            if self._has_scale:
                fins = []
                for k in range(V):
                    src = acc[k]
                    if gather_grads:
                        src = grads_full[k] if grads_full[k] is not None \
                            else {}
                    fins.append(self._timed(busy, k % P,
                                            self._get_prog("fin", k), src))
                last = NamedSharding(self._subs[-1], _pspec())
                fins_dev = tuple(jax.device_put(f, last) for f in fins)
                new_lsc, finite, inv = self._timed(
                    busy, P - 1, self._get_prog("scale", V - 1),
                    self._scale_state, fins_dev)
                self._scale_state = new_lsc
                fin_d = [self._put_carry((finite,), d)[0]
                         for d in range(P)]
                inv_d = [self._put_carry((inv,), d)[0] for d in range(P)]
            # ---- sampled numerics stats, per stage on its sub-mesh —
            # dispatched BEFORE the updates donate the stage params
            stats_s = None
            if sample:
                stats_s = []
                for k in range(V):
                    d = k % P
                    src = acc[k]
                    if gather_grads:
                        src = grads_full[k] if grads_full[k] is not None \
                            else {}
                    if self._bucket and self.zero and self._dp > 1 \
                            and _san._collective_on \
                            and self._stages[k].params:
                        # the per-param squared sums reduce across the
                        # bucket's dp rows inside the stats program
                        _san.note_collective(
                            "mxtpu_monitor_psum", name="stage%d" % k,
                            sig=("%d scalars"
                                 % len(self._stages[k].params),),
                            axes="dp")
                    call = [p_s[k], src]
                    if self._has_scale:
                        call.append(inv_d[d])
                    stats_s.append(self._timed(
                        busy, d, self._get_prog("stats", k), *call))
            # ---- per-stage optimizer update (ZeRO-1 shards over the
            # stage sub-mesh's dp axis); donated params/state
            new_params, new_state, new_aux = {}, {}, {}
            for k in range(V):
                d = k % P
                g_in = acc[k]
                if gather_grads:
                    g_in = grads_full[k] if grads_full[k] is not None \
                        else {}
                call = [p_s[k], st_s[k], g_in, hyper, t, rep_rngs[d]]
                if self._has_scale:
                    call += [fin_d[d], inv_d[d]]
                np_s, ns_s = self._timed(busy, d,
                                         self._get_prog("upd", k), *call)
                a_s = aux_s[k]
                if self._has_scale and self._stages[k].aux:
                    a_s = self._timed(busy, d,
                                      self._get_prog("auxsel", k),
                                      fin_d[d], a_s, aux_pre[k])
                new_params.update(np_s)
                new_state.update(ns_s)
                new_aux.update(a_s)
            if M == 1:
                outs = tuple(outs_m[0])
            else:
                import jax.numpy as jnp
                outs = tuple(jnp.concatenate([om[i] for om in outs_m],
                                             axis=0)
                             for i in range(len(outs_m[0])))
            heads_stats = None
            if sample:
                heads_stats = self._timed(
                    busy, P - 1, self._get_prog("headsfin", V - 1), outs)
        if _san._donate_on:
            _san.note_donated("pipeline_step",
                              self._donate_pairs(args_led),
                              step=self.num_update)
        # live-byte accounting per device slice: parameters/optimizer
        # state/aux resident on the slice plus the PEAK boundary stash the
        # executed schedule held there — pure shape metadata, no syncs;
        # exposed regardless of telemetry for the dryrun ladder
        static_nb = [0] * P
        for k in range(V):
            st = self._stages[k]
            # dp-sharded leaves (ZeRO params at level 3, state at
            # level >= 1) cost each device 1/dp of the array
            pdiv = self._dp if self.zero >= 3 else 1
            sdiv = self._dp if self.zero else 1
            nb = sum(nbytes(new_params[n]) // pdiv for n in st.params)
            nb += sum(nbytes(x) // sdiv
                      for n in st.params for x in new_state[n])
            nb += sum(nbytes(new_aux[n]) for n in st.aux)
            static_nb[k % P] += nb
        self.last_live_bytes = [static_nb[d] + peak_nb[d]
                                for d in range(P)]
        if telem:
            frac = plan["bubble"]
            for d in range(P):
                _tel.record_span("pp.stage", wall0, busy[d],
                                 cat="pipeline", stage=d, microbatches=M,
                                 schedule=self._schedule)
            wall = _time.perf_counter() - t0
            _tel.record_span("pp.bubble", wall0, wall * frac,
                             cat="pipeline", pp=P, microbatches=M,
                             schedule=self._schedule, interleave=self._v)
            _tel.gauge("pp_bubble_fraction", frac)
            for d in range(P):
                # stage in the NAME: the gauge registry (and everything
                # reading it — /metrics, summaries, the fleet merge) is
                # name-keyed last-write-wins, so a tagged single name
                # would surface only the final stage's footprint
                _tel.gauge("pp_stage%d_live_bytes" % d,
                           self.last_live_bytes[d], stage=d)
            if self._has_scale and self._amp_emit \
                    and _tel.scalar_due(self.num_update):
                scale_v, overflow = self.amp_stats()
                _tel.gauge("loss_scale", scale_v)
                if overflow:
                    _tel.counter("amp_overflow_steps", overflow)
            if self.zero:
                # worst-slice per-device residency per the placement
                # plan — shape metadata only, no syncs; invariant for a
                # step instance, so walked once and cached
                zb = self._zb_cache
                if zb is None:
                    zb = self._zb_cache = self.zero_bytes(new_params,
                                                          new_state)
                _tel.gauge("zero_param_bytes", zb["param"],
                           level=self.zero)
                _tel.gauge("zero_grad_bytes", zb["grad"], level=self.zero)
        if _diag._armed:
            _diag.heartbeat(pipeline_step=self.num_update)
        mode = _diag.check_numerics_mode() if self.check_numerics else None
        if mode is not None:
            _diag.check_outputs(outs, mode, where="pipeline_step",
                                num_update=self.num_update)
        if stats_s is not None:
            self._publish_monitor(stats_s, heads_stats, new_params,
                                  new_aux, batch, rng, upd_idx, mspec)
        return new_params, new_state, new_aux, outs
