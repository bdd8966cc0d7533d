"""Parameter-placement plans — ZeRO levels 0-3 behind one explicit object.

TrainStep, PipelineTrainStep and the checkpoint restore path used to share
their placement-and-update logic informally (``_host_init``,
``_flat_shards``, ``place_params``/``place_state``, ``_zero_state_host``
— the ROADMAP item 2 refactor target).  :class:`PlacementPlan` makes the
contract explicit so the pipeline schedule (gpipe/1f1b/interleaved) and
the sharding level are orthogonal knobs:

=====  ======================  =============================  ==================
level  parameters              gradients                      optimizer state
=====  ======================  =============================  ==================
0      replicated              full tree, all-reduced         replicated
1      replicated              full tree; constrained to the   the leaf's OWN
       .                       state's sharding inside the     shape, dp-sharded
       .                       update (a reduce-scatter        along axis 0; flat
       .                       along axis 0)                   ``(dp,chunk)`` where
       .                                                       dp does not divide
       .                                                       that axis
2      replicated              ONE flat ``(dp,chunk)`` bucket  flat ``(dp,chunk)``
       .                       (reduce-scatter residency; the  dp-sharded
       .                       full tree never persists), one
       .                       all-gather of *updated params*
3      flat ``(dp,chunk)``     bucket, as level 2 — but the    flat ``(dp,chunk)``
       dp-sharded; gathered    updated shards stay sharded     dp-sharded
       just-in-time in the     (no gather at all)
       step, freed after use
=====  ======================  =============================  ==================

Per-device model footprint at level 3 scales ~``1/(pp * dp)`` when
composed with pipeline stages — the memory lever that opens models past
one chip's HBM (docs/distributed.md "ZeRO levels").

Level 1 cuts a leaf in ``dp`` parts along the leaf's own leading axis
wherever ``dp`` divides it (:meth:`PlacementPlan.keeps_shape`): the shard
has the parameter's own layout, so the step pads nothing and reshapes
nothing (a flat view of a conv filter is a relayout on the TPU, not a
bitcast).  Every other leaf — a scalar, a leading axis ``dp`` does not
divide — and everything at levels 2 and 3 (one concatenated bucket needs
a common form) takes the flat ``(dp, chunk)`` layout: zero-padded, device
``i`` owns row ``i``.  Where ``dp`` divides the leading axis the two
forms hold the same elements on the same device, so the sharded
checkpoint writer's rows are the same bytes either way.  The rule and
the layouts exist exactly once, here.  Elementwise optimizer math
commutes with both forms, so every level trains to exact parity with the
replicated step (f64 @1e-9, test-pinned).
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError

__all__ = ["PlacementPlan", "normalize_zero", "chunk_rows", "flat_shards",
           "from_flat", "flat_np"]


# ------------------------------------------------------- flat (dp, chunk)
# The layout primitives live at module level so TrainStep /
# PipelineTrainStep / checkpoint all consume literally the same code.

def chunk_rows(size, dp):
    """Row width of the flat (dp, chunk) view for ``size`` elements —
    THE layout contract between :func:`flat_shards` and everything that
    slices its output (bucket offsets, the ZeRO update's per-param
    views, the checkpoint row writer): exactly one place."""
    return -(-int(size) // int(dp))


def flat_shards(x, dp):
    """Logical tensor -> flat (dp, chunk) view, zero-padded; device ``i``
    owns row ``i`` (traced).  Elementwise optimizer math commutes with
    this view.  An already-flat (dp, chunk) input round-trips
    unchanged."""
    import jax.numpy as jnp
    size = _size_of(x.shape)
    chunk = chunk_rows(size, dp)
    flat = jnp.reshape(x, (-1,))
    pad = dp * chunk - size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return jnp.reshape(flat, (dp, chunk))


def from_flat(xf, shape):
    """Flat (dp, chunk) view -> logical tensor (traced)."""
    import jax.numpy as jnp
    return jnp.reshape(jnp.reshape(xf, (-1,))[:_size_of(shape)], shape)


def flat_np(v, dp):
    """Host-side flat (dp, chunk) view — THE save/restore wire contract
    for ZeRO optimizer state and level-3 parameters (the checkpoint
    writer slices its rows and ``load_sharded`` unpads by
    ``flat[:size]``)."""
    v = _np.asarray(v)
    chunk = chunk_rows(v.size, dp)
    out = _np.zeros((dp, chunk), v.dtype)
    out.reshape(-1)[:v.size] = v.reshape(-1)
    return out


def normalize_zero(zero):
    """ZeRO level from the public ``zero=`` argument: ``False``/``True``
    keep their historical meaning (off / level 1), integers pass through.
    Levels outside 0..3 are a loud misconfiguration."""
    if isinstance(zero, bool):
        return 1 if zero else 0
    level = int(zero)
    if not 0 <= level <= 3:
        raise MXNetError(
            "zero=%r: ZeRO level must be 0 (off), 1 (optimizer-state "
            "sharding), 2 (+gradient sharding) or 3 (+parameter sharding)"
            % (zero,))
    return level


def _size_of(shape):
    size = 1
    for d in shape:
        size *= d
    return size


def _pspec(*names):
    from jax.sharding import PartitionSpec
    return PartitionSpec(*names)


class PlacementPlan(object):
    """One step's parameter-placement plan: ZeRO level + dp width + the
    rule of each leaf's sharded form, the layout helpers and the sharded
    update math.

    The traced helpers take the target Mesh per call — the whole mesh
    for ``TrainStep``, the owning stage's sub-mesh for
    ``PipelineTrainStep`` (sharding level composes with any schedule).
    The plan captures each parameter's LOGICAL shape at placement time
    (``note_host``); level 3 needs them to rebuild full tensors from
    the flat shards (``shape_of`` / ``unflatten_host``)."""

    def __init__(self, zero=0, dp=1, who="TrainStep"):
        self.zero = normalize_zero(zero)
        self.dp = int(dp) if self.zero else 1
        self._who = who
        self._shapes = {}

    # ------------------------------------------------------------- properties
    @property
    def shard_state(self):
        """Optimizer state lives dp-sharded (level >= 1): in the form
        :meth:`keeps_shape` decides at level 1, flat (dp, chunk) above."""
        return self.zero >= 1

    @property
    def bucket_grads(self):
        """Gradient residency is the flat (dp, chunk) bucket (level >= 2)."""
        return self.zero >= 2

    @property
    def shard_params(self):
        """Parameters live sharded; gather just-in-time (level >= 3)."""
        return self.zero >= 3

    # ----------------------------------------------------------- flat layout
    def chunk_rows(self, size):
        return chunk_rows(size, self.dp)

    def flat_shards(self, x):
        return flat_shards(x, self.dp)

    def from_flat(self, xf, shape):
        return from_flat(xf, shape)

    # ------------------------------------------- the optimizer state's form
    def keeps_shape(self, shape):
        """THE rule of how a leaf's optimizer state is cut in ``dp``
        parts: at level 1 a leaf whose leading axis ``dp`` divides keeps
        its own shape, sharded along that axis; every other leaf (a
        scalar, an indivisible axis) and every leaf at levels 2-3 (their
        bucket concatenates rows) takes the flat (dp, chunk) view."""
        shape = tuple(shape)
        return self.zero == 1 and len(shape) >= 1 \
            and shape[0] % self.dp == 0

    def to_shards(self, x, shape, mesh):
        """A leaf of ``shape`` -> the state's form of it, dp-sharded
        (traced).  ``x`` is the logical tensor or the leaf's flat
        (dp, chunk) rows (a slice of a reduced gradient bucket).  On a
        reduced gradient the constraint lowers as a reduce-scatter (along
        axis 0 of a leaf kept in its shape), on a replicated parameter as
        each device's slice of it; where a leaf keeps its shape, row
        ``i`` of its rows holds exactly part ``i`` of its leading axis,
        so that reshape moves nothing between devices."""
        import jax
        from jax.sharding import NamedSharding
        if not self.keeps_shape(shape):
            x = self.flat_shards(x)       # flat rows pass unchanged
        elif tuple(x.shape) != tuple(shape):
            x = self.from_flat(x, shape)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, _pspec("dp")))

    def from_shards(self, xs, shape, mesh):
        """The state's form of a leaf -> the logical tensor, replicated
        (traced; the all-gather of the updated parameter)."""
        import jax
        from jax.sharding import NamedSharding
        if not self.keeps_shape(shape):
            xs = self.from_flat(xs, shape)
        return jax.lax.with_sharding_constraint(
            xs, NamedSharding(mesh, _pspec()))

    def shards_np(self, v):
        """Host logical tensor -> the host template of its state's form
        (what ``device_put`` with a ``"dp"`` sharding then cuts by rows):
        the tensor itself where the leaf keeps its shape, else
        :func:`flat_np`."""
        v = _np.asarray(v)
        return v if self.keeps_shape(v.shape) else flat_np(v, self.dp)

    def state_host(self, fopt, params):
        """Sharded optimizer state born as host templates in the state's
        form of each leaf — built from the (padded, where flat) parameter
        VALUES, so dcasgd's prev-weight state starts AT the weight
        exactly as in replicated mode (any level >= 1)."""
        return fopt.init_state({n: self.shards_np(v)
                                for n, v in params.items()})

    # --------------------------------------------------------- shape registry
    def note_host(self, host_arrays):
        """Capture logical shapes from host tensors (placement time) —
        level 3's flat device buffers no longer carry them."""
        for n, v in host_arrays.items():
            self._shapes[n] = tuple(int(d)
                                    for d in _np.asarray(v).shape)

    def shape_of(self, name):
        if name not in self._shapes:
            raise MXNetError(
                "%s: logical shape of %s unknown — call init() or "
                "place_checkpoint() before stepping (ZeRO-3 buffers are "
                "flat shards; the plan records logical shapes at "
                "placement via note_host)" % (self._who, name))
        return self._shapes[name]

    def unflatten_host(self, name, arr):
        """Host array of a leaf's state (or level-3 parameter) -> logical
        tensor (checkpoint / sync-back export): the identity for a leaf
        kept in its shape, the unpadded reshape of a flat (dp, chunk)
        view."""
        shape = self.shape_of(name)
        arr = _np.asarray(arr)
        if self.keeps_shape(shape):
            return arr
        return arr.reshape(-1)[:_size_of(shape)].reshape(shape)

    # -------------------------------------------------------------- placement
    def param_spec(self, name, custom=None):
        """PartitionSpec of a parameter's resident buffer: flat
        dp-sharded at level 3, else the caller's custom spec/replicated."""
        if self.shard_params:
            return _pspec("dp")
        return custom if custom is not None else _pspec()

    # ------------------------------------------------------- traced step math
    def gather_params(self, params, mesh):
        """Flat shards -> logical, replicated parameters (traced; the
        just-in-time all-gather of the ZeRO-3 forward).  XLA frees the
        gathered tensors when their last use retires — full weights are
        a transient of the step, never a residency."""
        import jax
        from jax.sharding import NamedSharding
        if not self.shard_params:
            return params
        rep = NamedSharding(mesh, _pspec())
        return {n: jax.lax.with_sharding_constraint(
            self.from_flat(v, self.shape_of(n)), rep)
            for n, v in params.items()}

    def update_shards(self, fopt, names, params, grads, opt_state, hyper,
                      t, rng, mesh):
        """The level-1 sharded optimizer step: every rule in
        ``_FunctionalOptimizer`` is elementwise in (w, g, state), so it
        applies unchanged to each device's shard of a leaf, in the form
        :meth:`keeps_shape` decides.  The sharding constraints make XLA
        reduce-scatter the gradient in and all-gather the updated
        parameter out.  ``grads[n]`` is the leaf's gradient, or its flat
        (dp, chunk) rows of a reduced bucket (the pipeline's overlapped
        dp comm).  (SGLD's shape-dependent noise draws a different —
        equally valid — realisation than replicated mode; the
        deterministic rules match it exactly.)"""
        new_params, new_state = {}, {}
        for n in names:
            w = params[n]
            gs = self.to_shards(grads[n].astype(w.dtype), w.shape, mesh)
            nws, new_state[n] = fopt.update(
                n, self.to_shards(w, w.shape, mesh), gs, opt_state[n],
                hyper, t, rng=rng)
            new_params[n] = self.from_shards(nws, w.shape, mesh)
        return new_params, new_state

    def bucket_layout(self, params, names=None):
        """Static (name, chunk_rows) layout of the flat gradient bucket
        — per-param (dp, chunk) views concatenated along the chunk axis,
        so row ``d`` holds device ``d``'s shard of every parameter
        contiguously.  Works on logical OR flat param leaves (a flat
        (dp, chunk) leaf re-chunks to the same width)."""
        names = list(names if names is not None else params)
        return [(n, self.chunk_rows(_size_of(params[n].shape)))
                for n in names]

    def fold_bucket(self, grads, params, layout, mesh):
        """Fold a full gradient tree into ONE flat (dp, chunk) bucket
        with a dp-sharded constraint — the reduction lowers as a
        reduce-scatter and the bucket is the only gradient residency
        (level >= 2).  Returns None for an empty layout."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        if not layout:
            return None
        flat = jnp.concatenate(
            [self.flat_shards(grads[n].astype(params[n].dtype))
             for n, _ in layout], axis=1)
        return jax.lax.with_sharding_constraint(
            flat, NamedSharding(mesh, _pspec("dp")))

    def shard_update(self, fopt, params, bucket, layout, opt_state, hyper,
                     t, rng, mesh):
        """The sharded optimizer step over a gradient bucket (level >= 2):
        each rank updates its (dp, chunk) rows; level 2 re-materialises
        replicated parameters with ONE all-gather of the concatenated
        updated rows (replacing the gradient gather), level 3 keeps the
        updated shards sharded — no gather at all."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        if not layout:
            return {}, {}
        sh_dp = NamedSharding(mesh, _pspec("dp"))
        rep = NamedSharding(mesh, _pspec())
        new_state = {}
        new_rows = []
        off = 0
        for n, c in layout:
            w = params[n]
            gf = bucket[:, off:off + c].astype(w.dtype)
            off += c
            if self.shard_params:
                wf = jax.lax.with_sharding_constraint(w, sh_dp)
            else:
                wf = jax.lax.with_sharding_constraint(
                    self.flat_shards(w), sh_dp)
            nwf, new_state[n] = fopt.update(n, wf, gf, opt_state[n],
                                            hyper, t, rng=rng)
            new_rows.append(nwf)
        new_params = {}
        if self.shard_params:
            for (n, _c), nwf in zip(layout, new_rows):
                new_params[n] = jax.lax.with_sharding_constraint(nwf,
                                                                 sh_dp)
            return new_params, new_state
        # level 2: one gather of the UPDATED parameters for the whole
        # bucket (the scatter half already happened inside fold_bucket's
        # constraint), then slice back to logical shapes
        gathered = jax.lax.with_sharding_constraint(
            jnp.concatenate(new_rows, axis=1), rep)
        off = 0
        for n, c in layout:
            new_params[n] = self.from_flat(
                gathered[:, off:off + c],
                params[n].shape).astype(params[n].dtype)
            off += c
        return new_params, new_state

    # -------------------------------------------------------- byte accounting
    def per_device_bytes(self, params, opt_state=None):
        """Per-device {param, grad, opt} byte residency from shape
        metadata only (no syncs) — the ``zero_param_bytes`` /
        ``zero_grad_bytes`` gauge source and the dryrun ladder's memory
        stamp.  Gradient residency: the bucket's one row per device at
        level >= 2, the full tree below.  Optimizer state: a ``dp``-th of
        each leaf as it lies — no pad for a leaf kept in its shape, the
        padded row of a flat view."""
        from .. import telemetry as _tel
        nb = _tel.nbytes_of
        param = grad = opt = 0
        for n, v in params.items():
            b = nb(v)
            param += b // self.dp if self.shard_params else b
            if self.bucket_grads:
                size = _size_of(self.shape_of(n) if self.shard_params
                                else v.shape)
                grad += self.chunk_rows(size) * _np.dtype(v.dtype).itemsize
            else:
                # tree residency (levels 0-1; shard_params implies
                # bucket_grads, so this is always the full tree)
                grad += b
        if opt_state:
            for st in opt_state.values():
                for leaf in st:
                    b = nb(leaf)
                    opt += b // self.dp if self.shard_state else b
        return {"param": int(param), "grad": int(grad), "opt": int(opt)}
