"""Routed-expert operators (NEW capability, no reference analogue): a router
that scores every token against ALL experts and keeps the best few, and an
expert layer that is told which experts it holds.

Expert parallelism divides a layer's experts over chips.  ``moe_experts`` is
one chip's part of it: it takes the router's choice over all
``num_experts``, holds ``experts_held`` of them from ``first_expert`` on, and
returns the part of ``sum_chosen w_e expert_e(u)`` that its own experts give.
What absent experts would add is left out (on several chips the exchange
brings it; nothing here stands in for them).  With ``experts_held ==
num_experts`` it is the whole layer.

No token is dropped.  The assignments that land here are sorted by expert
and each expert's run is padded to whole blocks of 256 rows.  The layer's
two products are grouped products over those rows (``grouped_matmul``: row
``i`` meets its own expert's matrix; operands in the input's dtype, float32
accumulation, the activation on the accumulator), and the matrices'
gradients are transposed grouped products (``grouped_matmul_t``, summed in
float32 over an expert's rows and written once): two implementations of one
signature, the Pallas kernels ``mxtpu_gmm`` / ``mxtpu_tgmm`` on a TPU for
shapes their guard takes and ``jax.lax.ragged_dot_general`` elsewhere,
chosen by backend and shape as ``dot_product_attention`` chooses flash.
The kernels' grid is the counted number of blocks, an expert's matrix is
fetched once for its run of blocks, and a block whose upper half holds
nothing (a run's last block, half the time) is worked as its lower half:
the products' work follows the number of tokens routed here (each held
expert's run rounded up to half blocks, half a block for an expert with
none), not tokens x experts held, and not the rows set aside.  One
``custom_vjp`` is round the routed part: its backward keeps the op's
inputs, forms the sorted rows and the up product once more, and needs the
down product not at all: 7 products a block (forward up and down; backward
up again, ``d_out down``, the two matrices' gradients, ``d_pre up``).

An expert is ``down(act(up u))`` or, told ``gated`` and given a third
matrix a held expert, ``down(act(gate u) * up u)`` (SwiGLU with ``silu``).
The gated form is the same layout, the same two arms and the same counters
with one more first product everywhere the up product stands: forward gate,
up and down; backward gate and up again (their float32 results multiplied
and rounded once, as in the forward), ``d_out down``, the three matrices'
gradients, and ``d_gate_pre gate + d_up_pre up`` summed in float32: 11
products a block.  ``moe_rows`` counts the rows one product runs over in
either form, not the products.

The rows set aside are four times the mean number that lands here: a
chip's share of a router that nothing has balanced yet (random weights, the
first steps of training) is anything between a quarter and three times the
mean.  The room is no longer the work: it is the size of the row gather
into the sorted layout, of the weighted scatter-add out of it and of the
backward's elementwise passes, which are over all of its rows whatever
landed, and the bound of ``lax.cond``: a step that lands more takes, on
the counted load alone, the one other path, which walks the held experts
one at a time with rows for every token, so for every assignment there can
be.  What a kernel leaves in the rows it did not work is not zero; it is
masked before a weight multiplies it.

Counters, handed to ``telemetry.device_counter`` (accumulated on the device,
fetched by nobody inside a step): ``moe``: assignments that landed on held
experts, the fullest held expert's tokens, assignments to absent experts,
and landed assignments that no block computed (must read 0); ``moe_rows``:
the rows the products ran over (the blocks visited, whole or half).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry as _tel
from . import pallas_kernels
from .nn import ACTIVATIONS
from .registry import register, parse_bool, parse_float, parse_int, \
    parse_str

# the rows of one block, and the rows set aside in multiples of the mean
# number of assignments that land here
_BLOCK = 256
_ROOM = 4


# -------------------------------------------------------------------- router
def _router_infer(attrs, in_shapes):
    e, k = int(attrs.get("num_experts")), int(attrs.get("top_k"))
    data = in_shapes[0]
    ins = list(in_shapes)
    if data is not None:
        ins[1] = (e, data[-1])
    ins[2] = (e,)
    out = None if data is None else (data[0], k)
    return ins, [out, out], None


def _router_types(attrs, in_dtypes):
    import numpy as np
    known = [d for d in in_dtypes if d is not None]
    d = known[0] if known else np.float32
    return [d] * len(in_dtypes), [np.int32, np.float32], []


@register("moe_router", arg_names=("data", "weight", "bias"), num_outputs=2,
          attr_types={"num_experts": parse_int, "top_k": parse_int,
                      "scale": parse_float},
          defaults={"scale": 1.0},
          infer_shape=_router_infer, infer_type=_router_types,
          f32_inputs=("weight", "bias"))
def _moe_router(data, weight, bias, num_experts=None, top_k=None, scale=1.0):
    """Sigmoid router with a selection bias.  data (N, C), weight (E, C),
    bias (E,).  ``s = sigmoid(W u)`` in float32; the ``top_k`` largest of
    ``s + bias`` are chosen (the bias takes part in the choice only and has
    no gradient); the weights are ``s[chosen]`` divided by their sum, times
    ``scale``.  Returns (indices (N, k) int32, weights (N, k) float32)."""
    f32 = jnp.float32
    scores = jax.nn.sigmoid(jnp.dot(
        data.astype(f32), weight.astype(f32).T,
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(f32)), int(top_k))
    w = jnp.take_along_axis(scores, chosen, axis=1)
    w = w / (w.sum(axis=1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), w * scale


# -------------------------------------------------------------- expert layer
def _round_up(x, to):
    return -(-int(x) // to) * to


def capacity(tokens, top_k, num_experts, held):
    """(rows of a block, rows set aside, rows for every assignment there can
    be): ``_ROOM`` times the mean number of assignments that land on
    ``held`` experts, and all of them (an expert is chosen at most once a
    token), each in whole blocks of ``_BLOCK`` rows (fewer at a tiny size)
    with room for every held expert's last block to be part empty.  Where
    the rows set aside are already all, there is no other path."""
    per_expert = tokens * top_k / float(num_experts)
    block = _BLOCK if 2 * per_expert >= _BLOCK \
        else _round_up(max(per_expert, 1), 8)
    most = tokens * min(top_k, held)
    aside = min(most, _round_up(_ROOM * per_expert * held, block))
    return block, _round_up(aside, block) + held * block, \
        _round_up(most, block) + held * block


def _blocks_of(counts, block):
    """Each held expert's run in whole blocks, one block for an expert with
    no rows (the weights' gradient is written from it: zeros)."""
    return jnp.maximum(-(-counts // block), 1)


def _layout(order, counts, rows_cap, block):
    """The assignments that landed here, sorted by expert (``order``: the
    held experts' first), each expert's run padded to whole blocks of
    ``block`` rows, in ``rows_cap`` rows that hold them all (the caller
    sees to it).  Returns (the assignment of every row, whether the row
    holds one, (the expert of every block, the leading rows of every block
    that hold an assignment), the blocks that hold the runs)."""
    held = counts.shape[0]
    padded = _blocks_of(counts, block) * block
    ends = jnp.cumsum(padded)                    # in the padded layout
    starts = jnp.cumsum(counts) - counts         # in the sorted order
    pos = jnp.arange(rows_cap)
    expert = jnp.minimum(jnp.sum(pos[:, None] >= ends[None, :], axis=1),
                         held - 1)
    within = pos - (ends - padded)[expert]
    valid = (within < counts[expert]) & (pos < ends[-1])
    rows = order[jnp.where(valid, starts[expert] + within, 0)]
    live = jnp.minimum(ends[-1] // block, rows_cap // block)
    fill = valid.reshape(-1, block).sum(axis=1)
    return rows, valid, (expert[::block].astype(jnp.int32),
                         fill.astype(jnp.int32)), live.astype(jnp.int32)


def _group_rows(x, tile_group, live, groups):
    """The rows of every group among the first ``live`` blocks."""
    blocks = tile_group.shape[0]
    mine = (tile_group[None, :] == jnp.arange(groups)[:, None]) \
        & (jnp.arange(blocks) < live)[None, :]
    return (x.shape[0] // blocks) * mine.sum(axis=1).astype(jnp.int32)


def grouped_matmul(x, w, tile_group, tile_fill, live, transpose_rhs=False,
                   act=None, out_dtype=None):
    """``pallas_kernels.grouped_matmul`` in plain ``jax.lax``: row ``i`` of
    x (rows, k) times the matrix of its block's group, w (groups, k, n) or
    (groups, n, k); f32 accumulation, ``act`` before the cast.  Every live
    block is worked whole, whatever ``tile_fill``."""
    del tile_fill
    dims = jax.lax.RaggedDotDimensionNumbers(
        (([1], [2 if transpose_rhs else 1]), ([], [])), [0], [0])
    acc = jax.lax.ragged_dot_general(
        x, w, _group_rows(x, tile_group, live, w.shape[0]), dims,
        preferred_element_type=jnp.float32)
    return (acc if act is None else act(acc)).astype(out_dtype or x.dtype)


def grouped_matmul_t(lhs, rhs, tile_group, tile_fill, live, groups,
                     out_dtype=None):
    """``pallas_kernels.grouped_matmul_t`` in plain ``jax.lax``: every
    group's ``lhs_g.T @ rhs_g``, (rows, k) and (rows, n) -> (groups, k,
    n), summed in f32."""
    del tile_fill
    dims = jax.lax.RaggedDotDimensionNumbers((([0], [0]), ([], [])), [0], [])
    acc = jax.lax.ragged_dot_general(
        lhs, rhs, _group_rows(lhs, tile_group, live, groups), dims,
        preferred_element_type=jnp.float32)
    return acc.astype(out_dtype or lhs.dtype)


def _products(block, data, up):
    """(grouped product, transposed grouped product, the rows they work a
    part-empty block in): the Pallas kernels on a TPU for shapes their
    guard takes, which work a block whose upper half holds nothing as its
    lower half; else the plain forms above, which work blocks whole."""
    if jax.default_backend() == "tpu" and pallas_kernels.grouped_available(
            block, up.shape[2], up.shape[1], data.dtype.itemsize):
        return pallas_kernels.grouped_matmul, \
            pallas_kernels.grouped_matmul_t, block // 2
    return grouped_matmul, grouped_matmul_t, block


def _worked(fill, live, unit):
    """Rows the products run over: every live block in whole ``unit``s,
    one at least (an expert with no rows)."""
    units = jnp.maximum(-(-fill // unit), 1) * unit
    return jnp.where(jnp.arange(fill.shape[0]) < live, units, 0).sum()


def _hidden(act, gated):
    """What stands between the first products and the down product: the
    activation, or for a gated expert ``act(gate u) * (up u)``."""
    return (lambda a, b: act(a) * b) if gated else act


def _forward(data, flat_w, order, counts, mats, rows_cap, block, act, top_k):
    """What the experts of ``mats`` (up, down, the gate's matrix or None)
    add: ((out (N, C) float32, rows that hold an assignment, rows the
    products ran over), ())."""
    f32 = jnp.float32
    up, down, gate = mats
    rows, valid, tiles, live = _layout(order, counts, rows_cap, block)
    gmm, _, unit = _products(block, data, up)
    token = rows // top_k
    if gate is None:
        hid = gmm(data[token], up, *tiles, live, transpose_rhs=True, act=act)
    else:           # both halves leave in float32 and are rounded once
        x = data[token]
        hid = (gmm(x, gate, *tiles, live, transpose_rhs=True, act=act,
                   out_dtype=f32)
               * gmm(x, up, *tiles, live, transpose_rhs=True,
                     out_dtype=f32)).astype(x.dtype)
    y = gmm(hid, down, *tiles, live, transpose_rhs=True)
    # rows past the last that hold something were not written: not zero
    y = jnp.where(valid[:, None], y.astype(f32) * flat_w[rows][:, None], 0.0)
    out = jnp.zeros(data.shape, f32).at[token].add(y)
    return (out, valid.sum(), _worked(tiles[1], live, unit)), ()


def _backward(data, flat_w, order, counts, mats, d_out, rows_cap, block, act,
              top_k):
    """((gradient of data (N, C) float32, of the flat weights), (of up, of
    down, of the gate's matrix or None)): the sorted rows and the first
    products formed once more, the down product not at all (a row's weight
    meets ``<hid, d_out down>``, which is ``<y, d_out>``)."""
    f32 = jnp.float32
    up, down, gate = mats
    firsts = (up,) if gate is None else (gate, up)
    rows, valid, tiles, live = _layout(order, counts, rows_cap, block)
    gmm, tgmm, _ = _products(block, data, up)
    token = rows // top_k
    keep = valid[:, None]
    weight = flat_w[rows][:, None]
    x, g = data[token], d_out[token]
    hid, act_vjp = jax.vjp(_hidden(act, gate is not None), *(
        gmm(x, m, *tiles, live, transpose_rhs=True, out_dtype=f32)
        for m in firsts))
    d_hid = gmm(g, down, *tiles, live, out_dtype=f32)   # before the weight
    d_weight = jnp.where(valid, (hid * d_hid).sum(axis=1), 0.0)
    d_pre = [jnp.where(keep, d, 0.0).astype(x.dtype)
             for d in act_vjp(d_hid * weight)]
    weighted = jnp.where(keep, hid * weight, 0.0).astype(x.dtype)
    d_firsts = [tgmm(d, x, *tiles, live, up.shape[0], out_dtype=m.dtype)
                for d, m in zip(d_pre, firsts)]
    d_down = tgmm(g, weighted, *tiles, live, up.shape[0],
                  out_dtype=down.dtype)
    d_x = jnp.where(keep, functools.reduce(jnp.add, [
        gmm(d, m, *tiles, live).astype(f32)
        for d, m in zip(d_pre, firsts)]), 0.0)
    d_data = jnp.zeros(data.shape, f32).at[token].add(d_x)
    d_flat = jnp.zeros(flat_w.shape, f32).at[rows].add(d_weight)
    return (d_data, d_flat), (d_firsts[-1], d_down,
                              None if gate is None else d_firsts[0])


def _expert_by_expert(arm, tokens, block):
    """``arm`` over the held experts one at a time, each with rows for
    every token: the path of a step that lands more than the rows set
    aside hold.  Its arrays are an expert's, never a row for every
    assignment there can be at once; what the experts add is summed, their
    own gradients are stacked."""
    rows_cap = _round_up(tokens, block) + block

    def path(data, flat_w, order, counts, mats, *rest):
        starts = jnp.cumsum(counts) - counts
        last = order.shape[0] - 1

        def one(e):
            mine = order[jnp.minimum(starts[e] + jnp.arange(rows_cap), last)]
            return arm(data, flat_w, mine, counts[e][None],
                       jax.tree_util.tree_map(lambda m: m[e][None], mats),
                       *rest, rows_cap=rows_cap)

        def step(sums, e):
            # the barrier keeps a kernel's result out of the fusion that
            # stacks it, where the kernel's own VMEM limit does not reach
            part, own = jax.lax.optimization_barrier(one(e))
            return jax.tree_util.tree_map(jnp.add, sums, part), own
        zeros = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, x.dtype), jax.eval_shape(one, 0)[0])
        sums, own = jax.lax.scan(step, zeros, jnp.arange(counts.shape[0]))
        return sums, jax.tree_util.tree_map(lambda x: x[:, 0], own)
    return path


def _either(arm, indices, first, num_experts, act, held):
    """``arm`` with the rows set aside or, where more land than they hold,
    by ``lax.cond`` on the counted load alone, expert by expert with rows
    for every assignment there can be: (what to call with the arm's
    remaining arguments, the held experts' counts)."""
    n, k = indices.shape
    local = indices - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).reshape(-1)
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0)
    order = jnp.argsort(key, stable=True)
    block, aside, most = capacity(n, k, num_experts, held)
    arm = functools.partial(arm, block=block, act=act, top_k=k)
    room = functools.partial(arm, rows_cap=aside)

    def call(data, flat_w, *rest):
        args = (data, flat_w, order, counts) + rest
        if aside == most:
            return room(*args)
        needed = _blocks_of(counts, block).sum() * block
        return jax.lax.cond(needed <= aside, room,
                            _expert_by_expert(arm, n, block), *args)
    return call, counts


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _routed(data, indices, weights, up, down, first, num_experts, act,
            gate=None):
    """(this chip's part of the routed result (N, C) in data's dtype,
    float32[4] counters, float32 rows the products ran over).  Its backward
    keeps these inputs and nothing else."""
    return _routed_fwd(data, indices, weights, up, down, first, num_experts,
                       act, gate)[0]


def _routed_fwd(data, indices, weights, up, down, first, num_experts, act,
                gate=None):
    n, k = indices.shape
    call, counts = _either(_forward, indices, first, num_experts, act,
                           up.shape[0])
    (out, computed, worked), _ = call(
        data, weights.reshape(-1).astype(jnp.float32), (up, down, gate))
    landed = counts.sum()
    stats = jnp.stack([landed, counts.max(), n * k - landed,
                       landed - computed]).astype(jnp.float32)
    return (out.astype(data.dtype), stats, worked.astype(jnp.float32)), \
        (data, indices, weights, up, down, gate)


def _routed_bwd(first, num_experts, act, kept, cotangents):
    data, indices, weights, up, down, gate = kept
    call, _ = _either(_backward, indices, first, num_experts, act,
                      up.shape[0])
    (d_data, d_flat), own = call(
        data, weights.reshape(-1).astype(jnp.float32), (up, down, gate),
        cotangents[0])
    # the matrices' gradients leave the ``cond`` as they are: without the
    # barrier the compiler moves the optimizer's float32 casts of them into
    # its branches, and every expert layer's stay alive at twice the size
    d_up, d_down, d_gate = jax.lax.optimization_barrier(own)
    return d_data.astype(data.dtype), None, d_flat.reshape(
        weights.shape).astype(weights.dtype), d_up, d_down, d_gate


_routed.defvjp(_routed_fwd, _routed_bwd)


def _experts_args(attrs):
    names = ["data", "indices", "weights", "up_weight", "down_weight"]
    return names + ["gate_weight"] if attrs.get("gated", False) else names


def _experts_infer(attrs, in_shapes):
    held, f = int(attrs.get("experts_held")), int(attrs.get("num_hidden"))
    data = in_shapes[0]
    ins = list(in_shapes)
    if data is not None:
        ins[3] = (held, f, data[-1])
        ins[4] = (held, data[-1], f)
        ins[5:] = [ins[3]] * (len(ins) - 5)
    return ins, [data], None


def _experts_types(attrs, in_dtypes):
    import numpy as np
    d = in_dtypes[0] if in_dtypes[0] is not None else np.float32
    return [d, np.int32, np.float32] + [d] * (len(in_dtypes) - 3), [d], []


@register("moe_experts", arg_names=_experts_args,
          attr_types={"num_experts": parse_int, "experts_held": parse_int,
                      "first_expert": parse_int, "num_hidden": parse_int,
                      "act_type": parse_str, "gated": parse_bool},
          defaults={"first_expert": 0, "act_type": "relu2", "gated": False},
          infer_shape=_experts_infer, infer_type=_experts_types)
def _moe_experts(data, indices, weights, up_weight, down_weight,
                 gate_weight=None, num_experts=None, experts_held=None,
                 first_expert=0, num_hidden=None, act_type="relu2",
                 gated=False):
    """The held experts' part of the routed result.  data (N, C); indices
    and weights (N, k) from ``moe_router``, over all ``num_experts``;
    up_weight (held, F, C), down_weight (held, C, F); expert ``e`` is
    ``down_e(act(up_e u))``, no bias.  ``gated`` adds a sixth input,
    gate_weight (held, F, C), and expert ``e`` is ``down_e(act(gate_e u) *
    up_e u)`` (``act_type`` silu: SwiGLU).  Returns (N, C)."""
    del gated                       # told by the sixth input's presence
    out, stats, worked = _routed(
        data, indices, weights, up_weight, down_weight, int(first_expert),
        int(num_experts), ACTIVATIONS[act_type], gate_weight)
    _tel.device_counter("moe", jax.lax.stop_gradient(stats))
    _tel.device_counter("moe_rows", jax.lax.stop_gradient(worked))
    return out
