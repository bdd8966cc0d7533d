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

No token is dropped.  The assignments that land here are sorted by expert,
each expert's run is padded to whole blocks of 256 rows, and the blocks are
worked one after another, each on its own expert's matrices: the work
follows the number of tokens routed here, not tokens x experts held, and not
how evenly they spread over the held experts.  The rows set aside are four
times the mean number that lands here: a chip's share of a router that
nothing has balanced yet (random weights, the first steps of training) is
anything between a quarter and three times the mean, and a step inside the
room takes the same time wherever in it.  A step that lands more takes, by
``lax.cond`` on the counted load alone, the one other path, which has rows
for every assignment there can be.

Counters, handed to ``telemetry.device_counter`` (accumulated on the device,
fetched by nobody inside a step): assignments that landed on held experts,
the fullest held expert's tokens, assignments to absent experts, and landed
assignments that no block computed (must read 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry as _tel
from .nn import ACTIVATIONS
from .registry import register, parse_float, parse_int, parse_str

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
    with room for every held expert's last block to be part empty."""
    per_expert = tokens * top_k / float(num_experts)
    block = _BLOCK if 2 * per_expert >= _BLOCK \
        else _round_up(max(per_expert, 1), 8)
    most = tokens * min(top_k, held)
    aside = min(most, _round_up(_ROOM * per_expert * held, block))
    return block, _round_up(aside, block) + held * block, \
        _round_up(most, block) + held * block


def _grouped(data, flat_w, order, counts, up, down, rows_cap, block, act,
             top_k):
    """The assignments that landed here, sorted by expert (``order``: the
    held experts' first), each expert's run padded to whole blocks of
    ``block`` rows; one block after another, each on its own expert's two
    matrices.  ``rows_cap`` rows hold them all (the caller sees to it).
    Returns (out (N, C) float32, rows computed)."""
    f32 = jnp.float32
    held = up.shape[0]
    padded = -(-counts // block) * block
    ends = jnp.cumsum(padded)                    # in the padded layout
    starts = jnp.cumsum(counts) - counts         # in the sorted order
    pos = jnp.arange(rows_cap)
    expert = jnp.minimum(jnp.sum(pos[:, None] >= ends[None, :], axis=1),
                         held - 1)
    within = pos - (ends - padded)[expert]
    valid = (within < counts[expert]) & (pos < ends[-1])
    rows = order[jnp.where(valid, starts[expert] + within, 0)]
    token = rows // top_k
    weight = jnp.where(valid, flat_w[rows], 0.0)

    @jax.checkpoint
    def one(args):
        x, e = args                              # (block, C), its expert
        hid = jnp.dot(x, up[e].T, preferred_element_type=f32)
        hid = act(hid).astype(x.dtype)
        return jnp.dot(hid, down[e].T,
                       preferred_element_type=f32).astype(x.dtype)
    y = jax.lax.map(one, (data[token].reshape(-1, block, data.shape[1]),
                          expert[::block]))
    y = y.reshape(rows_cap, -1).astype(f32) * weight[:, None]
    out = jnp.zeros(data.shape, f32).at[token].add(y)
    return out, valid.sum()


def _routed(data, indices, weights, up, down, first, num_experts, act):
    """(this chip's part of the routed result (N, C) in data's dtype,
    float32[4] counters)."""
    n, k = indices.shape
    held = up.shape[0]
    local = indices - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).reshape(-1)
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0)
    block, aside, most = capacity(n, k, num_experts, held)
    needed = (-(-counts // block) * block).sum()
    paths = [functools.partial(_grouped, rows_cap=cap, block=block, act=act,
                               top_k=k) for cap in (aside, most)]
    args = (data, weights.reshape(-1).astype(jnp.float32),
            jnp.argsort(key, stable=True), counts, up, down)
    out, computed = paths[0](*args) if aside == most \
        else jax.lax.cond(needed <= aside, *paths, *args)
    landed = counts.sum()
    stats = jnp.stack([landed, counts.max(), n * k - landed,
                       landed - computed]).astype(jnp.float32)
    return out.astype(data.dtype), stats


def _experts_infer(attrs, in_shapes):
    held, f = int(attrs.get("experts_held")), int(attrs.get("num_hidden"))
    data = in_shapes[0]
    ins = list(in_shapes)
    if data is not None:
        ins[3] = (held, f, data[-1])
        ins[4] = (held, data[-1], f)
    return ins, [data], None


def _experts_types(attrs, in_dtypes):
    import numpy as np
    d = in_dtypes[0] if in_dtypes[0] is not None else np.float32
    return [d, np.int32, np.float32, d, d], [d], []


@register("moe_experts",
          arg_names=("data", "indices", "weights", "up_weight",
                     "down_weight"),
          attr_types={"num_experts": parse_int, "experts_held": parse_int,
                      "first_expert": parse_int, "num_hidden": parse_int,
                      "act_type": parse_str},
          defaults={"first_expert": 0, "act_type": "relu2"},
          infer_shape=_experts_infer, infer_type=_experts_types)
def _moe_experts(data, indices, weights, up_weight, down_weight,
                 num_experts=None, experts_held=None, first_expert=0,
                 num_hidden=None, act_type="relu2"):
    """The held experts' part of the routed result.  data (N, C); indices
    and weights (N, k) from ``moe_router``, over all ``num_experts``;
    up_weight (held, F, C), down_weight (held, C, F); expert ``e`` is
    ``down_e(act(up_e u))``, no bias.  Returns (N, C)."""
    core = jax.checkpoint(functools.partial(
        _routed, first=int(first_expert), num_experts=int(num_experts),
        act=ACTIVATIONS[act_type]))
    out, stats = core(data, indices, weights, up_weight, down_weight)
    _tel.device_counter("moe", jax.lax.stop_gradient(stats))
    return out
