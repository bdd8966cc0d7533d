"""ZeRO levels 0-3 (TrainStep/PipelineTrainStep ``zero=`` + ``MXNET_ZERO``).

Pins, on the virtual 8-device CPU mesh:
- f64 parity: one fused step at any zero level matches replicated mode
  exactly (elementwise optimizer math commutes with either form of a
  shard) — fast f32 2e-5 matrix over zero∈{2,3} × {dp, dp×pp per
  schedule}, slow f64 @1e-9 twin; level 1 against replicated after three
  f32 steps of a conv net and of a transformer, SGD-momentum and Adam;
- the compiled step really reduce-scatters gradients (HLO check) instead
  of all-reducing them into replicated optimizer state;
- optimizer state is born sharded over dp (1/dp of it on each device): at
  level 1 in the leaf's own shape along its leading axis where dp divides
  it, else (and at levels 2-3) as the flat (dp, chunk) view; the lowered
  level-1 step reshapes no such leaf to or from a flat array (by counts);
  level-3 parameters are born as flat (dp, chunk) shards;
- AMP overflow-skip under zero3 leaves the sharded masters untouched;
- the ``MXNET_ZERO`` fit dispatch (engages/toggles/guards byte-identical
  when unset), donation-ledger + ``MXNET_SAN=all:raise`` cleanliness
  with the ``zero.gather`` program in the collective ledger, the
  ``zero_param_bytes``/``zero_grad_bytes`` gauges (strict no-op off),
  and the live-bytes pin (zero3 per-device param residency <
  replicated's).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel.mesh import make_mesh, make_pp_mesh
from mxnet_tpu.parallel.placement import PlacementPlan, normalize_zero
from mxnet_tpu.train import TrainStep, PipelineTrainStep


@pytest.fixture
def f64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


def _net():
    from mxnet_tpu.models import resnet
    return resnet.get_symbol(num_classes=8, num_layers=20,
                             image_shape="3,16,16")


def _assert_dp_sharded(leaf, dp, what=""):
    """One ``dp``-th of the leaf on each device, cut along axis 0."""
    assert leaf.sharding.spec == P("dp"), (what, leaf.sharding)
    assert leaf.shape[0] % dp == 0, (what, leaf.shape)
    assert {s.data.shape for s in leaf.addressable_shards} \
        == {(leaf.shape[0] // dp,) + tuple(leaf.shape[1:])}, what
    assert len({s.device for s in leaf.addressable_shards}) == dp, what


def _one_step(opt_name, zero, mesh, batch=8, seed=0):
    if opt_name == "sgd":
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9, wd=1e-4,
                               rescale_grad=1.0 / batch)
    else:
        opt = mx.optimizer.Adam(learning_rate=1e-3, rescale_grad=1.0 / batch)
    ts = TrainStep(_net(), opt, mesh=mesh, zero=zero)
    dshape = (batch, 3, 16, 16)
    params, state, aux = ts.init({"data": dshape},
                                 {"softmax_label": (batch,)})
    params = {k: v.astype(jnp.float64) for k, v in params.items()}
    state = {k: tuple(s.astype(jnp.float64) for s in st)
             for k, st in state.items()}
    aux = {k: v.astype(jnp.float64) for k, v in aux.items()}
    rs = np.random.RandomState(seed)
    bd = ts.shard_batch({
        "data": rs.uniform(-1, 1, dshape).astype(np.float64),
        "softmax_label": rs.randint(0, 8, (batch,)).astype(np.float64)})
    key = jax.random.PRNGKey(7)
    for _ in range(2):   # two steps so momentum state participates
        params, state, aux, outs = ts(params, state, aux, bd, rng=key)
    return ts, params, state, aux


@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
def test_zero_matches_replicated_f64(opt_name, f64):
    mesh = make_mesh({"dp": 8})
    ts1, p1, s1, a1 = _one_step(opt_name, True, mesh)
    _, p0, s0, a0 = _one_step(opt_name, False, mesh)
    for k in p0:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p0[k]),
                                   rtol=1e-9, atol=1e-12, err_msg=k)
    for k in a0:
        np.testing.assert_allclose(np.asarray(a1[k]), np.asarray(a0[k]),
                                   rtol=1e-9, atol=1e-12, err_msg=k)
    # sharded state round-trips to the replicated values
    for k, st in s1.items():
        for s_leaf, r_leaf in zip(st, s0[k]):
            _assert_dp_sharded(s_leaf, 8, k)
            np.testing.assert_allclose(
                ts1.unflatten_host(k, np.asarray(s_leaf)),
                np.asarray(r_leaf), rtol=1e-9, atol=1e-12, err_msg=k)


def test_zero_collective_shape():
    """The compiled zero step must scatter gradients to shards and gather
    updated params.  On TPU the SPMD pipeline's ReduceScatterCreator pass
    fuses the scatter into reduce-scatter ops; the CPU pipeline (this
    test's backend) lacks that pass and lowers the same semantics as
    all-reduce + dynamic-slice — accept either, but the all-gather of the
    updated parameters (the ZeRO signature) must be present, and dynamic
    slicing must show the per-device shard reads."""
    mesh = make_mesh({"dp": 8})
    batch = 8
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                           rescale_grad=1.0 / batch)
    ts = TrainStep(_net(), opt, mesh=mesh, zero=True)
    params, state, aux = ts.init({"data": (batch, 3, 16, 16)},
                                 {"softmax_label": (batch,)})
    rs = np.random.RandomState(0)
    bd = ts.shard_batch({
        "data": rs.uniform(-1, 1, (batch, 3, 16, 16)).astype(np.float32),
        "softmax_label": rs.randint(0, 8, (batch,)).astype(np.float32)})
    hyper = ts.fopt.hyper(0)
    hlo = ts._step.lower(params, state, aux, bd, jax.random.PRNGKey(0),
                         hyper, np.int32(1)).compile().as_text()
    scattered = hlo.count("reduce-scatter") > 0 or (
        hlo.count("all-reduce") > 0 and hlo.count("dynamic-slice") > 0)
    assert scattered, "zero mode compiled without gradient scattering"
    assert hlo.count("all-gather") > 0, \
        "zero mode compiled without the param all-gather"
    # state shards: every leaf lies cut in dp parts along its axis 0
    for k, st in state.items():
        for leaf in st:
            _assert_dp_sharded(leaf, 8, k)


# ------------------------------------------- level 1: the form of a shard
def _conv10(classes=10):
    """Leading axes 8 (filters, norms) and 10 (the head): a ``dp`` of 4
    divides the first and not the second."""
    d = mx.sym.Variable("data")
    h = mx.sym.Convolution(d, name="c1", num_filter=8, kernel=(3, 3),
                           pad=(1, 1), no_bias=True)
    h = mx.sym.BatchNorm(h, name="bn1", fix_gamma=False)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.Convolution(h, name="c2", num_filter=8, kernel=(3, 3),
                           pad=(1, 1), no_bias=True)
    h = mx.sym.BatchNorm(h, name="bn2", fix_gamma=False)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.Pooling(h, global_pool=True, pool_type="avg", kernel=(1, 1))
    h = mx.sym.Flatten(h)
    h = mx.sym.FullyConnected(h, name="fc", num_hidden=classes)
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _tfm():
    from mxnet_tpu.models import transformer
    return transformer.get_symbol(vocab_size=24, seq_len=8, num_layers=1,
                                  num_hidden=16, num_heads=2)


def _net_and_batch(kind, batch, seed=0):
    rs = np.random.RandomState(seed)
    if kind == "conv":
        return _conv10(), {
            "data": rs.uniform(-1, 1, (batch, 3, 8, 8)).astype(np.float32),
            "softmax_label": rs.randint(0, 10, (batch,)).astype(np.float32)}
    return _tfm(), {
        "data": rs.randint(0, 24, (batch, 8)).astype(np.float32),
        "softmax_label": rs.randint(0, 24, (batch, 8)).astype(np.float32)}


def _level1(kind, opt_name, zero, dp=4, batch=8, steps=3):
    if opt_name == "sgd":
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9, wd=1e-4,
                               rescale_grad=1.0 / batch)
    else:
        opt = mx.optimizer.Adam(learning_rate=1e-3,
                                rescale_grad=1.0 / batch)
    net, host = _net_and_batch(kind, batch)
    ts = TrainStep(net, opt, zero=zero,
                   mesh=make_mesh({"dp": dp}, devices=jax.devices()[:dp]))
    p, s, a = ts.init({k: v.shape for k, v in host.items()
                       if k == "data"},
                      {"softmax_label": host["softmax_label"].shape})
    b = ts.shard_batch(host)
    key = jax.random.PRNGKey(7)
    for _ in range(steps):
        p, s, a, _ = ts(p, s, a, b, rng=key)
    return ts, p, s, a, b


@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
@pytest.mark.parametrize("kind", ["conv", "transformer"])
def test_zero1_matches_replicated_after_three_steps(kind, opt_name):
    """Level 1 against the replicated step over the same mesh, float32,
    three steps (momentum and Adam's moments take part): parameters and
    the state, read back in the leaves' shapes, to the last few bits.
    (Not bitwise, and not before this form either: XLA's CPU fusions
    contract the rule's multiply-adds differently round a sharded
    operand.  The f64 pair above holds 1e-9.)"""
    ts1, p1, s1, a1, _ = _level1(kind, opt_name, 1)
    ts0, p0, s0, a0, _ = _level1(kind, opt_name, 0)
    for n in p0:
        np.testing.assert_allclose(np.asarray(p1[n]), np.asarray(p0[n]),
                                   rtol=2e-6, atol=2e-7, err_msg=n)
        for l1, l0 in zip(s1[n], s0[n]):
            got = ts1.unflatten_host(n, np.asarray(l1))
            assert got.shape == l0.shape
            np.testing.assert_allclose(got, np.asarray(l0), rtol=2e-5,
                                       atol=2e-7, err_msg=n)
    for n in a0:
        np.testing.assert_allclose(np.asarray(a1[n]), np.asarray(a0[n]),
                                   rtol=2e-6, atol=2e-7, err_msg=n)


@pytest.mark.parametrize("leaf,kept", [
    ("c2_weight", True),        # (8, 8, 3, 3): 4 divides 8
    ("bn1_gamma", True),        # (8,)
    ("fc_weight", False),       # (10, 8): 4 does not divide 10
    ("fc_bias", False),         # (10,): 4 rows of 3, two of them pad
], ids=["filter", "vector", "indivisible_matrix", "indivisible_vector"])
def test_zero1_state_has_the_leafs_shape_where_dp_divides(leaf, kept):
    """A leaf whose leading axis ``dp`` divides keeps its own shape,
    sharded along that axis; any other takes the zero-padded flat
    ``(dp, chunk)`` view.  Either way a device holds one ``dp``-th, born
    so (``init``) and left so by a step, and the host reads the leaf's
    own shape back."""
    dp = 4
    ts, p, s, a, _ = _level1("conv", "adam", 1, dp=dp, steps=1)
    shape = tuple(p[leaf].shape)
    assert ts.plan.keeps_shape(shape) is kept
    size = int(np.prod(shape))
    want = shape if kept else (dp, -(-size // dp))
    fresh = ts.init({"data": (8, 3, 8, 8)}, {"softmax_label": (8,)})[1]
    for state in (fresh, s):
        assert len(state[leaf]) == 2
        for x in state[leaf]:
            assert tuple(x.shape) == want
            _assert_dp_sharded(x, dp, leaf)
            assert ts.unflatten_host(leaf, np.asarray(x)).shape == shape
    # the parameter itself stays whole on every device
    assert p[leaf].sharding.spec == P()
    # levels 2 and 3 keep the flat view for every leaf (one bucket)
    assert not PlacementPlan(zero=2, dp=dp).keeps_shape(shape)
    assert not PlacementPlan(zero=3, dp=dp).keeps_shape(shape)
    assert not PlacementPlan(zero=1, dp=dp).keeps_shape(())


def _flat_reshapes(text, shapes, dp):
    """Reshapes of the lowered (StableHLO) program between the shape of
    one of ``shapes`` and a flat array of it: rank 1 of its size, or its
    ``(dp, chunk)`` view, padded or not."""
    import re
    flats = {}
    for sh in shapes:
        size = int(np.prod(sh))
        chunk = -(-size // dp)
        flats.setdefault(tuple(sh), set()).update(
            {(size,), (dp * chunk,), (dp, chunk)})
    found = []
    for m in re.finditer(r"stablehlo\.reshape [^:]*: \(tensor<([0-9x]*)x?"
                         r"[a-z]+[0-9]*>\) -> tensor<([0-9x]*)x?[a-z]+"
                         r"[0-9]*>", text):
        a, b = (tuple(int(d) for d in g.split("x") if d)
                for g in m.groups())
        if a != b and ((a in flats and b in flats[a])
                       or (b in flats and a in flats[b])):
            found.append((a, b))
    return found


@pytest.mark.parametrize("level", [1, 2])
def test_zero1_lowered_step_reshapes_no_divisible_leaf_flat(level):
    """By counts, on the lowered step program: at level 1 no leaf whose
    leading axis ``dp`` divides is reshaped to or from a flat array (on
    the TPU such a reshape of a 3x3 filter is a relayout, 1.5 ms for
    stage 4's: PERF.md 6, PR 37); the indivisible leaves still are.
    Level 2, whose bucket is flat, is the witness that the count sees
    such reshapes."""
    dp = 4
    ts, p, s, a, b = _level1("conv", "sgd", level, dp=dp, steps=0)
    text = ts._step.lower(p, s, a, b, jax.random.PRNGKey(0),
                          ts.fopt.hyper(0), np.int32(1)).as_text()
    shapes = {n: tuple(v.shape) for n, v in p.items()}
    divisible = [sh for sh in shapes.values() if sh[0] % dp == 0]
    others = [sh for sh in shapes.values() if sh[0] % dp]
    assert len(divisible) == 6 and len(others) == 2
    if level == 1:
        assert _flat_reshapes(text, divisible, dp) == []
        assert _flat_reshapes(text, others, dp)
    else:
        assert len(_flat_reshapes(text, divisible, dp)) >= len(divisible)


def test_zero1_bytes_count_the_shard_without_pad():
    """``per_device_bytes`` (the ``zero_*_bytes`` gauges' source) counts a
    kept leaf's shard as it lies, a ``dp``-th of the leaf, and a flat
    view's row with its pad."""
    dp = 4
    ts, p, s, _, _ = _level1("conv", "adam", 1, dp=dp, steps=0)
    want = 0
    for n, v in p.items():
        size = int(np.prod(v.shape))
        per = size // dp if v.shape[0] % dp == 0 else -(-size // dp)
        want += 2 * 4 * per                       # Adam's two float32 slots
    got = ts.zero_bytes(p, s)
    assert got["opt"] == want
    assert got["param"] == got["grad"] == sum(
        4 * int(np.prod(v.shape)) for v in p.values())


def test_reduce_scatter_hlo_supported_on_cpu():
    """The explicit collective DOES lower to a reduce-scatter HLO on this
    backend (shard_map + psum_scatter) — pinning that the graph test's
    all-reduce+slice outcome is a missing fusion pass, not a missing
    instruction."""
    import re
    mesh = make_mesh({"dp": 8})
    from jax.sharding import PartitionSpec as P, NamedSharding
    @jax.jit
    def f(x):
        def body(xl):
            return jax.lax.psum_scatter(xl, "dp", scatter_dimension=0,
                                        tiled=True)
        return jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                             out_specs=P("dp"))(x)

    x = jax.device_put(np.ones((64, 4), np.float32),
                       NamedSharding(mesh, P("dp")))
    hlo = f.lower(x).compile().as_text()
    assert len(re.findall("reduce-scatter", hlo)) > 0


def test_zero_requires_dp_mesh():
    with pytest.raises(mx.base.MXNetError):
        TrainStep(_net(), mx.optimizer.SGD(), mesh=None, zero=True)


# ===================================================== ZeRO levels 2 / 3
BATCH = 8


def _mlp(classes=4):
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, name="fc1", num_hidden=16)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, name="fc2", num_hidden=16)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, name="fc3", num_hidden=classes)
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _mlp_batch(dtype=np.float32, seed=0):
    rs = np.random.RandomState(seed)
    return {"data": rs.uniform(-1, 1, (BATCH, 10)).astype(dtype),
            "softmax_label": rs.randint(0, 4, (BATCH,)).astype(dtype)}


def _sgd():
    return mx.optimizer.SGD(learning_rate=0.1, momentum=0.9, wd=1e-4,
                            rescale_grad=1.0 / BATCH)


def _cast64(p, s, a):
    return ({k: v.astype(jnp.float64) for k, v in p.items()},
            {k: tuple(x.astype(jnp.float64) for x in st)
             for k, st in s.items()},
            {k: v.astype(jnp.float64) for k, v in a.items()})


def _host_logical(ts, params):
    if getattr(ts, "zero", 0) >= 3:
        return {n: ts.unflatten_host(n, np.asarray(v))
                for n, v in params.items()}
    return {n: np.asarray(v) for n, v in params.items()}


def _run_level(zero, pp=0, dp=8, M=2, schedule="gpipe", f64=False,
               steps=2, policy=None):
    dt = np.float64 if f64 else np.float32
    if pp:
        ts = PipelineTrainStep(
            _mlp(), _sgd(),
            mesh=make_pp_mesh(pp, dp=dp, devices=jax.devices()[:pp * dp]),
            num_microbatches=M, zero=zero, schedule=schedule,
            policy=policy)
    elif zero:
        ts = TrainStep(_mlp(), _sgd(),
                       mesh=make_mesh({"dp": dp},
                                      devices=jax.devices()[:dp]),
                       zero=zero, policy=policy)
    else:
        ts = TrainStep(_mlp(), _sgd(), policy=policy)
    p, s, a = ts.init({"data": (BATCH, 10)}, {"softmax_label": (BATCH,)})
    if f64:
        p, s, a = _cast64(p, s, a)
    b = ts.shard_batch(_mlp_batch(dt))
    key = jax.random.PRNGKey(7)
    for _ in range(steps):
        p, s, a, outs = ts(p, s, a, b, rng=key)
    return ts, p, s, a


@pytest.mark.parametrize("zero", [2, 3])
@pytest.mark.parametrize("cfg", [
    ("dp8", 0, 8, "gpipe"),
    ("dp2xpp2-gpipe", 2, 2, "gpipe"),
    ("dp2xpp2-1f1b", 2, 2, "1f1b"),
    ("dp2xpp2-interleaved", 2, 2, "interleaved"),
], ids=lambda c: c[0] if isinstance(c, tuple) else c)
def test_zero23_parity_matrix_f32(zero, cfg):
    """zero∈{2,3} × {dp, dp×pp per schedule} matches the replicated
    single-program step at f32 2e-5 (collective/summation reorder
    noise); the slow f64 twin pins @1e-9."""
    _name, pp, dp, schedule = cfg
    _, p_ref, _, a_ref = _run_level(0)
    ts, p, s, a = _run_level(zero, pp=pp, dp=dp, M=2, schedule=schedule)
    ph = _host_logical(ts, p)
    for n in p_ref:
        np.testing.assert_allclose(ph[n], np.asarray(p_ref[n]),
                                   rtol=2e-5, atol=1e-6,
                                   err_msg="zero=%d %s %s"
                                           % (zero, cfg[0], n))
    # sharded residency: state rows at any level, params too at level 3
    for n, st in s.items():
        for leaf in st:
            assert leaf.shape[0] == ts.plan.dp, (n, leaf.shape)
    if zero >= 3:
        for n, v in p.items():
            assert v.shape[0] == ts.plan.dp, (n, v.shape)


@pytest.mark.slow
@pytest.mark.parametrize("zero", [2, 3])
@pytest.mark.parametrize("cfg", [
    ("dp8", 0, 8, "gpipe"),
    ("dp2xpp2-gpipe", 2, 2, "gpipe"),
    ("dp2xpp2-1f1b", 2, 2, "1f1b"),
    ("dp2xpp2-interleaved", 2, 2, "interleaved"),
], ids=lambda c: c[0] if isinstance(c, tuple) else c)
def test_zero23_parity_matrix_f64(zero, cfg, f64):
    _name, pp, dp, schedule = cfg
    _, p_ref, _, _ = _run_level(0, f64=True)
    ts, p, _, _ = _run_level(zero, pp=pp, dp=dp, schedule=schedule,
                             f64=True)
    ph = _host_logical(ts, p)
    for n in p_ref:
        np.testing.assert_allclose(ph[n], np.asarray(p_ref[n]),
                                   rtol=1e-9, atol=1e-12,
                                   err_msg="zero=%d %s %s"
                                           % (zero, cfg[0], n))


def test_normalize_zero_levels_and_bool_compat():
    assert normalize_zero(False) == 0 and normalize_zero(True) == 1
    assert [normalize_zero(v) for v in (0, 1, 2, 3)] == [0, 1, 2, 3]
    with pytest.raises(MXNetError):
        normalize_zero(4)
    with pytest.raises(MXNetError):
        normalize_zero(-1)
    with pytest.raises(MXNetError):
        TrainStep(_mlp(), _sgd(), mesh=make_mesh({"dp": 8}), zero=7)


def test_zero3_gather_params_and_roundtrip():
    """gather_params materialises logical replicated weights equal to the
    host unpad of the flat shards; below level 3 it is the identity."""
    ts, p, s, a = _run_level(3, steps=1)
    full = ts.gather_params(p)
    for n in p:
        want = ts.unflatten_host(n, np.asarray(p[n]))
        got = np.asarray(full[n])
        assert got.shape == want.shape
        np.testing.assert_array_equal(got, want, err_msg=n)
    ts1, p1, _, _ = _run_level(1, steps=1)
    assert ts1.gather_params(p1) is p1


def test_zero_bytes_staircase():
    """The plan's per-device residency walks the ladder: opt drops at
    level 1, grad at level 2, param at level 3 — and the zero3 param
    residency sits strictly below replicated/level-1's (the live-bytes
    pin)."""
    got = {}
    for level in (1, 2, 3):
        ts, p, s, _ = _run_level(level, steps=1)
        got[level] = ts.zero_bytes(p, s)
    # state always sharded at >= 1; gradient residency shrinks at 2
    assert got[2]["grad"] < got[1]["grad"]
    assert got[2]["param"] == got[1]["param"]
    # the level-3 pin: per-device params strictly below replicated's
    assert got[3]["param"] < got[1]["param"]
    assert got[3]["param"] <= -(-got[1]["param"] // 8) + 64
    assert got[3]["grad"] == got[2]["grad"]


def test_zero3_amp_overflow_skip_preserves_sharded_masters():
    """An overflow step under zero3 must skip the update without
    corrupting the sharded f32 masters or the sharded optimizer state,
    and the scale must halve (mirrors the replicated AMP pin)."""
    from mxnet_tpu.amp import Policy
    pol = Policy("float32", loss_scale=16.0, growth_interval=50)
    ts = TrainStep(_mlp(), _sgd(), mesh=make_mesh({"dp": 8}), zero=3,
                   policy=pol)
    p, s, a = ts.init({"data": (BATCH, 10)}, {"softmax_label": (BATCH,)})
    bad = _mlp_batch()
    bad["data"][0, 0] = np.inf
    bd = ts.shard_batch(bad)
    before = {k: np.asarray(v).copy() for k, v in p.items()}
    st_before = {k: tuple(np.asarray(x).copy() for x in st)
                 for k, st in s.items()}
    p, s, a, outs = ts(p, s, a, bd)
    for k in before:
        np.testing.assert_array_equal(before[k], np.asarray(p[k]),
                                      err_msg=k)
        for m0, m1 in zip(st_before[k], s[k]):
            np.testing.assert_array_equal(m0, np.asarray(m1))
    host = jax.device_get(ts._scale_state)
    assert float(host["scale"]) == 8.0 and int(host["overflow"]) == 1
    # and a clean step afterwards still updates the sharded masters
    good = ts.shard_batch(_mlp_batch())
    p, s, a, _ = ts(p, s, a, good)
    assert any(not np.array_equal(before[k], np.asarray(p[k]))
               for k in before)


def test_zero23_checkpoint_topology_carries_level():
    for level in (2, 3):
        ts, p, s, a = _run_level(level, steps=1)
        topo = ts.checkpoint_topology()
        assert topo["zero"] == level
        if level >= 3:
            assert topo["param_shapes"]["fc1_weight"] == [16, 10]


def test_zero_gauges_and_strict_noop(tmp_path):
    from mxnet_tpu import telemetry as tel
    tel.start(str(tmp_path / "t.jsonl"))
    try:
        ts, p, s, a = _run_level(3, steps=1)
        b = ts.shard_batch(_mlp_batch())
        p, s, a, _ = ts(p, s, a, b)
        gauges = tel.gauges()
        assert gauges["zero_param_bytes"] == ts.zero_bytes(p, s)["param"]
        assert gauges["zero_grad_bytes"] == ts.zero_bytes(p, s)["grad"]
        ts.gather_params(p)
        assert any(e.get("name") == "zero.gather" for e in tel.events())
    finally:
        tel.stop()
    # strict no-op: with telemetry off a zero step emits nothing (the
    # registry keeps the last session's values; no NEW update may land —
    # a level-2 resnet step would write different byte values)
    g0 = dict(tel.gauges())
    ts, p, s, a = _run_level(2, steps=1)
    assert tel.gauges().get("zero_param_bytes") \
        == g0.get("zero_param_bytes")
    assert tel.gauges().get("zero_grad_bytes") == g0.get("zero_grad_bytes")


def test_zero_sanitized_e2e_and_gather_in_ledger():
    """A zero3 train + gather under MXNET_SAN=all:raise runs clean
    (donation ledger, recompile budget, hot-path syncs, collective
    ledger), and the zero.gather dispatch lands in the collective
    ledger."""
    from mxnet_tpu import sanitize as san
    # the violation log is the process's: one that another file's test
    # provoked on this worker is not this run's
    san.reset()
    san.arm("recompile,sync,donate,collective", mode="raise")
    try:
        ts, p, s, a = _run_level(3, steps=3)
        full = ts.gather_params(p)
        jax.block_until_ready(jax.tree_util.tree_leaves(full)[0])
        ledger = san.ledger_tail(64)
        assert any(e["kind"] == "mxtpu_zero_gather" for e in ledger)
        assert not san.violations()
    finally:
        san.disarm()


def test_zero3_donation_ledger_names_reuse():
    """Re-stepping with the donated flat shards is named by the DONATE
    checker before XLA's cryptic deleted-buffer crash."""
    from mxnet_tpu import sanitize as san
    ts, p, s, a = _run_level(3, steps=1)
    b = ts.shard_batch(_mlp_batch())
    san.arm("donate", mode="raise")
    try:
        p1, s1, a1, _ = ts(p, s, a, b)
        with pytest.raises(san.SanitizerError):
            ts(p, s, a, b)   # p/s/a were donated into the previous step
    finally:
        san.disarm()


# ------------------------------------------------------ MXNET_ZERO dispatch
def _fit_data(seed=0):
    rs = np.random.RandomState(seed)
    x = rs.uniform(-1, 1, (64, 16)).astype(np.float32)
    w = rs.uniform(-1, 1, (16,))
    y = (x @ w > 0).astype(np.float32)
    return mx.io.NDArrayIter(x, y, batch_size=16, shuffle=False,
                             label_name="softmax_label")


def _fit_net(classes=2):
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, name="fc1", num_hidden=32)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, name="fc2", num_hidden=classes)
    return mx.sym.SoftmaxOutput(h, name="softmax")


@pytest.mark.parametrize("level", [1, 2, 3])
def test_zero_fit_dispatch_trains(monkeypatch, level):
    monkeypatch.setenv("MXNET_ZERO", str(level))
    data = _fit_data()
    mod = mx.Module(_fit_net(), context=mx.cpu())
    mod.fit(data, num_epoch=4, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            initializer=mx.init.Xavier(), eval_metric="acc")
    ts = mod._fused_ts_cache[1]
    assert isinstance(ts, TrainStep) and ts.zero == level
    assert ts.mesh is not None and ts.plan.dp == len(jax.devices())
    data.reset()
    score = dict(mod.score(data, mx.metric.Accuracy()))
    assert score["accuracy"] > 0.8, score
    # get_params returns LOGICAL shapes even at level 3
    arg, _aux = mod.get_params()
    assert arg["fc1_weight"].shape == (32, 16)
    # and so does the updater's state, whatever form the step kept it in
    # (at level 1 over 8 devices: fc1's in its shape, fc2's 2 rows flat)
    assert [st.shape for _, st in sorted(mod._updater.states.items())] \
        == [(32, 16), (32,), (2, 32), (2,)]


def test_zero_fit_env_unset_is_plain_fused_path(monkeypatch):
    monkeypatch.delenv("MXNET_ZERO", raising=False)
    data = _fit_data()
    mod = mx.Module(_fit_net(), context=mx.cpu())
    mod.fit(data, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    ts = mod._fused_ts_cache[1]
    assert isinstance(ts, TrainStep) and ts.zero == 0 and ts.mesh is None


def test_zero_fit_toggle_rebuilds_via_cache_key(monkeypatch):
    monkeypatch.delenv("MXNET_ZERO", raising=False)
    data = _fit_data()
    mod = mx.Module(_fit_net(), context=mx.cpu())
    mod.fit(data, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    assert mod._fused_ts_cache[1].zero == 0
    monkeypatch.setenv("MXNET_ZERO", "2")
    data.reset()
    mod.fit(data, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    ts2 = mod._fused_ts_cache[1]
    assert ts2.zero == 2
    # same level reuses the cached step; unset restores the plain path
    data.reset()
    mod.fit(data, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    assert mod._fused_ts_cache[1] is ts2
    monkeypatch.delenv("MXNET_ZERO")
    data.reset()
    mod.fit(data, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    assert mod._fused_ts_cache[1].zero == 0


def test_zero_fit_indivisible_batch_raises(monkeypatch):
    # the dp mesh shards each batch over all local devices — an
    # indivisible batch is a curated error at dispatch, not an obscure
    # jit sharding failure at the first step
    monkeypatch.setenv("MXNET_ZERO", "2")
    rs = np.random.RandomState(0)
    x = rs.uniform(-1, 1, (18, 16)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    data = mx.io.NDArrayIter(x, y, batch_size=6,
                             label_name="softmax_label")
    mod = mx.Module(_fit_net(), context=mx.cpu())
    with pytest.raises(MXNetError, match="not divisible"):
        mod.fit(data, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1})


def test_zero_fit_bad_level_raises(monkeypatch):
    monkeypatch.setenv("MXNET_ZERO", "5")
    data = _fit_data()
    mod = mx.Module(_fit_net(), context=mx.cpu())
    with pytest.raises(MXNetError):
        mod.fit(data, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1})


def test_run_compare_zero_block_gate(tmp_path):
    """run_compare ingests the dryrun's `zero` block: per-device byte
    metrics gate with down-direction hints, the config block is
    identity (a level change is never a regression pair), and the
    committed MULTICHIP_ZERO_r01.json self-compares rc=0."""
    import json
    import os
    from tools import run_compare as rc

    def record(param_mb, grad_mb, level=3):
        return {"metric": "zero3_param_bytes_mb", "value": param_mb,
                "zero": {"zero_param_bytes_mb": param_mb,
                         "zero_grad_bytes_mb": grad_mb,
                         "zero_opt_bytes_mb": grad_mb,
                         "config": {"zero": level, "dp": 4, "pp": 0}}}

    base = tmp_path / "a.json"
    base.write_text(json.dumps(record(10.0, 5.0)))
    same = tmp_path / "b.json"
    same.write_text(json.dumps(record(10.0, 5.0)))
    worse = tmp_path / "c.json"
    worse.write_text(json.dumps(record(20.0, 5.0)))
    other = tmp_path / "d.json"
    other.write_text(json.dumps(record(40.0, 40.0, level=1)))
    assert rc.main([str(base), str(same), "--check"]) == 0
    # per-device param bytes going UP is a REGRESSION (down-hint)
    assert rc.main([str(base), str(worse), "--check"]) == 2
    # a different ZeRO level is a different experiment, not a regression
    assert rc.main([str(base), str(other), "--check"]) == 0
    run = rc.load_run(str(base))
    assert run.bench["zero_param_bytes_mb"] == pytest.approx(10.0)
    assert "config" not in run.bench
    committed = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                             "MULTICHIP_ZERO_r01.json")
    assert rc.main([committed, committed, "--check"]) == 0
    rec = rc.load_run(committed)
    assert rec.bench["zero_param_bytes_mb"] > 0


def test_zero_fit_composes_with_pp(monkeypatch):
    monkeypatch.setenv("MXNET_ZERO", "3")
    monkeypatch.setenv("MXNET_PP", "2")
    monkeypatch.setenv("MXNET_PP_MICROBATCH", "2")
    data = _fit_data()
    mod = mx.Module(_fit_net(), context=mx.cpu())
    mod.fit(data, num_epoch=4, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.init.Xavier(), eval_metric="acc")
    ts = mod._fused_ts_cache[1]
    assert isinstance(ts, PipelineTrainStep) and ts.zero == 3
    data.reset()
    score = dict(mod.score(data, mx.metric.Accuracy()))
    assert score["accuracy"] > 0.8, score
