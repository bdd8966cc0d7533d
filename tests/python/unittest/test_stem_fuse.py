"""Input-BN + stem-conv fusion (executor.stem_fuse + ops/nn.py
input_bn_conv).

The fused backward replaces the backward-data convolution into the input
grid with per-tap rectangle sums of the cotangent (2D prefix sums) — an
exact real-arithmetic identity for d(beta).  These tests pin:

- unit: d(beta) from the rectangle-sum VJP vs autodiff of the unfused
  composition, across stem geometries, in f64;
- graph: a full ResNet-50 train step with MXNET_STEM_FUSE on vs off
  matches at 1e-9 in f64 (params AND aux moving stats);
- gating: the peephole must NOT fire when the input needs gradients,
  when any one condition of its pattern in ``_Lowered.__init__`` fails
  (the step is then the unfused graph's), or outside training.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import random as mxr
from mxnet_tpu.executor import _Lowered
from mxnet_tpu.ops.nn import input_bn_conv


@pytest.fixture
def f64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


GEOMS = [
    # H, K, S, P, Cin, Cout   (stem-like shapes incl. the 7x7/s2/p3 stem)
    (16, 7, 2, 3, 3, 8),
    (16, 3, 1, 1, 3, 8),
    (15, 5, 2, 2, 4, 8),
    (8, 1, 1, 0, 3, 8),
    (9, 3, 2, 1, 2, 6),
    (16, 3, 2, 0, 3, 8),
]


def _unfused(x, b, w, eps, k, s, p):
    axes = (0, 1, 2)
    mean = jnp.mean(x, axis=axes)
    var = jnp.maximum(jnp.mean(jnp.square(x), axis=axes)
                      - jnp.square(mean), 0.0)
    y = (x - mean) * jax.lax.rsqrt(var + eps) + b
    return jax.lax.conv_general_dilated(
        y, jnp.transpose(w, (2, 3, 1, 0)), window_strides=(s, s),
        padding=[(p, p), (p, p)], dimension_numbers=("NHWC", "HWIO", "NHWC"))


@pytest.mark.parametrize("geom", GEOMS)
def test_dbeta_rectangle_sums_vs_autodiff(geom, f64):
    h, k, s, p, cin, cout = geom
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(3, h, h, cin))
    w = jnp.asarray(rng.randn(cout, cin, k, k) * 0.1)
    b = jnp.asarray(rng.randn(cin))
    eps = 2e-5

    def loss_fused(b_, w_):
        out, _, _ = input_bn_conv(x, b_, w_, eps, (k, k), (s, s), (p, p))
        return jnp.sum(out * jnp.cos(out))   # non-trivial head grad

    def loss_ref(b_, w_):
        out = _unfused(x, b_, w_, eps, k, s, p)
        return jnp.sum(out * jnp.cos(out))

    v1, (db1, dw1) = jax.value_and_grad(loss_fused, (0, 1))(b, w)
    v0, (db0, dw0) = jax.value_and_grad(loss_ref, (0, 1))(b, w)
    np.testing.assert_allclose(v1, v0, rtol=1e-12)
    np.testing.assert_allclose(db1, db0, rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(dw1, dw0, rtol=1e-9, atol=1e-9)


def _resnet50(image=32, nclass=10):
    from mxnet_tpu.models import resnet
    return resnet.get_symbol(num_classes=nclass, num_layers=50,
                             image_shape="3,%d,%d" % (image, image))


def _train_step(env, net=None, dshape=(4, 3, 32, 32), nclass=10, seed=0):
    for k, v in env.items():
        os.environ[k] = v
    try:
        from mxnet_tpu.train import TrainStep
        net = _resnet50(dshape[-1], nclass) if net is None else net
        batch = dshape[0]
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
        ts = TrainStep(net, opt)
        params, state, aux = ts.init({"data": dshape},
                                     {"softmax_label": (batch,)})
        params = {k2: v.astype(jnp.float64) for k2, v in params.items()}
        aux = {k2: v.astype(jnp.float64) for k2, v in aux.items()}
        rng = np.random.RandomState(seed)
        bd = {"data": jnp.asarray(rng.uniform(-1, 1, dshape)),
              "softmax_label": jnp.asarray(
                  rng.randint(0, nclass, (batch,)).astype(np.float64))}
        mxr.seed(seed)
        key = mxr.next_key()
        hyper = ts.fopt.hyper(0)
        p, s, a, outs = jax.jit(ts._step_fn)(params, state, aux, bd, key,
                                             hyper, np.int32(1))
        return p, a, outs
    finally:
        for k in env:
            os.environ.pop(k, None)


def _assert_same_step(one, other):
    (p1, a1, o1), (p0, a0, o0) = one, other
    assert set(p1) == set(p0) and set(a1) == set(a0)
    for k in p0:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p0[k]),
                                   rtol=1e-9, atol=1e-9, err_msg=k)
    for k in a0:
        np.testing.assert_allclose(np.asarray(a1[k]), np.asarray(a0[k]),
                                   rtol=1e-9, atol=1e-9, err_msg=k)
    for x1, x0 in zip(o1, o0):
        np.testing.assert_allclose(np.asarray(x1), np.asarray(x0),
                                   rtol=1e-9, atol=1e-9)


def test_graph_parity_f64_resnet50(f64):
    """MXNET_STEM_FUSE on vs off over one full ResNet-50 train step; the
    cifar-shaped stem (3x3/s1/p1 bn_data->conv0) rides the same peephole."""
    _assert_same_step(_train_step({"MXNET_STEM_FUSE": "1"}),
                      _train_step({"MXNET_STEM_FUSE": "0"}))


def test_no_fuse_when_input_needs_grad():
    """Executor path with inputs_need_grad: d(data) must be real (the
    fused backward would return zeros for it)."""
    net = mx.sym.SoftmaxOutput(
        mx.sym.Flatten(mx.sym.Convolution(
            mx.sym.BatchNorm(mx.sym.Variable("data"), fix_gamma=True,
                             eps=2e-5, name="bn_data"),
            num_filter=4, kernel=(3, 3), pad=(1, 1), no_bias=True,
            name="conv0")), name="softmax")
    ex = net.simple_bind(mx.cpu(), data=(2, 3, 8, 8),
                         softmax_label=(2,), grad_req="write")
    rs = np.random.RandomState(1)
    ex.arg_dict["bn_data_gamma"][:] = np.ones(3, np.float32)
    ex.arg_dict["conv0_weight"][:] = \
        rs.randn(4, 3, 3, 3).astype(np.float32) * 0.1
    x = np.random.RandomState(0).rand(2, 3, 8, 8).astype(np.float32)
    y = np.array([1.0, 0.0], np.float32)
    ex.forward(is_train=True, data=mx.nd.array(x),
               softmax_label=mx.nd.array(y))
    ex.backward()
    ddata = ex.grad_dict["data"].asnumpy()
    assert np.abs(ddata).sum() > 0


def _stem_graph(bn=None, conv=None, shape=(2, 4, 8, 8), between=None,
                second_conv=False, bn_head=False):
    """data -> BatchNorm -> Convolution -> Flatten -> FC -> softmax: the
    stem the peephole takes when built with no argument.  Each argument
    breaks one of its conditions."""
    bn_kw = dict(fix_gamma=True, eps=2e-5, name="bn_data")
    bn_kw.update(bn or {})
    nd = len(shape) - 2
    conv_kw = dict(num_filter=4, kernel=(3,) * nd, pad=(1,) * nd,
                   no_bias=True, name="conv0")
    conv_kw.update(conv or {})
    b = mx.sym.BatchNorm(mx.sym.Variable("data"), **bn_kw)
    x = b[0] if bn_kw.get("output_mean_var") else b
    body = mx.sym.Convolution(between(x) if between else x, **conv_kw)
    if second_conv:
        body = body + mx.sym.Convolution(x, **dict(conv_kw, name="conv1"))
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Flatten(body), num_hidden=3,
                              name="fc"), name="softmax")
    return (mx.sym.Group([net, x]) if bn_head else net), shape


# (N, H, W, C) and (N, C, H, W) read alike on a cube, so an op told that
# its data is channel-last infers the shapes every other op does
CUBE = (2, 4, 4, 4)
GUARDS = {
    "bn_learns_gamma": dict(bn={"fix_gamma": False}),
    "bn_output_mean_var": dict(bn={"output_mean_var": True}),
    "bn_global_stats": dict(bn={"use_global_stats": True}),
    "bn_layout_attr": dict(bn={"layout": "NHWC"}, shape=CUBE),
    "bn_is_a_head": dict(bn_head=True),
    "bn_two_consumers": dict(second_conv=True),
    "bn_consumer_not_conv": dict(
        between=lambda x: mx.sym.Activation(x, act_type="tanh")),
    "conv_bias": dict(conv={"no_bias": False}),
    "conv_grouped": dict(conv={"num_group": 2}),
    "conv_dilated": dict(conv={"dilate": (2, 2), "pad": (2, 2)}),
    "conv_layout_attr": dict(conv={"layout": "NHWC"}, shape=CUBE),
    "conv_one_axis_kernel": dict(shape=(2, 4, 8)),
    "conv_three_axis_kernel": dict(shape=(2, 4, 4, 4, 4)),
}


def test_stem_graph_is_eligible_as_built():
    net, _ = _stem_graph()
    (info,) = _Lowered(net).stem_fuse.values()
    assert info["var"] == "data" and info["conv"].name == "conv0"


@pytest.mark.parametrize("guard", sorted(GUARDS))
def test_peephole_guard_keeps_generic_path(guard, f64):
    """Each condition of the stem peephole in ``_Lowered.__init__``, broken
    alone: nothing is fused, and the step is the unfused graph's."""
    net, shape = _stem_graph(**GUARDS[guard])
    assert _Lowered(net).stem_fuse == {}
    _assert_same_step(
        _train_step({"MXNET_STEM_FUSE": "1"}, net, shape, nclass=3),
        _train_step({"MXNET_STEM_FUSE": "0"}, net, shape, nclass=3))


@pytest.mark.parametrize("mode", ["is_train_false", "collect"])
def test_eligible_graph_outside_training_takes_generic_path(mode,
                                                            monkeypatch):
    """Inference and the monitor's collecting run never reach the fused
    pair, whose backward they do not need and whose internals they do."""
    net, shape = _stem_graph()
    low = _Lowered(net)
    assert len(low.stem_fuse) == 1

    def never(*a, **kw):
        raise AssertionError("the stem peephole ran")
    monkeypatch.setattr(low, "_stem_run", never)
    rs = np.random.RandomState(0)
    arg_shapes, _, aux_shapes = net.infer_shape(data=shape,
                                                softmax_label=shape[:1])
    args = {n: jnp.asarray(rs.uniform(-1, 1, s).astype(np.float32))
            for n, s in zip(net.list_arguments(), arg_shapes)}
    aux = {n: jnp.ones(s, jnp.float32)
           for n, s in zip(net.list_auxiliary_states(), aux_shapes)}
    res = low.run(args, aux, jax.random.PRNGKey(0), mode == "collect",
                  collect=mode == "collect", no_grad_inputs=("data",))
    assert res[0][0].shape == (shape[0], 3)
    if mode == "collect":
        assert {"bn_data_output", "conv0_output"} <= set(res[2])
    else:
        assert res[1] == {}      # inference moves no running statistic
