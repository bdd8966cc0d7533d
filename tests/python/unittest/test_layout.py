"""NHWC layout pass: numerical parity with logical-NCHW execution.

The executor rewrites conv-net graphs to channel-last between layout-aware
ops (executor._Lowered.run).  These tests pin the semantics: identical
gradients and aux updates in both modes (f64, so reduction-order noise
cannot mask a real bug), fused BatchNorm+ReLU correctness, and the
EvalStep bf16 path that the round-2 BatchNorm promoted to f32 by accident.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.executor import _Lowered
from mxnet_tpu import random as mxr


@pytest.fixture
def f64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


def _train_step_params(layouts, net, dshape, nclass, seed=0):
    """One float64 train step of ``net`` under each of ``layouts``, from
    one initialisation: the layout is read when the step is traced."""
    from mxnet_tpu.train import TrainStep
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
    ts = TrainStep(net, opt)
    # values straight from numpy: the initializers compile a program for
    # every shape, which is most of a small case's time and not its subject
    rng = np.random.RandomState(seed)
    arg_shapes, _, aux_shapes = net.infer_shape(
        data=dshape, softmax_label=(dshape[0],))
    params = {}
    for n, shape in zip(net.list_arguments(), arg_shapes):
        if n.endswith("weight"):
            params[n] = rng.randn(*shape) * np.sqrt(2.0 / np.prod(shape[1:]))
        elif n not in ("data", "softmax_label"):
            params[n] = n.endswith("gamma") + 0.1 * rng.randn(*shape)
    aux = {n: np.full(shape, float(n.endswith("var")))
           for n, shape in zip(net.list_auxiliary_states(), aux_shapes)}
    state = ts.fopt.init_state(params)
    bd = {"data": jnp.asarray(rng.uniform(-1, 1, dshape)),
          "softmax_label": jnp.asarray(
              rng.randint(0, nclass, (dshape[0],)).astype(np.float64))}
    mxr.seed(seed)
    key = mxr.next_key()
    hyper = ts.fopt.hyper(0)
    results = []
    for layout in layouts:
        os.environ["MXNET_CONV_LAYOUT"] = layout
        try:
            # a function of its own each time: jit keeps its traces by
            # the function, and the same one would not be traced again
            p, s, a, outs = jax.jit(lambda *args: ts._step_fn(*args))(
                params, state, aux, bd, key, hyper, np.int32(1))
            results.append((p, a, outs))
        finally:
            os.environ.pop("MXNET_CONV_LAYOUT", None)
    return results


def _resnet18():
    from mxnet_tpu.models import resnet
    return resnet.get_symbol(num_classes=10, num_layers=18,
                             image_shape="3,32,32")


# each model at the smallest image its strides take: BatchNorm, residual
# adds and Concat (resnet, inception); LRN, Dropout and Flatten after
# pooling (alexnet); a plain conv stack (vgg); tanh (lenet)
PARITY_MODELS = {
    "resnet": (_resnet18, (4, 3, 32, 32)),
    "inception": (lambda: models.get_inception_v3(num_classes=10),
                  (2, 3, 299, 299)),
    "alexnet": (lambda: models.get_alexnet(num_classes=10), (1, 3, 67, 67)),
    "vgg": (lambda: models.get_vgg(num_classes=10, num_layers=11),
            (1, 3, 32, 32)),
    "lenet": (lambda: models.get_lenet(num_classes=10), (2, 1, 16, 16)),
}


@pytest.mark.parametrize("model", list(PARITY_MODELS))
def test_nhwc_pass_parity_f64(f64, model):
    build, shape = PARITY_MODELS[model]
    net, ncls = build(), 10
    (p1, a1, o1), (p2, a2, o2) = _train_step_params(
        ("NCHW", "NHWC"), net, shape, ncls)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   atol=1e-9, err_msg=k)
    for k in a1:
        np.testing.assert_allclose(np.asarray(a1[k]), np.asarray(a2[k]),
                                   atol=1e-9, err_msg=k)
    np.testing.assert_allclose(np.asarray(o1[0]), np.asarray(o2[0]),
                               atol=1e-9)


def test_fused_bn_relu_matches_reference(f64):
    """Executor BatchNorm->relu fusion == hand-rolled conv/bn/relu chain."""
    mxr.seed(0)
    key = mxr.next_key()
    data = mx.sym.Variable("data")
    c = mx.sym.Convolution(data=data, kernel=(3, 3), num_filter=4,
                           pad=(1, 1), name="c", no_bias=True)
    bn = mx.sym.BatchNorm(data=c, name="bn", fix_gamma=False)
    act = mx.sym.Activation(data=bn, act_type="relu")
    top = mx.sym.Convolution(data=act, kernel=(1, 1), num_filter=3,
                             name="c2", no_bias=True)
    low = _Lowered(top)
    assert len(low.fused_relu) == 1

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 3, 8, 8))
    w = jnp.asarray(rng.randn(4, 3, 3, 3) * 0.3)
    w2 = jnp.asarray(rng.randn(3, 4, 1, 1) * 0.3)
    gamma = jnp.asarray(rng.rand(4) + 0.5)
    beta = jnp.asarray(rng.randn(4) * 0.1)
    aux = {"bn_moving_mean": jnp.zeros(4), "bn_moving_var": jnp.ones(4)}

    def loss_fused(args):
        vals = {"data": x, "c_weight": args[0], "c2_weight": args[1],
                "bn_gamma": args[2], "bn_beta": args[3]}
        outs, _ = low.run(vals, aux, key, True)
        return jnp.sum(jnp.sin(outs[0]))

    def loss_ref(args):
        w, w2, g, b = args
        dn = ("NCHW", "OIHW", "NCHW")
        h = jax.lax.conv_general_dilated(x, w, (1, 1), [(1, 1)] * 2,
                                         dimension_numbers=dn)
        mean = h.mean((0, 2, 3))
        var = h.var((0, 2, 3))
        cs = (1, -1, 1, 1)
        hn = (h - mean.reshape(cs)) * jax.lax.rsqrt(var.reshape(cs) + 1e-3) \
            * g.reshape(cs) + b.reshape(cs)
        hr = jnp.maximum(hn, 0)
        o = jax.lax.conv_general_dilated(hr, w2, (1, 1), [(0, 0)] * 2,
                                         dimension_numbers=dn)
        return jnp.sum(jnp.sin(o))

    args = (w, w2, gamma, beta)
    v1, g1 = jax.value_and_grad(loss_fused)(args)
    v2, g2 = jax.value_and_grad(loss_ref)(args)
    assert abs(float(v1 - v2)) < 1e-10
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-10)


def test_bn_custom_vjp_matches_autodiff(f64):
    """BatchNorm's hand-written backward == autodiff of the naive form,
    including the (rare) gradients through the mean/var outputs."""
    from mxnet_tpu.ops.nn import _batch_norm
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 3, 5, 5))
    gamma = jnp.asarray(rng.rand(3) + 0.5)
    beta = jnp.asarray(rng.randn(3))
    mm, mv = jnp.zeros(3), jnp.ones(3)

    def f(x, g, b):
        out, mean, var, _, _ = _batch_norm(
            x, g, b, mm, mv, is_train=True, fix_gamma=False,
            output_mean_var=True)
        return jnp.sum(out * jnp.cos(out)) + jnp.sum(mean * var * var)

    def ref(x, g, b):
        axes, cs = (0, 2, 3), (1, -1, 1, 1)
        mean = x.mean(axes)
        var = x.var(axes)
        out = (x - mean.reshape(cs)) * jax.lax.rsqrt(var.reshape(cs) + 1e-3) \
            * g.reshape(cs) + b.reshape(cs)
        return jnp.sum(out * jnp.cos(out)) + jnp.sum(mean * var * var)

    g1 = jax.grad(f, argnums=(0, 1, 2))(x, gamma, beta)
    g2 = jax.grad(ref, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-10)


def test_evalstep_bfloat16():
    """Round-2 bug: BatchNorm inference promoted bf16 to f32 and crashed the
    next conv; EvalStep(dtype='bfloat16') must run end to end."""
    from mxnet_tpu.models import resnet
    from mxnet_tpu.train import TrainStep, EvalStep
    net = resnet.get_symbol(num_classes=10, num_layers=18,
                            image_shape="3,32,32")
    opt = mx.optimizer.SGD(learning_rate=0.1)
    ts = TrainStep(net, opt, dtype="bfloat16")
    params, state, aux = ts.init({"data": (4, 3, 32, 32)},
                                 {"softmax_label": (4,)})
    es = EvalStep(net, dtype="bfloat16")
    bd = {"data": jnp.zeros((4, 3, 32, 32), jnp.float32),
          "softmax_label": jnp.zeros((4,), jnp.float32)}
    out = es(params, aux, bd)
    assert out[0].shape == (4, 10)
    assert bool(jnp.all(jnp.isfinite(out[0].astype(jnp.float32))))


def test_pooling_layout_parity():
    from mxnet_tpu.ops.nn import _pooling
    x = jnp.asarray(np.random.RandomState(0).randn(2, 5, 13, 13),
                    jnp.float32)
    xt = jnp.moveaxis(x, 1, -1)
    for pt in ("max", "avg", "sum"):
        for gp in (False, True):
            for conv_ in ("valid", "full"):
                kw = dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                          pool_type=pt, global_pool=gp,
                          pooling_convention=conv_)
                a = _pooling(x, **kw)
                b = jnp.moveaxis(_pooling(xt, layout="NHWC", **kw), -1, 1)
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-6, atol=1e-6)
