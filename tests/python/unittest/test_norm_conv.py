"""NormConv fusion: Pallas fused (BN-apply+relu) -> conv -> (stats) kernel
and its executor peephole (ops/pallas_conv.py, executor._Lowered).

Three layers of evidence:
- kernel unit: interpret-mode Pallas vs the XLA composition, values AND
  gradients, across geometries (1x1/3x3, stride 1/2, pad, odd sizes);
- graph f64 parity: a full ResNet-50 fused train step with the peephole on
  vs off must agree to 1e-9 (stats-from-epilogue, prologue-apply, aux
  updates, multi-consumer BNs, shortcut convs all exercised);
- graph interpret parity: the same with the Pallas kernel forced on (f32).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import random as mxr
from mxnet_tpu.ops.pallas_conv import (norm_conv, norm_conv_available,
                                       NC_VMEM_BUDGET)


@pytest.fixture
def f64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


GEOMS = [
    # H, K, S, P, Cin, Cout, relu, prologue, stats
    (8, 3, 1, 1, 16, 32, True, True, True),
    (8, 3, 2, 1, 16, 32, True, True, False),
    (8, 1, 1, 0, 16, 32, False, False, True),
    (9, 1, 2, 0, 16, 24, True, True, True),
    (7, 3, 2, 1, 16, 16, True, True, True),
]


@pytest.mark.parametrize("geom", GEOMS)
def test_kernel_interpret_vs_ref(geom):
    h, k, s, p, cin, cout, relu, prologue, stats = geom
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, h, h, cin).astype(np.float32))
    w = jnp.asarray(rng.randn(k, k, cin, cout).astype(np.float32) * 0.1)
    sc = jnp.asarray(rng.rand(cin).astype(np.float32) + 0.5)
    sh = jnp.asarray(rng.randn(cin).astype(np.float32))

    def run(use_pallas):
        return norm_conv(x, w, sc, sh, kernel=k, stride=s, pad=p, relu=relu,
                         prologue=prologue, stats=stats,
                         use_pallas=use_pallas, interpret=use_pallas)

    yp, sp_, qp = run(True)
    yr, sr_, qr = run(False)
    np.testing.assert_allclose(yp, yr, rtol=2e-5, atol=2e-5)
    if stats:
        np.testing.assert_allclose(sp_, sr_, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(qp, qr, rtol=2e-4, atol=2e-4)

    def loss(use_pallas):
        def f(x_, w_, sc_, sh_):
            y, su, sq = norm_conv(x_, w_, sc_, sh_, kernel=k, stride=s,
                                  pad=p, relu=relu, prologue=prologue,
                                  stats=stats, use_pallas=use_pallas,
                                  interpret=use_pallas)
            out = (y * y).sum().astype(jnp.float32)
            if stats:
                out = out + (su * 1.7).sum() + (sq * 0.3).sum()
            return out
        return f

    gp = jax.grad(loss(True), argnums=(0, 1, 2, 3))(x, w, sc, sh)
    gr = jax.grad(loss(False), argnums=(0, 1, 2, 3))(x, w, sc, sh)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-3)


def test_available_guard():
    # 1x1 matmul path: modest working set, always eligible at ResNet sizes
    assert norm_conv_available((8, 28, 28, 512), (1, 1, 512, 128),
                               (1, 1), (0, 0))
    # 3x3 pack path at a mid-size layer
    assert norm_conv_available((8, 28, 28, 128), (3, 3, 128, 128),
                               (1, 1), (1, 1))
    # stem: tiny Cin wastes the MXU -> XLA path
    assert not norm_conv_available((8, 224, 224, 3), (7, 7, 3, 64),
                                   (2, 2), (3, 3))
    # 5x5 kernels, groups, dilation -> XLA path
    assert not norm_conv_available((8, 28, 28, 64), (5, 5, 64, 64),
                                   (1, 1), (2, 2))
    assert not norm_conv_available((8, 28, 28, 64), (3, 3, 64, 64),
                                   (1, 1), (1, 1), num_group=2)
    assert not norm_conv_available((8, 28, 28, 64), (3, 3, 64, 64),
                                   (1, 1), (1, 1), dilate=(2, 2))
    # working set beyond the VMEM budget -> XLA path
    big = (1, 224, 224, 512)
    assert not norm_conv_available(big, (3, 3, 512, 512), (1, 1), (1, 1))
    assert NC_VMEM_BUDGET <= 16 * 1024 * 1024
    # the budget follows what compiled on the chip: ResNet-50's largest
    # site is admitted, the smallest estimate Mosaic refused is not
    assert norm_conv_available((8, 56, 56, 256), (1, 1, 256, 512),
                               (2, 2), (0, 0))
    assert not norm_conv_available((8, 112, 112, 64), (3, 3, 64, 128),
                                   (2, 2), (1, 1))


def _train_step(env, num_layers, image, batch=4, nclass=10, seed=0):
    for k, v in env.items():
        os.environ[k] = v
    try:
        from mxnet_tpu.models import resnet
        from mxnet_tpu.train import TrainStep
        net = resnet.get_symbol(num_classes=nclass, num_layers=num_layers,
                                image_shape="3,%d,%d" % (image, image))
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
        ts = TrainStep(net, opt)
        dshape = (batch, 3, image, image)
        params, state, aux = ts.init({"data": dshape},
                                     {"softmax_label": (batch,)})
        if jax.config.jax_enable_x64:
            params = {k2: v.astype(jnp.float64) for k2, v in params.items()}
            aux = {k2: v.astype(jnp.float64) for k2, v in aux.items()}
        rng = np.random.RandomState(seed)
        bd = {"data": jnp.asarray(
                  rng.uniform(-1, 1, dshape).astype(np.float64)
                  if jax.config.jax_enable_x64 else
                  rng.uniform(-1, 1, dshape).astype(np.float32)),
              "softmax_label": jnp.asarray(
                  rng.randint(0, nclass, (batch,)).astype(
                      np.float64 if jax.config.jax_enable_x64
                      else np.float32))}
        mxr.seed(seed)
        key = mxr.next_key()
        hyper = ts.fopt.hyper(0)
        p, s, a, outs = jax.jit(ts._step_fn)(params, state, aux, bd, key,
                                             hyper, np.int32(1))
        return p, a, outs
    finally:
        for k in env:
            os.environ.pop(k, None)


def test_graph_parity_f64_resnet50(f64):
    """Peephole on (XLA composition path) vs off: identical params and aux
    after one fused ResNet-50 train step — bottleneck blocks, shortcut
    convs sharing one BN, stats-from-epilogue chains, the non-fused 7x7
    stem and the final materialising BN are all in this graph."""
    p1, a1, _ = _train_step({"MXNET_NORM_CONV": "1"}, 50, 32)
    p0, a0, _ = _train_step({"MXNET_NORM_CONV": "0"}, 50, 32)
    for k in p0:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p0[k]),
                                   rtol=1e-9, atol=1e-9, err_msg=k)
    for k in a0:
        np.testing.assert_allclose(np.asarray(a1[k]), np.asarray(a0[k]),
                                   rtol=1e-9, atol=1e-9, err_msg=k)


def test_graph_parity_pallas_interpret_resnet20():
    """The Pallas kernel (interpret mode) under the full peephole vs the
    unfused graph, f32 tolerance."""
    pi, ai, _ = _train_step(
        {"MXNET_NORM_CONV": "1", "MXNET_PALLAS_CONV": "interpret"}, 20, 16)
    pr, ar, _ = _train_step({"MXNET_NORM_CONV": "0"}, 20, 16)
    for k in pr:
        a = np.asarray(pi[k], np.float64)
        b = np.asarray(pr[k], np.float64)
        denom = np.max(np.abs(b)) + 1e-6
        assert np.max(np.abs(a - b)) / denom < 2e-4, k


def test_eval_mode_parity_f64(f64):
    """Inference: prologue from moving stats, no stats epilogue."""
    from mxnet_tpu.models import resnet
    from mxnet_tpu.train import EvalStep
    net = resnet.get_symbol(num_classes=10, num_layers=50,
                            image_shape="3,32,32")
    rng = np.random.RandomState(3)

    from mxnet_tpu.train import TrainStep
    opt = mx.optimizer.SGD(learning_rate=0.1)
    params, _, aux = TrainStep(net, opt).init(
        {"data": (2, 3, 32, 32)}, {"softmax_label": (2,)})
    params = {k: v.astype(jnp.float64) for k, v in params.items()}
    aux = {k: (v.astype(jnp.float64) + 0.5) for k, v in aux.items()}
    bd = {"data": jnp.asarray(rng.uniform(-1, 1, (2, 3, 32, 32))),
          "softmax_label": jnp.zeros((2,), jnp.float64)}

    def run(on):
        os.environ["MXNET_NORM_CONV"] = "1" if on else "0"
        try:
            es = EvalStep(net)
            return es(params, aux, bd)
        finally:
            os.environ.pop("MXNET_NORM_CONV", None)

    o1 = run(True)
    o0 = run(False)
    np.testing.assert_allclose(np.asarray(o1[0]), np.asarray(o0[0]),
                               rtol=1e-9, atol=1e-9)
