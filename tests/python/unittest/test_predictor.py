"""Python Predictor tests (parity model: reference c_predict_api semantics —
forward-only bind from saved symbol+params, missing-arg zero fill, blob and
checkpoint loading paths)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.predictor import Predictor

RS = np.random.RandomState


def _checkpoint(tmp_path, num_classes=4, dim=16):
    rng = RS(0)
    centers = rng.randn(num_classes, dim) * 3
    y = rng.randint(0, num_classes, 150)
    x = (centers[y] + rng.randn(150, dim)).astype(np.float32)
    it = mx.io.NDArrayIter(x, y.astype(np.float32), batch_size=25)
    mod = mx.Module(models.get_mlp(num_classes=num_classes),
                    context=mx.cpu())
    mod.fit(it, num_epoch=10,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    prefix = str(tmp_path / "model")
    mod.save_checkpoint(prefix, 4)
    return prefix, mod, x, y


def test_predictor_matches_module(tmp_path):
    prefix, mod, x, y = _checkpoint(tmp_path)
    batch = 10
    pred = Predictor.from_checkpoint(prefix, 4, {"data": (batch, 16)})
    pred.set_input("data", x[:batch])
    pred.forward()
    out = pred.get_output(0)
    assert pred.get_output_shape(0) == (batch, 4)

    it = mx.io.NDArrayIter(x[:batch], y[:batch].astype(np.float32),
                           batch_size=batch)
    want = mod.predict(it).asnumpy()
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    # trained model should classify the separable blobs correctly
    assert (out.argmax(axis=1) == y[:batch]).mean() > 0.8

    # over several batches predict() must own each batch's rows before the
    # next forward overwrites the executor's output buffers
    it = mx.io.NDArrayIter(x[:3 * batch], y[:3 * batch].astype(np.float32),
                           batch_size=batch)
    many = mod.predict(it).asnumpy()
    for i in range(3):
        pred.forward(data=x[i * batch:(i + 1) * batch])
        np.testing.assert_allclose(many[i * batch:(i + 1) * batch],
                                   pred.get_output(0), rtol=1e-5, atol=1e-6)


def test_predictor_from_blob_bytes(tmp_path):
    prefix, _, x, _ = _checkpoint(tmp_path)
    with open(prefix + "-symbol.json") as f:
        sym_json = f.read()
    with open(prefix + "-0004.params", "rb") as f:
        blob = f.read()
    pred = Predictor(sym_json, blob, {"data": (5, 16)})
    pred.set_input("data", x[:5])
    pred.forward()
    assert pred.get_output(0).shape == (5, 4)
    assert pred.num_outputs == 1


def test_set_input_stages_at_bound_dtype():
    """satellite fix: set_input must stage at the BOUND arg's dtype — the
    old forced float32 host cast silently rounded int values above 2^24
    (and would up/down-cast any non-f32 binding)."""
    data = mx.sym.Variable("data")
    net = mx.sym.Cast(data, dtype="int32")
    pred = Predictor(net, {}, {"data": (2, 3)},
                     input_types={"data": np.int32})
    assert pred._executor.arg_dict["data"].dtype == np.int32
    big = 2 ** 24 + 1   # not representable in float32
    vals = np.array([[big, 1, 2], [3, 4, big + 2]], dtype=np.int64)
    pred.set_input("data", vals)
    pred.forward()
    out = pred.get_output(0)
    assert out.dtype == np.int32
    np.testing.assert_array_equal(out, vals.astype(np.int32))


def test_forward_kwargs_batched_staging():
    """forward(**inputs) stages every given input (at its bound dtype)
    and runs in one call — the serving batcher's staging path."""
    data = mx.sym.Variable("data")
    net = mx.sym.Cast(data, dtype="int32")
    pred = Predictor(net, {}, {"data": (1, 2)},
                     input_types={"data": np.int32})
    pred.forward(data=np.array([[2 ** 24 + 1, 5]], dtype=np.int64))
    np.testing.assert_array_equal(pred.get_output(0),
                                  [[2 ** 24 + 1, 5]])
    with pytest.raises(mx.MXNetError, match="unknown input"):
        pred.forward(bogus=np.zeros((1, 2)))


def test_predictor_bf16_input_binding():
    """input_types binds a non-f32 input; f32 values stage down to the
    binding's dtype instead of widening the binding to f32."""
    import jax.numpy as jnp
    data = mx.sym.Variable("data")
    net = mx.sym.Cast(data, dtype="float32")
    pred = Predictor(net, {}, {"data": (2, 4)},
                     input_types={"data": jnp.bfloat16})
    arr = pred._executor.arg_dict["data"]
    assert str(arr.dtype) == "bfloat16"
    x = RS(0).randn(2, 4).astype(np.float32)
    pred.set_input("data", x)
    assert str(arr.dtype) == "bfloat16"   # staging kept the binding dtype
    pred.forward()
    np.testing.assert_array_equal(
        pred.get_output(0), x.astype(jnp.bfloat16).astype(np.float32))


def test_predictor_input_types_rejects_non_inputs():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=2, name="fc")
    with pytest.raises(mx.MXNetError, match="input_types"):
        Predictor(net, {}, {"data": (1, 3)},
                  input_types={"fc_weight": np.int32})


def test_from_checkpoint_partial_out(tmp_path):
    """satellite fix: from_checkpoint forwards output_names, so the
    MXPredCreatePartialOut feature-extraction binding works straight from
    checkpoint files."""
    prefix, _, x, _ = _checkpoint(tmp_path)
    feat = Predictor.from_checkpoint(prefix, 4, {"data": (5, 16)},
                                     output_names=["fc1"])
    feat.set_input("data", x[:5])
    feat.forward()
    out = feat.get_output(0)
    assert out.shape == (5, 128)   # fc1 hidden width, not the 4-way head

    # identical to the direct partial-out constructor path
    with open(prefix + "-symbol.json") as f:
        sym_json = f.read()
    with open(prefix + "-0004.params", "rb") as f:
        blob = f.read()
    direct = Predictor(sym_json, blob, {"data": (5, 16)},
                       output_names=["fc1"])
    direct.set_input("data", x[:5])
    direct.forward()
    np.testing.assert_array_equal(out, direct.get_output(0))


def test_predictor_batchnorm_aux(tmp_path):
    """Aux states (BatchNorm moving stats) ride the params blob."""
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=4, kernel=(3, 3), name="c1")
    net = mx.sym.BatchNorm(net, name="bn")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    x = RS(1).rand(40, 1, 8, 8).astype(np.float32)
    y = RS(2).randint(0, 2, 40).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=10)
    mod = mx.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer_params={"learning_rate": 0.1})
    prefix = str(tmp_path / "bnmodel")
    mod.save_checkpoint(prefix, 2)
    pred = Predictor.from_checkpoint(prefix, 2, {"data": (10, 1, 8, 8)})
    pred.set_input("data", x[:10])
    pred.forward()
    it2 = mx.io.NDArrayIter(x[:10], y[:10], batch_size=10)
    want = mod.predict(it2).asnumpy()
    np.testing.assert_allclose(pred.get_output(0), want, rtol=1e-4,
                               atol=1e-5)
