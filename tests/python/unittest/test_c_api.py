"""Native C API + cpp-package tests (parity model: the reference's C API is
exercised implicitly by every frontend; here we drive libmxnet_tpu.so
directly via ctypes and run the cpp-package example binary end to end)."""
import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))
BUILD = os.path.join(REPO, "build")
LIB = os.path.join(BUILD, "libmxnet_tpu.so")
EXAMPLE = os.path.join(BUILD, "mlp_predict")


@pytest.fixture(scope="module")
def libmx():
    # always configure + build: build/ is ignored by git, so a library found
    # there may come from another tree; ninja is incremental, an up-to-date
    # build costs a fraction of a second
    subprocess.run(["cmake", "-S", REPO, "-B", BUILD, "-G", "Ninja",
                    "-DCMAKE_BUILD_TYPE=Release"], check=True,
                   capture_output=True)
    subprocess.run(["ninja", "-C", BUILD], check=True, capture_output=True)
    lib = ctypes.CDLL(LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p
    assert lib.MXTPULibInit() == 0, "library init failed"
    return lib


def _check(lib, rc):
    assert rc == 0, lib.MXGetLastError().decode()


def test_ndarray_roundtrip(libmx):
    shape = (ctypes.c_uint * 2)(3, 4)
    handle = ctypes.c_void_p()
    _check(libmx, libmx.MXNDArrayCreate(shape, 2, 1, 0, 0,
                                        ctypes.byref(handle)))
    data = np.arange(12, dtype=np.float32)
    _check(libmx, libmx.MXNDArraySyncCopyFromCPU(
        handle, data.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(12)))
    out = np.zeros(12, dtype=np.float32)
    _check(libmx, libmx.MXNDArraySyncCopyToCPU(
        handle, out.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(12)))
    np.testing.assert_array_equal(out, data)

    ndim = ctypes.c_uint()
    pdata = ctypes.POINTER(ctypes.c_uint)()
    _check(libmx, libmx.MXNDArrayGetShape(handle, ctypes.byref(ndim),
                                          ctypes.byref(pdata)))
    assert ndim.value == 2 and pdata[0] == 3 and pdata[1] == 4
    _check(libmx, libmx.MXNDArrayFree(handle))


def test_ndarray_create_none_kvstore_pull(libmx):
    """MXNDArrayCreateNone (parity: reference c_api.h:195-201): the handle
    starts ndim == 0 and a kvstore pull fills it in — the reference's
    deferred-output calling pattern."""
    none_h = ctypes.c_void_p()
    _check(libmx, libmx.MXNDArrayCreateNone(ctypes.byref(none_h)))
    ndim = ctypes.c_uint(7)
    pdata = ctypes.POINTER(ctypes.c_uint)()
    _check(libmx, libmx.MXNDArrayGetShape(none_h, ctypes.byref(ndim),
                                          ctypes.byref(pdata)))
    assert ndim.value == 0

    kv = ctypes.c_void_p()
    _check(libmx, libmx.MXKVStoreCreate(b"local", ctypes.byref(kv)))
    shape = (ctypes.c_uint * 1)(4)
    src = ctypes.c_void_p()
    _check(libmx, libmx.MXNDArrayCreate(shape, 1, 1, 0, 0,
                                        ctypes.byref(src)))
    data = np.arange(4, dtype=np.float32)
    _check(libmx, libmx.MXNDArraySyncCopyFromCPU(
        src, data.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(4)))
    key = (ctypes.c_int * 1)(3)
    _check(libmx, libmx.MXKVStoreInit(kv, 1, key,
                                      (ctypes.c_void_p * 1)(src)))
    _check(libmx, libmx.MXKVStorePull(kv, 1, key,
                                      (ctypes.c_void_p * 1)(none_h), 0))
    _check(libmx, libmx.MXNDArrayGetShape(none_h, ctypes.byref(ndim),
                                          ctypes.byref(pdata)))
    assert ndim.value == 1 and pdata[0] == 4
    out = np.zeros(4, dtype=np.float32)
    _check(libmx, libmx.MXNDArraySyncCopyToCPU(
        none_h, out.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(4)))
    np.testing.assert_array_equal(out, data)
    _check(libmx, libmx.MXNDArrayFree(none_h))
    _check(libmx, libmx.MXNDArrayFree(src))
    _check(libmx, libmx.MXKVStoreFree(kv))


def test_ndarray_save_load(libmx, tmp_path):
    fname = str(tmp_path / "arrs.params").encode()
    shape = (ctypes.c_uint * 1)(5)
    h = ctypes.c_void_p()
    _check(libmx, libmx.MXNDArrayCreate(shape, 1, 1, 0, 0, ctypes.byref(h)))
    vals = np.array([1, 2, 3, 4, 5], np.float32)
    _check(libmx, libmx.MXNDArraySyncCopyFromCPU(
        h, vals.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(5)))
    handles = (ctypes.c_void_p * 1)(h)
    keys = (ctypes.c_char_p * 1)(b"w")
    _check(libmx, libmx.MXNDArraySave(fname, 1, handles, keys))

    out_size = ctypes.c_uint()
    out_arr = ctypes.POINTER(ctypes.c_void_p)()
    name_size = ctypes.c_uint()
    names = ctypes.POINTER(ctypes.c_char_p)()
    _check(libmx, libmx.MXNDArrayLoad(fname, ctypes.byref(out_size),
                                      ctypes.byref(out_arr),
                                      ctypes.byref(name_size),
                                      ctypes.byref(names)))
    assert out_size.value == 1 and name_size.value == 1
    assert names[0] == b"w"
    got = np.zeros(5, np.float32)
    _check(libmx, libmx.MXNDArraySyncCopyToCPU(
        ctypes.c_void_p(out_arr[0]), got.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_size_t(5)))
    np.testing.assert_array_equal(got, vals)


def test_list_ops_and_symbol_json(libmx):
    n = ctypes.c_uint()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    _check(libmx, libmx.MXListAllOpNames(ctypes.byref(n), ctypes.byref(arr)))
    ops = {arr[i].decode() for i in range(n.value)}
    assert n.value > 200
    assert {"FullyConnected", "Convolution",
            "dot_product_attention"} <= ops

    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                name="fc")
    json_str = net.tojson().encode()
    h = ctypes.c_void_p()
    _check(libmx, libmx.MXSymbolCreateFromJSON(json_str, ctypes.byref(h)))
    ns = ctypes.c_uint()
    sarr = ctypes.POINTER(ctypes.c_char_p)()
    _check(libmx, libmx.MXSymbolListArguments(h, ctypes.byref(ns),
                                              ctypes.byref(sarr)))
    args = [sarr[i].decode() for i in range(ns.value)]
    assert args == ["data", "fc_weight", "fc_bias"]
    out_json = ctypes.c_char_p()
    _check(libmx, libmx.MXSymbolSaveToJSON(h, ctypes.byref(out_json)))
    assert b"fc_weight" in out_json.value
    _check(libmx, libmx.MXSymbolFree(h))


def test_error_reporting(libmx):
    h = ctypes.c_void_p()
    rc = libmx.MXSymbolCreateFromJSON(b"{not json", ctypes.byref(h))
    assert rc == -1
    assert len(libmx.MXGetLastError()) > 0


def _train_tiny_mlp(prefix):
    rng = np.random.RandomState(0)
    centers = rng.randn(4, 32) * 3
    y = rng.randint(0, 4, 200)
    x = (centers[y] + rng.randn(200, 32)).astype(np.float32)
    it = mx.io.NDArrayIter(x, y.astype(np.float32), batch_size=25)
    from mxnet_tpu import models
    mod = mx.Module(models.get_mlp(num_classes=4), context=mx.cpu())
    mod.fit(it, num_epoch=10,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    mod.save_checkpoint(prefix, 4)
    return mod


def test_c_predict_api(libmx, tmp_path):
    prefix = str(tmp_path / "mlp")
    mod = _train_tiny_mlp(prefix)

    with open(prefix + "-symbol.json", "rb") as f:
        sym_json = f.read()
    with open(prefix + "-0004.params", "rb") as f:
        params = f.read()
    batch, dim = 3, 32
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint * 2)(0, 2)
    shapes = (ctypes.c_uint * 2)(batch, dim)
    pred = ctypes.c_void_p()
    _check(libmx, libmx.MXPredCreate(
        sym_json, params, len(params), 1, 0, 1, keys, indptr, shapes,
        ctypes.byref(pred)))

    x = np.linspace(-1, 1, batch * dim).astype(np.float32)
    _check(libmx, libmx.MXPredSetInput(
        pred, b"data", x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_uint(x.size)))
    _check(libmx, libmx.MXPredForward(pred))
    sd = ctypes.POINTER(ctypes.c_uint)()
    nd_ = ctypes.c_uint()
    _check(libmx, libmx.MXPredGetOutputShape(pred, 0, ctypes.byref(sd),
                                             ctypes.byref(nd_)))
    shape = tuple(sd[i] for i in range(nd_.value))
    assert shape == (batch, 4)
    out = np.zeros(batch * 4, np.float32)
    _check(libmx, libmx.MXPredGetOutput(
        pred, 0, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_uint(out.size)))
    _check(libmx, libmx.MXPredFree(pred))

    # must match the Python predictor numerically
    from mxnet_tpu.predictor import Predictor
    py_pred = Predictor.from_checkpoint(prefix, 4,
                                        {"data": (batch, dim)})
    py_pred.set_input("data", x.reshape(batch, dim))
    py_pred.forward()
    np.testing.assert_allclose(out.reshape(batch, 4),
                               py_pred.get_output(0), rtol=1e-5)


def test_cpp_example_binary(libmx, tmp_path):
    """The cpp-package example runs standalone (its own embedded runtime)."""
    if not os.path.exists(EXAMPLE):
        pytest.skip("example binary not built")
    prefix = str(tmp_path / "mlp")
    _train_tiny_mlp(prefix)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    res = subprocess.run([EXAMPLE, prefix, "4", "3", "32"],
                         capture_output=True, text=True, env=env,
                         timeout=240)
    assert res.returncode == 0, res.stderr
    assert "output shape: (3, 4)" in res.stdout
    assert res.stdout.count("argmax") == 3
    # the partial-out feature-extraction path through the .so
    assert "FEATURES OK" in res.stdout
    assert "feature shape: (3, 128)" in res.stdout


def test_cpp_train_binary(libmx):
    """The cpp-package TRAINING example (VERDICT r2 #3): generated op.h
    symbol composition + Executor + SGDOptimizer + KVStore-updater training
    loop through libmxnet_tpu.so, converging to >95% accuracy."""
    binary = os.path.join(BUILD, "mlp_train")
    if not os.path.exists(binary):
        pytest.skip("mlp_train binary not built")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    res = subprocess.run([binary], capture_output=True, text=True, env=env,
                         timeout=300)
    assert res.returncode == 0, res.stderr + res.stdout
    assert "PASS" in res.stdout


def test_op_h_generator(libmx, tmp_path):
    """op.h regenerates from the registry and covers the op surface."""
    gen = os.path.join(BUILD, "op_h_generator")
    if not os.path.exists(gen):
        pytest.skip("generator not built")
    out = str(tmp_path / "op.h")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    res = subprocess.run([gen, out], capture_output=True, text=True, env=env,
                         timeout=240)
    assert res.returncode == 0, res.stderr
    text = open(out).read()
    for op in ("FullyConnected", "Convolution", "BatchNorm", "Pooling",
               "SoftmaxOutput", "Concat", "Activation", "Dropout",
               "Embedding", "RNN"):
        assert ("Symbol %s(" % op) in text, op


def test_recordio_c_api(libmx, tmp_path):
    """MXRecordIO* round-trip through the native boundary (parity:
    reference c_api.h:1379-1437)."""
    uri = str(tmp_path / "data.rec").encode()
    w = ctypes.c_void_p()
    _check(libmx, libmx.MXRecordIOWriterCreate(uri, ctypes.byref(w)))
    payloads = [b"alpha", b"bravo" * 100, b"charlie"]
    for p in payloads:
        _check(libmx, libmx.MXRecordIOWriterWriteRecord(
            w, p, ctypes.c_size_t(len(p))))
    pos = ctypes.c_size_t()
    _check(libmx, libmx.MXRecordIOWriterTell(w, ctypes.byref(pos)))
    assert pos.value > 0
    _check(libmx, libmx.MXRecordIOWriterFree(w))

    r = ctypes.c_void_p()
    _check(libmx, libmx.MXRecordIOReaderCreate(uri, ctypes.byref(r)))
    got = []
    while True:
        buf = ctypes.c_char_p()
        size = ctypes.c_size_t()
        _check(libmx, libmx.MXRecordIOReaderReadRecord(
            r, ctypes.byref(buf), ctypes.byref(size)))
        if size.value == 0:
            break
        got.append(ctypes.string_at(buf, size.value))
    assert got == payloads
    _check(libmx, libmx.MXRecordIOReaderFree(r))


def test_c_predict_partial_out_and_ndlist(libmx, tmp_path):
    """MXPredCreatePartialOut binds up to a named hidden layer;
    MXPredPartialForward counts the step protocol down; MXNDList* reads an
    in-memory .params blob (the mean-image loader)."""
    prefix = str(tmp_path / "mlp")
    _train_tiny_mlp(prefix)
    with open(prefix + "-symbol.json", "rb") as f:
        sym_json = f.read()
    with open(prefix + "-0004.params", "rb") as f:
        params = f.read()
    batch, dim = 3, 32
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint * 2)(0, 2)
    shapes = (ctypes.c_uint * 2)(batch, dim)
    outs = (ctypes.c_char_p * 1)(b"fc1")
    pred = ctypes.c_void_p()
    _check(libmx, libmx.MXPredCreatePartialOut(
        sym_json, params, len(params), 1, 0, 1, keys, indptr, shapes,
        1, outs, ctypes.byref(pred)))
    x = np.linspace(-1, 1, batch * dim).astype(np.float32)
    _check(libmx, libmx.MXPredSetInput(
        pred, b"data", x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_uint(x.size)))
    step, left = 0, ctypes.c_int(1)
    while left.value > 0:
        step += 1
        _check(libmx, libmx.MXPredPartialForward(pred, step,
                                                 ctypes.byref(left)))
    assert step > 1   # the protocol actually counted nodes down
    sd = ctypes.POINTER(ctypes.c_uint)()
    nd_ = ctypes.c_uint()
    _check(libmx, libmx.MXPredGetOutputShape(pred, 0, ctypes.byref(sd),
                                             ctypes.byref(nd_)))
    shape = tuple(sd[i] for i in range(nd_.value))
    assert shape == (batch, 128)
    feat = np.zeros(batch * 128, np.float32)
    _check(libmx, libmx.MXPredGetOutput(
        pred, 0, feat.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_uint(feat.size)))
    _check(libmx, libmx.MXPredFree(pred))
    # hidden layer must match the python-side internals binding
    from mxnet_tpu.predictor import Predictor
    py_pred = Predictor(sym_json.decode(), params, {"data": (batch, dim)},
                        output_names=["fc1"])
    py_pred.set_input("data", x.reshape(batch, dim))
    py_pred.forward()
    np.testing.assert_allclose(feat.reshape(batch, 128),
                               py_pred.get_output(0), rtol=1e-5)

    # ---- NDList over the params blob itself
    lst = ctypes.c_void_p()
    length = ctypes.c_uint()
    _check(libmx, libmx.MXNDListCreate(params, len(params),
                                       ctypes.byref(lst),
                                       ctypes.byref(length)))
    assert length.value >= 6   # fc1-3 weight+bias
    key = ctypes.c_char_p()
    data_p = ctypes.POINTER(ctypes.c_float)()
    shape_p = ctypes.POINTER(ctypes.c_uint)()
    ndim = ctypes.c_uint()
    found = {}
    for i in range(length.value):
        _check(libmx, libmx.MXNDListGet(lst, i, ctypes.byref(key),
                                        ctypes.byref(data_p),
                                        ctypes.byref(shape_p),
                                        ctypes.byref(ndim)))
        shp = tuple(shape_p[j] for j in range(ndim.value))
        n = int(np.prod(shp))
        found[key.value.decode()] = np.ctypeslib.as_array(
            data_p, shape=(n,)).reshape(shp).copy()
    assert any(k.endswith("fc1_weight") for k in found)
    wkey = [k for k in found if k.endswith("fc1_weight")][0]
    assert found[wkey].shape == (128, 32)
    _check(libmx, libmx.MXNDListFree(lst))


def test_cpp_resnet_train_binary(libmx, tmp_path):
    """A convolutional residual network with BatchNorm aux states trains
    through the .so (parity: reference cpp-package/example/resnet.cpp):
    generated op.h BatchNorm + operator+ junctions + projection shortcut
    + global pooling, aux arrays threaded through MXExecutorBind."""
    binary = os.path.join(BUILD, "resnet_train")
    if not os.path.exists(binary):
        pytest.skip("resnet_train binary not built")
    rng = np.random.RandomState(0)
    n, h = 256, 12
    y = rng.randint(0, 2, n)
    x = rng.randn(n, 1, h, h).astype(np.float32) * 0.4
    x[y == 1, 0, 3:9, 3:9] += 1.5
    data_csv = tmp_path / "d.csv"
    label_csv = tmp_path / "l.csv"
    np.savetxt(data_csv, x.reshape(n, -1), delimiter=",", fmt="%.5f")
    np.savetxt(label_csv, y.astype(np.float32), delimiter=",", fmt="%g")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    res = subprocess.run([binary, str(data_csv), str(label_csv), "32", "8"],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "PASS" in res.stdout


def test_cpp_charrnn_train_binary(libmx, tmp_path):
    """A character LSTM trains through the .so (parity: reference
    cpp-package/example/charRNN.cpp): generated op.h Embedding + fused-
    parameter RNN + SwapAxis/Reshape sequence plumbing, with the hidden/
    cell state threaded as no-grad executor inputs."""
    binary = os.path.join(BUILD, "charrnn_train")
    if not os.path.exists(binary):
        pytest.skip("charrnn_train binary not built")
    rs = np.random.RandomState(0)
    pattern = np.array([3, 7, 1, 9, 4, 2, 8, 5])
    n, seq = 256, 16
    xs, ys = [], []
    for _ in range(n):
        phase = rs.randint(0, len(pattern))
        ids = pattern[(phase + np.arange(seq + 1)) % len(pattern)]
        xs.append(ids[:seq])
        ys.append(ids[1:])
    data_csv = tmp_path / "d.csv"
    label_csv = tmp_path / "l.csv"
    np.savetxt(data_csv, np.array(xs, np.float32), delimiter=",", fmt="%g")
    np.savetxt(label_csv, np.array(ys, np.float32), delimiter=",", fmt="%g")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    res = subprocess.run([binary, str(data_csv), str(label_csv), "16", "6"],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "PASS" in res.stdout


def test_cpp_lenet_train_binary(libmx, tmp_path):
    """The round-4 cpp-package surfaces (DataIter/CSVIter, Xavier
    initializer, Accuracy metric) train LeNet end to end through the .so
    (parity: reference cpp-package lenet example)."""
    binary = os.path.join(BUILD, "lenet_train")
    if not os.path.exists(binary):
        pytest.skip("lenet_train binary not built")
    rng = np.random.RandomState(0)
    n, h = 256, 12
    y = rng.randint(0, 2, n)
    x = rng.randn(n, 1, h, h).astype(np.float32) * 0.4
    x[y == 1, 0, 3:9, 3:9] += 1.5
    data_csv = tmp_path / "d.csv"
    label_csv = tmp_path / "l.csv"
    np.savetxt(data_csv, x.reshape(n, -1), delimiter=",", fmt="%.5f")
    np.savetxt(label_csv, y.astype(np.float32), delimiter=",", fmt="%g")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    res = subprocess.run([binary, str(data_csv), str(label_csv), "32", "8"],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "PASS" in res.stdout
