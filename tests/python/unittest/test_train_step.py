"""TrainStep (fused SPMD training core) tests: single-step vs Module parity
is covered indirectly by the optimizer suite; here the multi-step fused loop
(lax.scan) must match sequential stepping exactly."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.train import TrainStep, EvalStep

RS = np.random.RandomState


def _net():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _batch(ts, batch=8, dim=10):
    rng = RS(0)
    return ts.shard_batch({
        "data": rng.rand(batch, dim).astype(np.float32),
        "softmax_label": rng.randint(0, 4, batch).astype(np.float32)})


def test_run_steps_matches_sequential():
    net = _net()

    def make():
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
        ts = TrainStep(net, opt)
        params, state, aux = ts.init({"data": (8, 10)},
                                     {"softmax_label": (8,)}, seed=1)
        return ts, params, state, aux

    ts1, p1, s1, a1 = make()
    bd = _batch(ts1)
    # 4 fused steps (scan of 3 + 1 emitting)
    p1, s1, a1, outs1 = ts1.run_steps(p1, s1, a1, bd, 3)

    ts2, p2, s2, a2 = make()
    for _ in range(4):
        p2, s2, a2, outs2 = ts2(p2, s2, a2, bd)

    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(outs1[0]), np.asarray(outs2[0]),
                               rtol=1e-5, atol=1e-6)


def test_run_steps_matches_sequential_adam():
    """Adam bias correction must advance per fused step (traced t), not
    freeze at the chunk start."""
    net = _net()

    def make():
        opt = mx.optimizer.Adam(learning_rate=0.01)
        ts = TrainStep(net, opt)
        params, state, aux = ts.init({"data": (8, 10)},
                                     {"softmax_label": (8,)}, seed=1)
        return ts, params, state, aux

    ts1, p1, s1, a1 = make()
    bd = _batch(ts1)
    p1, s1, a1, outs1 = ts1.run_steps(p1, s1, a1, bd, 3)

    ts2, p2, s2, a2 = make()
    for _ in range(4):
        p2, s2, a2, outs2 = ts2(p2, s2, a2, bd)

    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=1e-5, atol=1e-6)


def test_run_steps_stacked_batches():
    """stacked=True consumes one minibatch per step (minibatch-SGD
    semantics) and matches sequential stepping over the same batches."""
    net = _net()
    rng = RS(3)
    xs = rng.rand(4, 8, 10).astype(np.float32)
    ys = rng.randint(0, 4, (4, 8)).astype(np.float32)

    def make():
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
        ts = TrainStep(net, opt)
        params, state, aux = ts.init({"data": (8, 10)},
                                     {"softmax_label": (8,)}, seed=2)
        return ts, params, state, aux

    ts1, p1, s1, a1 = make()
    stacked = {"data": xs, "softmax_label": ys}
    p1, s1, a1, _ = ts1.run_steps(p1, s1, a1, stacked, 3, stacked=True)

    ts2, p2, s2, a2 = make()
    for i in range(4):
        bd = ts2.shard_batch({"data": xs[i], "softmax_label": ys[i]})
        p2, s2, a2, _ = ts2(p2, s2, a2, bd)

    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=1e-5, atol=1e-6)


def test_run_steps_trains():
    # rescale_grad=1/batch (the Module.fit convention): the loss heads
    # accumulate PER-SAMPLE gradients, so the raw sum over 16 samples at
    # lr=0.2/momentum=0.9 is an effective step ~32x too large — weights
    # blow past 1e12 and the run oscillates at ~0.56 accuracy.  Sequential
    # stepping diverges identically (the fused loop is faithful; verified
    # while re-pinning), so the old assertion pinned divergent
    # hyper-parameters, not a run_steps regression.
    net = _net()
    opt = mx.optimizer.SGD(learning_rate=0.2, momentum=0.9,
                           rescale_grad=1.0 / 16)
    ts = TrainStep(net, opt)
    params, state, aux = ts.init({"data": (16, 10)},
                                 {"softmax_label": (16,)}, seed=0)
    rng = RS(0)
    centers = rng.randn(4, 10).astype(np.float32) * 2
    y = rng.randint(0, 4, 16)
    x = (centers[y] + 0.1 * rng.randn(16, 10)).astype(np.float32)
    bd = ts.shard_batch({"data": x,
                         "softmax_label": y.astype(np.float32)})
    params, state, aux, outs0 = ts(params, state, aux, bd)
    params, state, aux, outs = ts.run_steps(params, state, aux, bd, 30)
    pred = np.asarray(outs[0]).argmax(axis=1)
    assert (pred == y).mean() == 1.0, "fused loop failed to overfit"


def test_eval_step():
    net = _net()
    opt = mx.optimizer.SGD(learning_rate=0.1)
    ts = TrainStep(net, opt)
    params, _, aux = ts.init({"data": (4, 10)}, {"softmax_label": (4,)})
    ev = EvalStep(net)
    bd = _batch(ts, batch=4)
    outs = ev(params, aux, bd)
    assert np.asarray(outs[0]).shape == (4, 4)
