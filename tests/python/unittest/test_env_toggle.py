"""An env var read while a program is traced must take effect AFTER a
prior compile.

The hazard (mxlint JIT001): an ``MXNET_*`` read inside a jit-traced body
freezes the first value seen into every cached program.  The contract that
prevents it is one row of ``base.TRACE_ENV_DEFAULTS``: every jit cache that
traces ``executor._Lowered.run`` keys on ``base.trace_env_key()``.  Pinned
here on the two lowering switches that are left, ``MXNET_CONV_LAYOUT`` and
``MXNET_STEM_FUSE`` (their other values are the reference lowerings the
parity tests compare against), through each such cache: one bound
executor's, ``TrainStep.run_steps``' chunk cache, and the fused fit's step.
And neither switch is a no-op: each selects a different lowered program.
"""
import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.base import TRACE_ENV_DEFAULTS, trace_env_key

# each switch with the value that is not its default
TOGGLES = [("MXNET_CONV_LAYOUT", "NCHW"), ("MXNET_STEM_FUSE", "0")]


def _stem_net():
    """The ResNet stem the peephole takes: bn_data -> conv0 (7x7/s2)."""
    return mx.sym.SoftmaxOutput(
        mx.sym.Flatten(mx.sym.Convolution(
            mx.sym.BatchNorm(mx.sym.Variable("data"), fix_gamma=True,
                             eps=2e-5, name="bn_data"),
            num_filter=4, kernel=(7, 7), stride=(2, 2), pad=(3, 3),
            no_bias=True, name="conv0")), name="softmax")


def _bound_stem():
    ex = _stem_net().simple_bind(
        mx.cpu(), data=(2, 3, 16, 16), softmax_label=(2,),
        grad_req={"data": "null", "softmax_label": "null",
                  "bn_data_gamma": "null", "bn_data_beta": "write",
                  "conv0_weight": "write"})
    rs = np.random.RandomState(0)
    ex.arg_dict["bn_data_gamma"][:] = np.ones(3, np.float32)
    ex.arg_dict["conv0_weight"][:] = \
        rs.randn(4, 3, 7, 7).astype(np.float32) * 0.1
    x = mx.nd.array(rs.rand(2, 3, 16, 16).astype(np.float32))
    y = mx.nd.array(np.array([1.0, 0.0], np.float32))

    def step():
        ex.forward(is_train=True, data=x, softmax_label=y)
        ex.backward()
        return (ex.outputs[0].asnumpy().copy(),
                ex.grad_dict["conv0_weight"].asnumpy().copy(),
                ex.grad_dict["bn_data_beta"].asnumpy().copy())
    return ex, step


def test_trace_key_holds_the_switches_and_the_monitor():
    assert [n for n, _ in TRACE_ENV_DEFAULTS] == \
        ["MXNET_CONV_LAYOUT", "MXNET_STEM_FUSE", "MXNET_MONITOR"]


@pytest.mark.parametrize("var,value", TOGGLES)
def test_toggle_retraces_bound_executor(var, value, monkeypatch):
    """Both values compute the same function, so 'takes effect' means: the
    one bound executor retraces under the new key, and back again it finds
    the first program."""
    monkeypatch.delenv(var, raising=False)
    ex, step = _bound_stem()
    out0, dw0, db0 = step()
    n_compiled = len(ex._jit_cache)
    step()
    assert len(ex._jit_cache) == n_compiled       # warm cache: no retrace

    monkeypatch.setenv(var, value)
    out1, dw1, db1 = step()
    assert len(ex._jit_cache) > n_compiled        # toggle keyed a retrace
    np.testing.assert_allclose(out1, out0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dw1, dw0, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(db1, db0, rtol=1e-4, atol=1e-5)

    n_both = len(ex._jit_cache)
    monkeypatch.delenv(var)
    step()
    assert len(ex._jit_cache) == n_both


def test_layout_toggle_retraces_run_steps_chunk(monkeypatch):
    """(test_sanitize.py toggles MXNET_STEM_FUSE through this cache and
    the next; the layout goes through them here, on a conv graph.)"""
    from mxnet_tpu.train import TrainStep
    var, value = TOGGLES[0]
    monkeypatch.delenv(var, raising=False)
    ts = TrainStep(_stem_net(), mx.optimizer.SGD(learning_rate=0.1))
    state = ts.init({"data": (2, 3, 16, 16)}, {"softmax_label": (2,)})
    rs = np.random.RandomState(0)
    batch = {"data": rs.rand(2, 3, 16, 16).astype(np.float32),
             "softmax_label": np.array([1.0, 0.0], np.float32)}

    def chunk(state):
        return ts.run_steps(*state, batch, num_steps=1)[:3]
    state = chunk(chunk(state))
    assert len(ts._multi_cache) == 1
    before = trace_env_key()
    monkeypatch.setenv(var, value)
    state = chunk(state)
    assert set(ts._multi_cache) == {(1, False, before),
                                    (1, False, trace_env_key())}
    assert all(np.isfinite(np.asarray(v)).all() for v in state[0].values())


def test_layout_toggle_rebuilds_fused_fit_step(monkeypatch):
    var, value = TOGGLES[0]
    monkeypatch.delenv(var, raising=False)
    rs = np.random.RandomState(0)
    x = rs.randn(8, 1, 16, 16).astype(np.float32)
    y = rs.randint(0, 4, 8).astype(np.float32)
    mod = mx.Module(models.get_lenet(num_classes=4))

    def fit():
        mod.fit(mx.io.NDArrayIter(x, y, batch_size=4), num_epoch=1,
                optimizer_params={"learning_rate": 0.01})
        return mod._fused_ts_cache[1]
    first = fit()
    assert fit() is first                 # same key: the step is kept
    monkeypatch.setenv(var, value)
    assert fit() is not first             # new key: a step traced anew


def _lowered_stem_grad():
    """The lowered text of the stem's forward and backward, as an executor
    traces it with ``data`` needing no gradient."""
    from mxnet_tpu.executor import _Lowered
    net = _stem_net()
    low = _Lowered(net)
    shapes, _, aux_shapes = net.infer_shape(data=(2, 3, 16, 16),
                                            softmax_label=(2,))
    args = {n: np.ones(s, np.float32)
            for n, s in zip(net.list_arguments(), shapes)}
    aux = {n: np.ones(s, np.float32)
           for n, s in zip(net.list_auxiliary_states(), aux_shapes)}
    learned = ("bn_data_beta", "conv0_weight")

    def loss(grad_args):
        outs, _ = low.run(dict(args, **grad_args), aux,
                          jax.random.PRNGKey(0), True,
                          no_grad_inputs=("data", "softmax_label"))
        return outs[0].sum()
    return jax.jit(jax.grad(loss)).lower(
        {n: args[n] for n in learned}).as_text()


def test_conv_layout_selects_a_different_program(monkeypatch):
    monkeypatch.delenv("MXNET_CONV_LAYOUT", raising=False)
    channel_last = _lowered_stem_grad()
    monkeypatch.setenv("MXNET_CONV_LAYOUT", "NCHW")
    logical = _lowered_stem_grad()
    assert "[b, 0, 1, f]x" in channel_last and "[b, f, 0, 1]x" in logical
    assert "[b, f, 0, 1]x" not in channel_last


def test_stem_fuse_selects_a_different_program(monkeypatch):
    """Fused, the backward holds the weight gradient's convolution alone;
    unfused it also convolves back into the 3-channel input grid."""
    monkeypatch.delenv("MXNET_STEM_FUSE", raising=False)
    fused = _lowered_stem_grad()
    monkeypatch.setenv("MXNET_STEM_FUSE", "0")
    unfused = _lowered_stem_grad()
    assert fused.count("stablehlo.convolution") == 2
    assert unfused.count("stablehlo.convolution") == 3
