"""The gradient of ``Pooling(pool_type="max")``: ``reduce_window``'s own
(XLA's select-and-scatter) against a per-window numpy reference.

On inputs without ties each window's gradient goes to its one maximum, in
logical NCHW, through the executor's channel-last rule, and under
``pooling_convention="full"`` with its extra high padding.  In a tied
window the first maximal element (row-major) takes the whole gradient: the
one divergence from the reference's unpool, which gives it to every element
equal to the maximum (COVERAGE.md).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops.nn import _pool_out_dim
from mxnet_tpu.ops.registry import OPS


GEOMS = [
    # k, s, p
    ((3, 3), (2, 2), (1, 1)),
    ((2, 2), (2, 2), (0, 0)),
    ((3, 3), (1, 1), (1, 1)),
    ((3, 3), (3, 3), (1, 1)),
]


def _head_grad(shape):
    # a weight of its own for each output position, so a gradient sent to
    # the wrong window shows
    return 1.0 + np.arange(np.prod(shape), dtype=np.float32).reshape(shape)


def _reference_grad(x, k, s, p, convention="valid"):
    """Each window's head gradient added at the window's first maximum."""
    n, c, h, w = x.shape
    oh = _pool_out_dim(h, k[0], s[0], p[0], convention)
    ow = _pool_out_dim(w, k[1], s[1], p[1], convention)
    dy = _head_grad((n, c, oh, ow))
    # room for the low padding, the input, and whatever the last window
    # reaches beyond it
    padded = np.full((n, c, max((oh - 1) * s[0] + k[0], p[0] + h),
                      max((ow - 1) * s[1] + k[1], p[1] + w)),
                     -np.inf, np.float32)
    padded[:, :, p[0]:p[0] + h, p[1]:p[1] + w] = x
    dx = np.zeros_like(padded)
    for i in range(oh):
        for j in range(ow):
            win = padded[:, :, i * s[0]:i * s[0] + k[0],
                         j * s[1]:j * s[1] + k[1]].reshape(n, c, -1)
            a, b = np.divmod(win.argmax(-1), k[1])
            for nn in range(n):
                for cc in range(c):
                    dx[nn, cc, i * s[0] + a[nn, cc],
                       j * s[1] + b[nn, cc]] += dy[nn, cc, i, j]
    return dx[:, :, p[0]:p[0] + h, p[1]:p[1] + w]


def _op_grad(x, k, s, p, convention="valid"):
    call = OPS.get("Pooling").make_callable(
        {"kernel": k, "stride": s, "pad": p, "pool_type": "max",
         "pooling_convention": convention}, True)

    def loss(xx):
        out = call(xx)
        return jnp.sum(out * _head_grad(out.shape))
    return np.asarray(jax.grad(loss)(jnp.asarray(x)))


def _tie_free(shape, seed=0):
    # a permutation: no two elements are equal, so no window has a tie
    rs = np.random.RandomState(seed)
    return rs.permutation(int(np.prod(shape))).astype(np.float32) \
        .reshape(shape)


@pytest.mark.parametrize("geom", GEOMS)
def test_max_pool_grad_matches_reference_no_ties(geom):
    x = _tie_free((2, 3, 12, 11))
    np.testing.assert_array_equal(_op_grad(x, *geom),
                                  _reference_grad(x, *geom))


def test_max_pool_grad_full_convention():
    """8 -> ceil((8 - 3) / 2) + 1 = 4 windows where "valid" has 3: the
    last one reaches past the input, over the extra high padding."""
    x = _tie_free((3, 2, 8, 8), seed=1)
    geom = ((3, 3), (2, 2), (0, 0))
    got = _op_grad(x, *geom, convention="full")
    assert _pool_out_dim(8, 3, 2, 0, "full") == 4 \
        and _pool_out_dim(8, 3, 2, 0, "valid") == 3
    np.testing.assert_array_equal(got, _reference_grad(x, *geom, "full"))


def test_max_pool_grad_through_nhwc_rule(monkeypatch):
    """Bound in an executor, the op runs channel-last between two
    transposes (the default layout pass); the gradient comes back in the
    logical layout."""
    monkeypatch.delenv("MXNET_CONV_LAYOUT", raising=False)
    k, s, p = GEOMS[0]
    x = _tie_free((2, 3, 12, 11), seed=2)
    net = mx.sym.Pooling(mx.sym.Variable("data"), kernel=k, stride=s, pad=p,
                         pool_type="max")
    ex = net.simple_bind(mx.cpu(), data=x.shape, grad_req="write")
    ex.forward(is_train=True, data=mx.nd.array(x))
    ex.backward(mx.nd.array(_head_grad(ex.outputs[0].shape)))
    np.testing.assert_array_equal(ex.grad_dict["data"].asnumpy(),
                                  _reference_grad(x, k, s, p))


def test_max_pool_grad_tie_goes_to_first_maximum():
    x = np.zeros((1, 1, 2, 2), np.float32)      # one window, all four tied
    got = _op_grad(x, (2, 2), (2, 2), (0, 0))
    np.testing.assert_array_equal(got, [[[[1.0, 0.0], [0.0, 0.0]]]])
    np.testing.assert_array_equal(
        got, _reference_grad(x, (2, 2), (2, 2), (0, 0)))
