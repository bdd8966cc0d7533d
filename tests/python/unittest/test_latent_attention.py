"""Attention whose value heads are narrower than its query/key heads (a
latent-attention layer's 128 beside 192): ``dot_product_attention`` against
the plain masked-softmax expression, the flash kernels in interpret mode at
both widths, their guard, and the ``L`` part of ``models/hybrid_lm.py``
(a key part shared by all heads) against the same expression."""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.ops import pallas_kernels as pk  # noqa: E402
from mxnet_tpu.ops.registry import get_op  # noqa: E402

HP = jax.lax.Precision.HIGHEST


def _plain(q, k, v, causal=True):
    """softmax(q k^T / sqrt(Dk) under the mask) v, the (T, T) scores in
    full."""
    s = jnp.einsum("bhtd,bhsd->bhts", q, k, precision=HP) \
        / math.sqrt(q.shape[-1])
    if causal:
        t = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, axis=-1), v,
                      precision=HP)


def _qkv(seed, b, h, t, dk, dv, dtype=jnp.float32):
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(r.randn(b, h, t, d), jnp.float32).astype(dtype)
                 for d in (dk, dk, dv))


def _gap(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return np.abs(got - want).max() / (np.abs(want).max() + 1e-30)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dk,dv", [(24, 16), (16, 24), (16, 16)])
def test_the_op_takes_value_heads_of_their_own_width(dk, dv, causal):
    q, k, v = _qkv(0, 2, 3, 40, dk, dv)
    got = get_op("dot_product_attention").fn(q, k, v, causal=causal)
    assert got.shape == (2, 3, 40, dv)
    assert _gap(got, _plain(q, k, v, causal)) < 1e-5
    net = mx.sym.dot_product_attention(
        mx.sym.Variable("q"), mx.sym.Variable("k"), mx.sym.Variable("v"),
        causal=causal, name="a")
    _, outs, _ = net.infer_shape(q=q.shape, k=k.shape, v=v.shape)
    assert outs == [(2, 3, 40, dv)]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dk,dv", [(48, 32), (32, 48), (192, 128)])
def test_the_flash_kernels_take_both_widths(dk, dv, causal):
    """Forward, dQ and dK/dV in interpret mode, value narrower and wider
    than the keys, and the new cell's own 192 / 128."""
    q, k, v = _qkv(1, 1, 2, 256, dk, dv)
    flash = lambda q, k, v: pk.flash_attention(  # noqa: E731
        q, k, v, causal, None, 128, 128, True)
    got = flash(q, k, v)
    assert got.shape == (1, 2, 256, dv)
    assert _gap(got, _plain(q, k, v, causal)) < 1e-5
    w = jnp.asarray(np.random.RandomState(2).randn(*got.shape), jnp.float32)
    grads = jax.grad(lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: (_plain(*a, causal) * w).sum(),
                    argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", grads, want):
        assert a.shape == b.shape and _gap(a, b) < 1e-4, name


def test_the_blocked_xla_backward_takes_both_widths_too():
    q, k, v = _qkv(3, 1, 2, 256, 48, 32)
    out, res = pk._flash_fwd(q, k, v, True, None, 128, 128, True)
    g = jnp.asarray(np.random.RandomState(4).randn(*out.shape), jnp.float32)
    got = pk._flash_bwd_xla(True, None, 128, 128, True, res, g)
    want = jax.vjp(lambda *a: _plain(*a), q, k, v)[1](g)
    for a, b in zip(got, want):
        assert _gap(a, b) < 1e-4


def test_the_guard_admits_a_value_width_and_refuses_other_unequal_shapes():
    shape = (1, 32, 4096, 192)
    assert pk.flash_available(shape, shape, (1, 32, 4096, 128))
    assert pk.flash_available(shape, shape, (1, 32, 4096, 256))
    assert pk.flash_available(shape, shape, shape) \
        == pk.flash_available(shape)
    assert not pk.flash_available(shape, (1, 32, 2048, 192), shape)
    assert not pk.flash_available(shape, shape, (1, 16, 4096, 128))
    assert not pk.flash_available(shape, shape, (1, 32, 4096, 100))
    assert not pk.flash_available(shape, shape, (1, 32, 4096, 512))
    # the blocks are planned at the wider of the two widths
    assert pk.flash_blocks(4096, 192, 2) == (512, 512)


def test_in_bfloat16_through_the_kernels():
    q, k, v = _qkv(5, 1, 2, 256, 48, 32, jnp.bfloat16)
    got = pk.flash_attention(q, k, v, True, None, 128, 128, True)
    assert got.dtype == jnp.bfloat16
    want = _plain(*(x.astype(jnp.float32) for x in (q, k, v)))
    assert _gap(got, want) < 2e-2


def test_the_latent_attention_part_shares_one_key_part_among_the_heads():
    """The ``L`` part of the model: q as heads of nope + rope; the latent
    normed; keys and values up from it; the rope channels of the key
    straight from the input and the same for every head."""
    from mxnet_tpu.models import hybrid_lm
    b, t, c, heads, rank, nope, rope, dv = 2, 24, 32, 4, 16, 8, 4, 6
    hp = dict(num_heads=heads, kv_lora_rank=rank, qk_nope_head_dim=nope,
              qk_rope_head_dim=rope, v_head_dim=dv, num_hidden=c, eps=1e-5)
    net = hybrid_lm._latent_attention(mx.sym.Variable("x"), "l", t, hp)
    names = net.list_arguments()
    assert names == ["x", "l_q_proj_weight", "l_kv_a_proj_weight",
                     "l_kv_a_norm_gamma", "l_kv_b_proj_weight",
                     "l_o_proj_weight"]
    shapes, outs, _ = net.infer_shape(x=(b * t, c))
    assert dict(zip(names, shapes)) == {
        "x": (b * t, c), "l_q_proj_weight": (heads * (nope + rope), c),
        "l_kv_a_proj_weight": (rank + rope, c), "l_kv_a_norm_gamma": (rank,),
        "l_kv_b_proj_weight": (heads * (nope + dv), rank),
        "l_o_proj_weight": (c, heads * dv)}
    assert outs == [(b * t, c)]
    r = np.random.RandomState(6)
    vals = {n: jnp.asarray(r.randn(*s) * 0.3, jnp.float32)
            for n, s in zip(names, shapes)}
    ex = net.bind(mx.cpu(), {n: mx.nd.array(np.asarray(v))
                             for n, v in vals.items()})
    got = ex.forward()[0].asnumpy()

    x = vals["x"]
    qq = jnp.dot(x, vals["l_q_proj_weight"].T, precision=HP).reshape(
        b, t, heads, nope + rope).transpose(0, 2, 1, 3)
    kv_a = jnp.dot(x, vals["l_kv_a_proj_weight"].T, precision=HP)
    latent = kv_a[:, :rank]
    latent = latent * jax.lax.rsqrt(
        (latent ** 2).mean(-1, keepdims=True) + 1e-5) \
        * vals["l_kv_a_norm_gamma"]
    kv = jnp.dot(latent, vals["l_kv_b_proj_weight"].T, precision=HP).reshape(
        b, t, heads, nope + dv).transpose(0, 2, 1, 3)
    shared = jnp.broadcast_to(kv_a[:, rank:].reshape(b, 1, t, rope),
                              (b, heads, t, rope))
    kk = jnp.concatenate([kv[..., :nope], shared], axis=-1)
    o = _plain(qq, kk, kv[..., nope:])
    want = jnp.dot(o.transpose(0, 2, 1, 3).reshape(b * t, heads * dv),
                   vals["l_o_proj_weight"].T, precision=HP)
    assert _gap(got, want) < 1e-5
