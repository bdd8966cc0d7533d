"""Ring attention / sequence-context parallelism tests (SURVEY.md §5.7 —
NEW capability, no reference analogue: correctness = ring output ==
full-sequence attention on the virtual 8-device mesh, values and grads)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.parallel import mesh as mesh_mod
from mxnet_tpu.parallel.ring import (ring_attention, attention_reference,
                                     sequence_sharding)

RS = np.random.RandomState

needs_8dev = pytest.mark.skipif(jax.device_count() < 8,
                                reason="needs 8 virtual devices")


def _qkv(B=2, H=3, T=64, D=16, seed=0):
    rng = RS(seed)
    return (rng.randn(B, H, T, D).astype(np.float32),
            rng.randn(B, H, T, D).astype(np.float32),
            rng.randn(B, H, T, D).astype(np.float32))


@needs_8dev
@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_reference(causal):
    m = mesh_mod.make_mesh({"sp": 8})
    q, k, v = _qkv()
    sh = sequence_sharding(m)
    qd, kd, vd = (jax.device_put(x, sh) for x in (q, k, v))
    out = np.asarray(ring_attention(qd, kd, vd, m, causal=causal))
    ref = np.asarray(attention_reference(jnp.asarray(q), jnp.asarray(k),
                                         jnp.asarray(v), causal=causal))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


@needs_8dev
def test_ring_gradients_match():
    m = mesh_mod.make_mesh({"sp": 8})
    q, k, v = _qkv(seed=3)
    sh = sequence_sharding(m)
    qd, kd, vd = (jax.device_put(x, sh) for x in (q, k, v))

    def loss_ring(q, k, v):
        return (ring_attention(q, k, v, m, causal=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    # under jit, as every training step differentiates it (an eager
    # shard_map dispatches the ring's loop op by op: ~50 s for this test)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(qd, kd, vd)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    for gr, gf in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=3e-3, atol=3e-4)


@needs_8dev
def test_ring_under_jit():
    """ring_attention composes with jit (one compiled SPMD program)."""
    m = mesh_mod.make_mesh({"sp": 8})
    q, k, v = _qkv(T=32, seed=1)
    sh = sequence_sharding(m)
    qd, kd, vd = (jax.device_put(x, sh) for x in (q, k, v))
    fn = jax.jit(lambda a, b, c: ring_attention(a, b, c, m, causal=True))
    out = np.asarray(fn(qd, kd, vd))
    ref = np.asarray(attention_reference(jnp.asarray(q), jnp.asarray(k),
                                         jnp.asarray(v), causal=True))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_attention_op_single_device():
    """dot_product_attention symbol op == reference math (no mesh)."""
    q, k, v = _qkv(B=1, H=2, T=8, D=4, seed=2)
    qs, ks, vs = (mx.sym.Variable(n) for n in ("q", "k", "v"))
    net = mx.sym.dot_product_attention(qs, ks, vs, causal=True)
    ex = net.bind(mx.cpu(), {"q": mx.nd.array(q), "k": mx.nd.array(k),
                             "v": mx.nd.array(v)})
    out = ex.forward()[0].asnumpy()
    ref = np.asarray(attention_reference(jnp.asarray(q), jnp.asarray(k),
                                         jnp.asarray(v), causal=True))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_transformer_trains():
    """Decoder-only transformer LM overfits a tiny corpus via Module.fit."""
    from mxnet_tpu.models import transformer
    vocab, T, B = 30, 16, 4
    net = transformer.get_symbol(vocab_size=vocab, seq_len=T, num_layers=1,
                                 num_hidden=32, num_heads=4)
    rng = RS(0)
    # deterministic next-token structure: x[t+1] = (x[t] + 1) % vocab
    starts = rng.randint(0, vocab, (32, 1))
    seqs = (starts + np.arange(T + 1)) % vocab
    x, y = seqs[:, :-1].astype(np.float32), seqs[:, 1:].astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=B,
                           label_name="softmax_label")
    mod = mx.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=10, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.initializer.Xavier(magnitude=2.0),
            eval_metric=mx.metric.Perplexity(ignore_label=None))
    it.reset()
    score = mod.score(it, mx.metric.Perplexity(ignore_label=None))
    assert score[0][1] < 8.0, score  # vastly better than chance (=30)


@needs_8dev
def test_transformer_sequence_parallel_matches():
    """The SAME transformer graph runs ring-parallel when a sequence mesh is
    active, producing identical outputs (long-context scaling story)."""
    from mxnet_tpu.models import transformer
    vocab, T, B = 20, 32, 2
    net = transformer.get_symbol(vocab_size=vocab, seq_len=T, num_layers=1,
                                 num_hidden=16, num_heads=2)
    rng = RS(1)
    x = rng.randint(0, vocab, (B, T)).astype(np.float32)
    y = rng.randint(0, vocab, (B, T)).astype(np.float32)

    def forward():
        mx.random.seed(0)
        ex = net.simple_bind(mx.cpu(), data=(B, T), softmax_label=(B, T))
        ini = mx.initializer.Xavier()
        for n, arr in sorted(ex.arg_dict.items()):
            if n not in ("data", "softmax_label"):
                mx.random.seed(sum(map(ord, n)))
                ini(mx.initializer.InitDesc(n), arr)
        ex.arg_dict["data"][:] = x
        ex.arg_dict["softmax_label"][:] = y
        return ex.forward()[0].asnumpy().copy()

    out_plain = forward()
    m = mesh_mod.make_mesh({"sp": 8})
    mesh_mod.set_sequence_mesh(m)
    try:
        out_ring = forward()
    finally:
        mesh_mod.set_sequence_mesh(None)
    np.testing.assert_allclose(out_ring, out_plain, rtol=2e-4, atol=2e-5)


@needs_8dev
def test_sequence_parallel_training_matches():
    """TrainStep under an sp mesh (ring attention through vjp + optimizer)
    matches single-device training parameter-for-parameter."""
    from mxnet_tpu.models import transformer
    from mxnet_tpu.train import TrainStep
    vocab, T, B = 16, 32, 2
    net = transformer.get_symbol(vocab_size=vocab, seq_len=T, num_layers=1,
                                 num_hidden=16, num_heads=2)
    rng = RS(0)
    x = rng.randint(0, vocab, (B, T)).astype(np.float32)
    y = rng.randint(0, vocab, (B, T)).astype(np.float32)

    def train(steps=3):
        opt = mx.optimizer.SGD(learning_rate=0.1)
        ts = TrainStep(net, opt)
        params, state, aux = ts.init({"data": (B, T)},
                                     {"softmax_label": (B, T)}, seed=4)
        bd = ts.shard_batch({"data": x, "softmax_label": y})
        for _ in range(steps):
            params, state, aux, _ = ts(params, state, aux, bd)
        return {k: np.asarray(v) for k, v in params.items()}

    p_single = train()
    m = mesh_mod.make_mesh({"sp": 8})
    mesh_mod.set_sequence_mesh(m)
    try:
        p_ring = train()
    finally:
        mesh_mod.set_sequence_mesh(None)
    for k in p_single:
        np.testing.assert_allclose(p_ring[k], p_single[k], rtol=2e-4,
                                   atol=2e-5)
