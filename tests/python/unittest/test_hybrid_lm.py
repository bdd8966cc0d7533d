"""The hybrid language model (``models/hybrid_lm.py``) against the plain
reference at a small size: grouped-query attention, the whole model's loss
and every leaf's gradient through ``TrainStep``, several steps through
``run_steps`` under the bfloat16 policy, and ``Module.fit`` on the same
symbol."""
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
from mxnet_tpu import amp, telemetry  # noqa: E402
from mxnet_tpu.models import hybrid_lm  # noqa: E402
from mxnet_tpu.ops.registry import get_op  # noqa: E402
from mxnet_tpu.parallel.ring import attention_reference  # noqa: E402
from mxnet_tpu.train import TrainStep  # noqa: E402
from benchmark import gen  # noqa: E402
from benchmark.reference import hybrid_lm as ref  # noqa: E402
from benchmark.reference.train import Exact  # noqa: E402


# every kind of layer, T = 40 over chunks of 16 (the last one padded), 4 of
# 8 experts held from expert 2 on
ARGS = dict(pattern="MEM*E", vocab_size=64, seq_len=40, num_hidden=32,
            ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=8,
            conv_kernel=4, chunk_size=16, num_heads=4, num_kv_heads=2,
            head_dim=8, num_experts=8, experts_held=4, first_expert=2,
            experts_per_token=2, expert_hidden=16, shared_hidden=32,
            routed_scale=2.5, eps=1e-5)
CFG = {"hidden_size": 32, "vocab_size": 64, "hybrid_override_pattern": "MEM*E",
       "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
       "ssm_state_size": 8, "conv_kernel": 4, "head_dim": 8,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "published": {"n_routed_experts": 8}, "n_routed_experts": 4,
       "moe_intermediate_size": 16, "moe_shared_expert_intermediate_size": 32,
       "n_shared_experts": 1, "num_experts_per_tok": 2,
       "routed_scaling_factor": 2.5, "norm_eps": 1e-5,
       "deployment": {"first_expert": 2}, "max_position_embeddings": 40}
B, T = 2, 40


def _weights(seed):
    """Seeded weights by the harness's rules, then the scan's leaves put
    where the published initialisation has them (dt 0.001-0.1, A 1-16), so
    that the carried state takes part."""
    w = dict(gen.make_weights(ref.param_shapes(CFG),
                              {"matrix_std": 0.2, "beta_bias_std": 0.05},
                              seed))
    r = np.random.RandomState(seed)
    for k in w:
        if k.endswith("_A_log"):
            w[k] = jnp.asarray(np.log(r.uniform(1, 16, w[k].shape)),
                               jnp.float32)
        if k.endswith("_dt_bias"):
            dt = np.exp(r.uniform(np.log(1e-3), np.log(0.1), w[k].shape))
            w[k] = jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32)
    return w


def _copy(w):
    """The step programs donate what they are given."""
    return {k: jnp.copy(v) for k, v in w.items()}


def _tokens(seed, steps):
    data, label = gen.device_tokens(seed, steps, B, T, 64)
    return data, label


@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 1), (4, 4)])
@pytest.mark.parametrize("causal", [True, False])
def test_grouped_query_attention_is_attention_with_repeated_heads(hq, hkv,
                                                                   causal):
    r = np.random.RandomState(hq + hkv)
    q = jnp.asarray(r.randn(2, hq, 12, 8), jnp.float32)
    k = jnp.asarray(r.randn(2, hkv, 12, 8), jnp.float32)
    v = jnp.asarray(r.randn(2, hkv, 12, 8), jnp.float32)
    fn = get_op("dot_product_attention").fn

    def rep(x):
        return jnp.repeat(x, hq // hkv, axis=1)
    want = attention_reference(q, rep(k), rep(v), causal=causal)
    np.testing.assert_allclose(fn(q, k, v, causal=causal), want, atol=1e-6)
    # the gradient of a key/value head is the sum over its query heads
    g = jax.grad(lambda k_: fn(q, k_, v, causal=causal).sum())(k)
    g_rep = jax.grad(lambda k_: attention_reference(
        q, k_, rep(v), causal=causal).sum())(rep(k))
    np.testing.assert_allclose(
        g, g_rep.reshape(2, hkv, hq // hkv, 12, 8).sum(axis=2), atol=1e-5)


def test_equal_heads_take_the_path_they_took_and_odd_counts_are_refused():
    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(1, 4, 8, 8), jnp.float32)
               for _ in range(3))
    fn = get_op("dot_product_attention").fn
    text = str(jax.make_jaxpr(lambda *a: fn(*a, causal=True))(q, k, v))
    same = str(jax.make_jaxpr(lambda *a: attention_reference(
        *a, causal=True))(q, k, v))
    assert text == same                       # no repeat, nothing added
    with pytest.raises(ValueError):
        fn(q, k[:, :3], v[:, :3])
    from mxnet_tpu.ops import pallas_kernels
    assert pallas_kernels.flash_available((1, 32, 4096, 128),
                                          (1, 32, 4096, 128),
                                          (1, 32, 4096, 128))
    assert not pallas_kernels.flash_available((1, 32, 4096, 128),
                                              (1, 2, 4096, 128),
                                              (1, 2, 4096, 128))


def test_the_symbol_names_the_references_leaves_and_shapes():
    net = hybrid_lm.get_symbol(**ARGS)
    shapes, outs, _ = net.infer_shape(data=(B, T), softmax_label=(B, T))
    got = {k: v for k, v in zip(net.list_arguments(), shapes)
           if k not in ("data", "softmax_label")}
    assert got == {k: tuple(v) for k, v in ref.param_shapes(CFG).items()}
    assert outs == [(B * T, 64)]
    assert net.list_outputs() == ["softmax_output"]
    with pytest.raises(ValueError):
        hybrid_lm.get_symbol(pattern="MX")
    with pytest.raises(TypeError):              # the depth is the pattern
        hybrid_lm.get_symbol(num_layers=2)


def test_loss_and_every_gradient_against_the_reference():
    """One SGD step of rate 1 through TrainStep: the change of every leaf is
    minus its gradient.  float32 on both sides; 1e-4 of each leaf's largest
    entry (the scan's two algorithms differ by 2e-4 of the largest value,
    test_ssm.py) and never under 1e-6."""
    net = hybrid_lm.get_symbol(**ARGS)
    opt = mx.optimizer.create("sgd", learning_rate=1.0,
                              rescale_grad=1.0 / (B * T))
    ts = TrainStep(net, opt)
    w = _weights(3)
    data, label = _tokens(3, 1)
    slots = ts.fopt.init_state({k: np.zeros(1, np.float32) for k in w})
    state = {k: tuple(jnp.zeros_like(w[k]) for _ in v)
             for k, v in slots.items()}
    new, _, _, outs = ts(_copy(w), state, {}, {"data": data[0],
                                               "softmax_label": label[0]})
    loss, grads = jax.value_and_grad(ref.mean_loss)(w, data[0], label[0],
                                                    CFG, Exact())
    probs = np.asarray(outs[0])
    picked = probs[np.arange(B * T), np.asarray(label[0], np.int32).ravel()]
    np.testing.assert_allclose(-np.log(picked).mean(), float(loss), rtol=1e-5)
    for k in sorted(w):
        want = np.asarray(grads[k])
        got = np.asarray(w[k]) - np.asarray(new[k])
        np.testing.assert_allclose(
            got, want, atol=max(1e-4 * np.abs(want).max(), 1e-6), rtol=0,
            err_msg=k)
    assert not np.asarray(grads["layer1_router_bias"]).any()
    assert np.abs(np.asarray(grads["layer0_A_log"])).max() > 0


def test_run_steps_under_the_bfloat16_policy_follows_the_reference():
    """Three steps of Adam in one scan chunk, bfloat16 compute with float32
    islands, against the float32 reference's three steps: Adam's first
    moment of every matrix within 8% in norm (read 0.2-5.5%: bfloat16
    operands, 2**-8 a rounding, a few dozen roundings deep, and at 80 tokens
    a routed choice that flips moves an expert's leaf whole).  A router's
    weight gets 50% (read 6% and 29%): the normalised weights of a token sum
    to a constant, so its gradient is a difference of nearly equal terms.
    And the counters of the chunk."""
    from benchmark.reference import train as ref_train
    cfg = dict(CFG, family="hybrid_lm", optimizer={
        "name": "adam", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0})
    net = hybrid_lm.get_symbol(**ARGS)
    opt = mx.optimizer.create("adam", learning_rate=1e-3, beta1=0.9,
                              beta2=0.95, epsilon=1e-8,
                              rescale_grad=1.0 / (B * T))
    ts = TrainStep(net, opt, policy=amp.Policy("bfloat16"))
    assert set(ts.param_names) == set(ref.param_shapes(CFG))
    w = _weights(5)
    data, label = _tokens(5, 3)
    state = {k: (jnp.zeros_like(v), jnp.zeros_like(v)) for k, v in w.items()}
    new, state, _, outs = ts.run_steps(
        _copy(w), state, {}, {"data": data, "softmax_label": label}, 2,
        stacked=True)
    assert outs[0].shape == (B * T, 64) and outs[0].dtype == jnp.float32
    want = ref_train.follow(cfg, lambda: _copy(w), [
        (data[i], label[i]) for i in range(3)])
    got = ref_train.leaf_norms({k: v[0] for k, v in state.items()})
    for k, norm in want["moment"].items():
        if len(w[k].shape) > 1:
            room = 0.5 if k.endswith("_router_weight") else 0.08
            assert abs(float(got[k]) - norm) <= room * norm, k
    counted, steps = telemetry.device_counters()
    assert steps == 3 and counted["moe"].shape == (2, 4)      # two E layers
    assert (counted["moe"][:, 0] + counted["moe"][:, 2]
            == 3 * B * T * 2).all() and not counted["moe"][:, 3].any()
    # the float32 islands: these leaves reach their ops uncast
    assert ts._low.f32_leaves() == {
        k for k in w if k.endswith(("_A_log", "_D_gamma", "_dt_bias",
                                    "_router_weight", "_router_bias"))}


def test_module_fit_binds_and_trains_the_same_symbol():
    net = hybrid_lm.get_symbol(**ARGS)
    r = np.random.RandomState(0)
    x = r.randint(0, 64, (8, T)).astype(np.float32)
    y = np.roll(x, -1, axis=1)
    it = mx.io.NDArrayIter(x, y, batch_size=B)
    mod = mx.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer="adam",
            optimizer_params={"learning_rate": 3e-3},
            initializer=mx.init.Normal(0.1), eval_metric="ce")
    args, _ = mod.get_params()
    assert set(args) == set(ref.param_shapes(CFG))
    a = np.exp(args["layer0_A_log"].asnumpy())
    assert a.min() >= 0.9 and a.max() <= 17       # its own initialiser
    assert not args["layer1_router_bias"].asnumpy().any()
    it.reset()
    score = dict(mod.score(it, "ce"))
    assert np.isfinite(score["cross-entropy"]) and score[
        "cross-entropy"] < np.log(64) + 0.5


def test_a_second_chunk_does_not_compile_the_program_again():
    """The loss-scale state starts uncommitted, as the caller's jit-made
    parameters are: committed, it committed the first call's outputs, and
    the second call lowered and compiled the whole chunk program again for
    committed inputs (on the chip, 84 s of every run's set-up and a second
    98 MB entry in a compile cache capped at 192 MiB)."""
    net = hybrid_lm.get_symbol(**dict(ARGS, pattern="M"))
    opt = mx.optimizer.create("adam", learning_rate=1e-3,
                              rescale_grad=1.0 / (B * T))
    ts = TrainStep(net, opt, policy=amp.Policy("bfloat16"))
    shapes = {k: v for k, v in ref.param_shapes(
        dict(CFG, hybrid_override_pattern="M")).items()}
    w = gen.make_weights(shapes, {"matrix_std": 0.1, "beta_bias_std": 0.02}, 1)
    state = jax.jit(lambda p: {k: (jnp.zeros_like(v), jnp.zeros_like(v))
                               for k, v in p.items()})(w)
    data, label = _tokens(1, 6)
    p, s, a = w, state, {}
    for c in range(3):
        p, s, a, _ = ts.run_steps(p, s, a, {
            "data": data[2 * c:2 * c + 2],
            "softmax_label": label[2 * c:2 * c + 2]}, 1, stacked=True)
    (program,) = ts._multi_cache.values()
    assert program._cache_size() == 1


# ------------------------------------- two-part layers: K, L, D, gated E
from benchmark.reference import kda_lm as kda_ref  # noqa: E402

# three layers of a mixer and a feed-forward (KDA + dense, KDA + experts, MLA
# + experts), T = 40 over rule chunks of 16 (the last one ragged), 4 of 8
# gated experts held from expert 2 on, value heads narrower than key heads
KARGS = dict(pattern="KDKELE", vocab_size=64, seq_len=40, num_hidden=32,
             kda_heads=4, kda_head_dim=8, kda_chunk=16, conv_kernel=4,
             num_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
             qk_rope_head_dim=4, v_head_dim=6, mlp_hidden=48, mlp_act="silu",
             mlp_gated=True, num_experts=8, experts_held=4, first_expert=2,
             experts_per_token=2, expert_hidden=16, shared_hidden=16,
             routed_scale=2.446, eps=1e-5)
KCFG = {"hidden_size": 32, "vocab_size": 64, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "intermediate_size": 48,
        "linear_attn_config": {"full_attn_layers": [3], "kda_layers": [1, 2],
                               "head_dim": 8, "num_heads": 4,
                               "short_conv_kernel_size": 4},
        "num_attention_heads": 4, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 4, "v_head_dim": 6,
        "published": {"num_experts": 8}, "num_experts": 4,
        "moe_intermediate_size": 16, "num_shared_experts": 1,
        "num_experts_per_token": 2, "routed_scaling_factor": 2.446,
        "rms_norm_eps": 1e-5, "deployment": {"first_expert": 2},
        "max_position_embeddings": 40}


def _kweights(seed):
    """As ``_weights``: the rule's leaves where the published initialisation
    has them, so that the state carried between chunks takes part."""
    w = dict(gen.make_weights(kda_ref.param_shapes(KCFG),
                              {"matrix_std": 0.2, "beta_bias_std": 0.05},
                              seed))
    r = np.random.RandomState(seed)
    for k in w:
        if k.endswith("_A_log"):
            w[k] = jnp.asarray(np.log(r.uniform(1, 16, w[k].shape)),
                               jnp.float32)
        if k.endswith("_dt_bias"):
            dt = np.exp(r.uniform(np.log(1e-3), np.log(0.1), w[k].shape))
            w[k] = jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32)
    return w


def test_the_new_letters_name_the_references_leaves_and_shapes():
    assert kda_ref.parts(KCFG) == KARGS["pattern"]
    net = hybrid_lm.get_symbol(**KARGS)
    shapes, outs, _ = net.infer_shape(data=(B, T), softmax_label=(B, T))
    got = {k: v for k, v in zip(net.list_arguments(), shapes)
           if k not in ("data", "softmax_label")}
    assert got == {k: tuple(v) for k, v in kda_ref.param_shapes(KCFG).items()}
    assert outs == [(B * T, 64)]
    # a letter is a part on a pre-norm of its own
    assert {k for k in got if k.endswith("_norm_gamma") and k.count("_") == 2
            and k.startswith("layer")} == {
        "layer%d_norm_gamma" % i for i in range(len(KARGS["pattern"]))}
    assert {"layer0_q_conv_weight", "layer0_A_log", "layer0_dt_bias",
            "layer0_o_norm_gamma", "layer1_mlp_gate_weight",
            "layer3_experts_gate_weight", "layer3_shared_gate_weight",
            "layer4_kv_a_proj_weight", "layer4_kv_a_norm_gamma"} <= set(got)
    assert got["layer0_dt_bias"] == (32,) and got["layer0_A_log"] == (4,)
    assert got["layer4_kv_a_proj_weight"] == (16 + 4, 32)
    assert not any(k.endswith("_conv_bias") for k in got)


def test_the_older_patterns_build_the_graph_they_built():
    """The one-part letters M, E and * with their defaults: the same
    leaves, no gate, the convolution's bias, squared-ReLU experts."""
    net = hybrid_lm.get_symbol(**ARGS)
    names = [k for k in net.list_arguments()
             if k not in ("data", "softmax_label")]
    assert set(names) == set(ref.param_shapes(CFG))
    assert not any("_gate_" in k or "_mlp_" in k for k in names)
    assert "layer0_conv_bias" in names
    nodes = {n["name"]: n
             for n in __import__("json").loads(net.tojson())["nodes"]}
    experts, conv = nodes["layer1_experts"], nodes["layer0_conv"]
    assert experts["param"]["act_type"] == "relu2"
    assert experts["param"]["gated"] == "False" \
        and len(experts["inputs"]) == 5
    assert conv["param"]["no_bias"] == "False" and len(conv["inputs"]) == 3


def test_the_two_part_layers_loss_and_every_gradient_against_the_reference():
    """One SGD step of rate 1 through TrainStep, float32 on both sides:
    the chunked rule against the token-by-token recurrence, the latent
    attention, the gated MLP and experts; 2e-4 of each leaf's largest
    entry."""
    net = hybrid_lm.get_symbol(**KARGS)
    opt = mx.optimizer.create("sgd", learning_rate=1.0,
                              rescale_grad=1.0 / (B * T))
    ts = TrainStep(net, opt)
    w = _kweights(3)
    data, label = _tokens(3, 1)
    slots = ts.fopt.init_state({k: np.zeros(1, np.float32) for k in w})
    state = {k: tuple(jnp.zeros_like(w[k]) for _ in v)
             for k, v in slots.items()}
    new, _, _, outs = ts(_copy(w), state, {}, {"data": data[0],
                                               "softmax_label": label[0]})
    loss, grads = jax.value_and_grad(kda_ref.mean_loss)(
        w, data[0], label[0], KCFG, Exact())
    probs = np.asarray(outs[0])
    picked = probs[np.arange(B * T), np.asarray(label[0], np.int32).ravel()]
    np.testing.assert_allclose(-np.log(picked).mean(), float(loss), rtol=1e-5)
    for k in sorted(w):
        want = np.asarray(grads[k])
        got = np.asarray(w[k]) - np.asarray(new[k])
        np.testing.assert_allclose(
            got, want, atol=max(2e-4 * np.abs(want).max(), 1e-6), rtol=0,
            err_msg=k)
    assert not np.asarray(grads["layer3_router_bias"]).any()
    for leaf in ("layer0_A_log", "layer0_dt_bias", "layer2_b_proj_weight",
                 "layer4_kv_a_norm_gamma", "layer3_experts_gate_weight"):
        assert np.abs(np.asarray(grads[leaf])).max() > 0, leaf


def test_the_two_part_layers_through_run_steps_under_bfloat16():
    """Three steps of Adam in one scan chunk under the bfloat16 policy:
    finite, the counters of the two expert layers, and the float32
    islands."""
    net = hybrid_lm.get_symbol(**KARGS)
    opt = mx.optimizer.create("adam", learning_rate=1e-3, beta1=0.9,
                              beta2=0.95, epsilon=1e-8,
                              rescale_grad=1.0 / (B * T))
    ts = TrainStep(net, opt, policy=amp.Policy("bfloat16"))
    w = _kweights(5)
    data, label = _tokens(5, 3)
    state = {k: (jnp.zeros_like(v), jnp.zeros_like(v)) for k, v in w.items()}
    new, state, _, outs = ts.run_steps(
        _copy(w), state, {}, {"data": data, "softmax_label": label}, 2,
        stacked=True)
    assert outs[0].shape == (B * T, 64)
    assert all(bool(jnp.isfinite(v).all()) for v in new.values())
    counted, steps = telemetry.device_counters()
    assert steps == 3 and counted["moe"].shape == (2, 4)
    assert (counted["moe"][:, 0] + counted["moe"][:, 2]
            == 3 * B * T * 2).all() and not counted["moe"][:, 3].any()
    assert ts._low.f32_leaves() == {
        k for k in w if k.endswith(("_A_log", "_dt_bias", "_router_weight",
                                    "_router_bias"))}
