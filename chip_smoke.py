#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls, on ResNet-50 (1000 classes, 3x224x224, batch 32, the bf16 AMP policy;
random weights and data from a seed):

  fit        ``mx.Module(net, context=mx.tpu(0)).fit`` over an ``NDArrayIter``
             for 24 batches, the fused step engaged (asserted per batch —
             the general executor loop would only log its fallback), loss
             finite, parameters changed;
  run_steps  ``TrainStep.run_steps`` for two 41-step scan chunks (the program
             ``bench.py`` times), outputs finite, parameters changed;
  serve      ``save_checkpoint`` from the fit -> ``serving.Server`` on the
             chip answering 48 concurrent single-image requests whose rows
             agree with ``Module.predict``;
  dp         only with more than one chip: one ``TrainStep`` update over a
             ``dp`` mesh of all chips against the same update on one chip.

Every phase asserts that what it made — parameters, optimizer state, aux,
outputs — lives on devices of the expected platform.  ``__main__`` expects
``tpu`` unconditionally: on any other platform it exits 1 before the first
phase, and nothing (no flag, no environment variable) relaxes that.  The
last line of stdout is ``{"ok": true, "device": {...}}``; any failed check
raises, so a failed phase can never end in exit code 0.

Tests import :func:`run` and drive the same phases at a tiny size on the
CPU harness, naming ``cpu`` as the platform they expect.
"""
import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

# the full-size configuration __main__ runs; tests shrink it
FULL = dict(num_layers=50, image=224, classes=1000, batch=32,
            fit_batches=8, fit_epochs=3, chunk=40, requests=48,
            serve_max_batch=8, seed=0)

# softmax rows from two f32 forwards of the same weights at different batch
# sizes (serving buckets vs Module.predict).  The TPU multiplies f32 convs in
# bf16 passes, so rows agree to bf16 resolution of a probability, not to f32
# round-off.
SERVE_RTOL = 5e-2
SERVE_ATOL = 1e-4
# one dp-mesh update against the same update on one device: bf16 compute,
# different reduction order across shards
DP_RTOL = 5e-2
DP_ATOL = 2e-3


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, msg, *args):
    if not cond:
        raise SmokeFailure(msg % args if args else msg)


def log(msg, *args):
    print("[chip_smoke] " + (msg % args if args else msg), flush=True)


def describe_device():
    """Print what jax runs on, first of all.  Returns the result stamp."""
    import jax
    import jaxlib
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:   # a CPU-only installation
        libtpu = None
    devs = jax.devices()
    stamp = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    log("platform=%s device_kind=%r count=%d  jax=%s jaxlib=%s libtpu=%s  "
        "JAX_PLATFORMS=%r", stamp["platform"], stamp["kind"], stamp["count"],
        jax.__version__, jaxlib.__version__, libtpu,
        os.environ.get("JAX_PLATFORMS"))
    return stamp


def on_platform(tree, platform, what):
    """Every array under ``tree`` lives on ``platform`` devices only.
    Returns the set of devices seen."""
    import jax
    from mxnet_tpu.ndarray import NDArray
    leaves = [l.value if isinstance(l, NDArray) else l
              for l in jax.tree_util.tree_leaves(
                  tree, is_leaf=lambda l: isinstance(l, NDArray))]
    check(leaves, "%s: nothing to check", what)
    seen = set()
    for leaf in leaves:
        check(isinstance(leaf, jax.Array), "%s: %r is not a device array",
              what, type(leaf))
        seen |= set(leaf.devices())
    wrong = sorted(str(d) for d in seen if d.platform != platform)
    check(not wrong, "%s: expected platform %r, found arrays on %s", what,
          platform, wrong)
    return seen


def all_finite(x, what):
    x = np.asarray(x, np.float32)
    check(np.isfinite(x).all(), "%s: non-finite values", what)
    return x


def make_net(cfg):
    from mxnet_tpu.models import resnet
    return resnet.get_symbol(num_classes=cfg["classes"],
                             num_layers=cfg["num_layers"],
                             image_shape="3,%d,%d" % (cfg["image"],
                                                      cfg["image"]))


def synthetic(cfg, n, seed_offset=0):
    rng = np.random.RandomState(cfg["seed"] + seed_offset)
    x = rng.uniform(-1, 1, (n, 3, cfg["image"], cfg["image"])) \
        .astype(np.float32)
    y = rng.randint(0, cfg["classes"], (n,)).astype(np.float32)
    return x, y


# ---------------------------------------------------------------- phase: fit
def phase_fit(cfg, platform):
    """Module.fit on mx.tpu(0), fused path asserted.  Returns the module."""
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    mx.random.seed(cfg["seed"])
    batch = cfg["batch"]
    x, y = synthetic(cfg, batch * cfg["fit_batches"])
    it = mx.io.NDArrayIter(x, y, batch_size=batch)
    mod = mx.Module(make_net(cfg), context=mx.tpu(0))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.init.Xavier(magnitude=2.0))
    before = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    seen = {"batches": 0, "devices": set()}

    def each_batch(param):
        # BatchEndParam.locals is the fit loop's frame (reference parity):
        # `fast` is the fused engine, None on the general executor path
        fast = param.locals["fast"]
        check(fast is not None and mod._active_fused is fast,
              "fit batch %d ran on the general (executor) path, not the "
              "fused step", param.nbatch)
        if seen["batches"] == 0:
            seen["devices"] |= on_platform(
                (fast._params, fast._state, fast._aux,
                 fast._ts._scale_state, param.locals["outputs"]),
                platform, "fit params/optimizer state/aux/loss scale/outputs")
        seen["batches"] += 1

    metric = mx.metric.create("ce")
    t0 = time.time()
    mod.fit(it, num_epoch=cfg["fit_epochs"], eval_metric=metric,
            optimizer="sgd",
            # lr 0.01: at 0.05 the 24 steps on random labels leave a net
            # whose inference-mode (moving-statistics) activations grow to
            # 1e4 and whose softmax is the same one-hot row for every input
            # — nothing the serve phase could tell apart (chip run, PR 21)
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9,
                              "wd": 1e-4},
            policy=amp.Policy("bfloat16"),
            batch_end_callback=each_batch)
    want = cfg["fit_batches"] * cfg["fit_epochs"]
    check(seen["batches"] == want, "fit ran %d batches, expected %d",
          seen["batches"], want)
    loss = metric.get()[1]
    check(np.isfinite(loss), "fit: cross-entropy is %r", loss)
    arg, aux = mod.get_params()
    moved = sum(1 for k, v in arg.items()
                if not np.array_equal(all_finite(v.asnumpy(), "param " + k),
                                      before[k]))
    check(moved == len(arg), "fit: only %d of %d parameters changed", moved,
          len(arg))
    for k, v in aux.items():
        all_finite(v.asnumpy(), "aux " + k)
    log("fit: %d fused batches on %s, cross-entropy %.4f, %d/%d params "
        "changed, %.1fs", want, sorted(str(d) for d in seen["devices"]),
        loss, moved, len(arg), time.time() - t0)
    return mod


# ---------------------------------------------------------- phase: run_steps
def phase_run_steps(cfg, platform):
    """TrainStep.run_steps, two chunks: the program bench.py times."""
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu.train import TrainStep
    batch, image, chunk = cfg["batch"], cfg["image"], cfg["chunk"]
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                           rescale_grad=1.0 / batch, wd=1e-4)
    ts = TrainStep(make_net(cfg), opt, policy=amp.Policy("bfloat16"))
    params, state, aux = ts.init({"data": (batch, 3, image, image)},
                                 {"softmax_label": (batch,)},
                                 seed=cfg["seed"])
    on_platform((params, state, aux), platform, "run_steps initial state")
    x, y = synthetic(cfg, batch, seed_offset=1)
    dev_batch = ts.shard_batch({"data": x, "softmax_label": y})
    on_platform(dev_batch, platform, "run_steps batch")
    name = ts.param_names[-1]
    snaps = [np.asarray(params[name])]
    t0 = time.time()
    for _ in range(2):
        params, state, aux, outs = ts.run_steps(params, state, aux,
                                                dev_batch, chunk)
        snaps.append(np.asarray(params[name]))
    on_platform((params, state, aux, outs, ts._scale_state), platform,
                "run_steps params/optimizer state/aux/outputs/loss scale")
    probs = all_finite(outs[0], "run_steps outputs")
    check(probs.shape == (batch, cfg["classes"]),
          "run_steps output shape %s", probs.shape)
    check(np.allclose(probs.sum(axis=1), 1.0, atol=1e-2),
          "run_steps softmax rows do not sum to 1")
    check(ts.num_update == 2 * (chunk + 1), "run_steps advanced %d updates",
          ts.num_update)
    for a, b in zip(snaps, snaps[1:]):
        all_finite(b, "run_steps param " + name)
        check(not np.array_equal(a, b), "run_steps: %s did not change over "
              "a chunk", name)
    log("run_steps: 2 chunks x %d steps, outputs %s finite, %.1fs",
        chunk + 1, probs.shape, time.time() - t0)


# -------------------------------------------------------------- phase: serve
def phase_serve(cfg, platform, mod, workdir):
    """Checkpoint from the fit -> serving.Server -> concurrent requests."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    image, n = cfg["image"], cfg["requests"]
    prefix = os.path.join(workdir, "smoke")
    mod.save_checkpoint(prefix, 1)
    x, _ = synthetic(cfg, n, seed_offset=2)
    # NDArrayIter pads the last batch itself; predict removes the padding
    ref = mod.predict(mx.io.NDArrayIter(x, None, batch_size=cfg["batch"]))
    on_platform(ref, platform, "Module.predict outputs")
    ref = all_finite(ref.asnumpy(), "Module.predict outputs")
    check(ref.shape == (n, cfg["classes"]), "Module.predict shape %s",
          ref.shape)

    server = serving.Server()
    try:
        model = server.register_checkpoint(
            "resnet", prefix, 1, {"data": (3, image, image)},
            dev_type="tpu", dev_id=0, max_batch=cfg["serve_max_batch"])
        on_platform(model._param_blob, platform, "served weights")
        # compile the whole bucket ladder before traffic, as a deployment
        # would: which rungs the batcher picks depends on thread timing,
        # and a rung first met in a later run would be a fresh compile
        model.warm(timeout=600.0)
        rows = [None] * n
        errors = []

        def client(i):
            try:
                rows[i] = server.predict("resnet", {"data": x[i]},
                                         timeout=600.0)[0]
            except Exception as exc:   # re-raised after join
                errors.append(exc)

        t0 = time.time()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(900.0)
        check(not any(t.is_alive() for t in threads),
              "serve: a client is still waiting")
        if errors:
            raise errors[0]
        stats = model.stats()
    finally:
        server.close()
    got = all_finite(np.stack(rows), "served rows")
    check(stats["requests"] == n and not stats["errors"],
          "serve stats %r", stats)
    worst = np.abs(got - ref).max()
    check(np.allclose(got, ref, rtol=SERVE_RTOL, atol=SERVE_ATOL),
          "served rows differ from Module.predict: max abs %.3e (rows up "
          "to %.3e)", worst, np.abs(ref).max())
    # agreement means something only if the rows depend on the request
    spread = np.abs(ref - ref[0]).max()
    check(spread > 10 * max(worst, SERVE_ATOL * 1e-2),
          "serve: rows differ between requests by %.3e at most — too "
          "little to tell served rows apart (max error %.3e)", spread, worst)
    log("serve: %d concurrent requests in %d batches %s, max |served - "
        "predict| %.2e against a spread between requests of %.2e, %.1fs",
        n, stats["batches"], stats["batches_by_bucket"], worst, spread,
        time.time() - t0)


# ----------------------------------------------------------------- phase: dp
def phase_dp(cfg, platform):
    """One update over a dp mesh of every chip == the same update on one."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.train import TrainStep
    devs = jax.devices()
    n = len(devs)
    batch, image = cfg["batch"] * n, cfg["image"]
    x, y = synthetic(cfg, batch, seed_offset=3)
    shapes = ({"data": (batch, 3, image, image)}, {"softmax_label": (batch,)})
    key = jax.random.PRNGKey(cfg["seed"])

    def one_update(mesh):
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                               rescale_grad=1.0 / batch, wd=1e-4)
        ts = TrainStep(make_net(cfg), opt, mesh=mesh,
                       policy=amp.Policy("bfloat16"))
        params, state, aux = ts.init(*shapes, seed=cfg["seed"])
        dev_batch = ts.shard_batch({"data": x, "softmax_label": y})
        where = {s.device for s in dev_batch["data"].addressable_shards}
        params, state, aux, outs = ts(params, state, aux, dev_batch, rng=key)
        on_platform((params, state, aux, outs), platform, "dp step state")
        return ({k: np.asarray(v) for k, v in params.items()},
                np.asarray(outs[0], np.float32), where)

    t0 = time.time()
    p_dp, o_dp, where = one_update(make_mesh({"dp": n}, devices=devs))
    check(len(where) == n, "dp: batch shards sit on %d device(s), not %d",
          len(where), n)
    for d in devs:
        stats = d.memory_stats()
        if stats is not None:      # the CPU backend reports none
            check(stats["bytes_in_use"] > 0, "dp: %s holds no buffers", d)
    p_one, o_one, _ = one_update(None)
    all_finite(o_dp, "dp outputs")
    check(np.allclose(o_dp, o_one, rtol=DP_RTOL, atol=DP_ATOL),
          "dp outputs differ from one device: max abs %.3e",
          np.abs(o_dp - o_one).max())
    # all parameters as one vector: per-tensor ratios blow up on tensors
    # that are still ~0 after one update (BN betas, biases)
    diff = np.sqrt(sum(float(np.square(p_dp[k] - p_one[k], dtype=np.float64)
                             .sum()) for k in p_one))
    norm = np.sqrt(sum(float(np.square(p_one[k], dtype=np.float64).sum())
                       for k in p_one))
    check(diff < DP_RTOL * norm, "dp updated params differ from one "
          "device: relative L2 %.3e", diff / norm)
    log("dp: %d chips, global batch %d, shards on %d devices, outputs max "
        "diff %.2e, params relative L2 diff %.2e, %.1fs", n, batch,
        len(where), np.abs(o_dp - o_one).max(), diff / norm,
        time.time() - t0)


def run(platform, **overrides):
    """All phases, in order, on devices of ``platform``."""
    import jax
    cfg = dict(FULL, **overrides)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        mod = phase_fit(cfg, platform)
        phase_run_steps(cfg, platform)
        phase_serve(cfg, platform, mod, workdir)
        if len(jax.devices()) > 1:
            phase_dp(cfg, platform)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main():
    t0 = time.time()
    device = describe_device()
    if device["platform"] != "tpu":
        print("chip_smoke: FAILED before any phase — jax.devices()[0]."
              "platform is %r, not 'tpu'.  This script proves the system on "
              "the chip; run it there (the CPU harness has "
              "tests/python/unittest/test_chip_smoke.py)."
              % device["platform"], file=sys.stderr)
        return 1
    from mxnet_tpu import cost, sanitize
    from mxnet_tpu.base import enable_compile_cache
    cache_dir = enable_compile_cache()
    t_phases = time.perf_counter()
    log("compile cache at %s (JAX_COMPILATION_CACHE_DIR %s)", cache_dir,
        "set" if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "unset")
    log("roofline peaks for %r: %.0f TFLOP/s, %.0f GB/s", device["kind"],
        *[p / s for p, s in zip(cost.resolve_peaks(), (1e12, 1e9))])
    run("tpu")
    # the set-up account's compile requests against the persistent cache
    # and its hits: their difference is what this run really compiled
    compiles = sanitize.setup_account(since=t_phases)
    log("compiled %d program(s); %d of %d compile requests were persistent-"
        "cache hits; trace %.1fs, lowering %.1fs, compile %.1fs; wall %.1fs",
        compiles["misses"], compiles["hits"], compiles["requests"],
        compiles["trace"], compiles["lower"], compiles["compile"],
        time.time() - t0)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
