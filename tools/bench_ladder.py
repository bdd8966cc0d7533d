"""Benchmark ladder (parity: reference docs/how_to/perf.md tables +
example/image-classification/benchmark_score.py).

Measures the reference's full published matrix on one TPU chip:
  - training img/s: resnet-50 b32, alexnet b256, inception-v3 b32
  - inference img/s (EvalStep): resnet-50 b32, resnet-152 b32
Prints one JSON line per row with the vs_baseline ratio against the
strongest published reference number (P100).

Usage: python tools/bench_ladder.py [--quick]
"""
import argparse
import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")


BASELINES_P100 = {
    # reference docs/how_to/perf.md:108-137 (train) and :67-99 (inference)
    "resnet50_train_b32": 181.53,
    "alexnet_train_b256": 1869.69,
    "inceptionv3_train_b32": 129.98,
    "resnet50_infer_b32": 713.17,
    "resnet152_infer_b32": 294.17,
}


def _symbol(name):
    from mxnet_tpu import models
    if name == "resnet50":
        return models.resnet.get_symbol(num_classes=1000, num_layers=50,
                                        image_shape="3,224,224")
    if name == "resnet152":
        return models.resnet.get_symbol(num_classes=1000, num_layers=152,
                                        image_shape="3,224,224")
    if name == "alexnet":
        return models.alexnet.get_symbol(num_classes=1000)
    if name == "inceptionv3":
        return models.inception_v3.get_symbol(num_classes=1000)
    raise ValueError(name)


def bench_train(name, batch, image=224, chunk=20, rounds=6):
    import mxnet_tpu as mx
    from mxnet_tpu.train import TrainStep
    net = _symbol(name)
    if name == "inceptionv3":
        image = 299
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                           rescale_grad=1.0 / batch, wd=1e-4)
    ts = TrainStep(net, opt, dtype="bfloat16")
    params, state, aux = ts.init({"data": (batch, 3, image, image)},
                                 {"softmax_label": (batch,)})
    rng = np.random.RandomState(0)
    data = rng.uniform(-1, 1, (batch, 3, image, image)).astype(np.float32)
    label = rng.randint(0, 1000, (batch,)).astype(np.float32)
    bd = ts.shard_batch({"data": data, "softmax_label": label})
    # warm the step AND the scalar-fetch sync program; the timed region
    # then ends in ONE scalar copy for rounds*(chunk+1) steps (same
    # protocol as bench.py)
    params, state, aux, outs = ts.run_steps(params, state, aux, bd, chunk)
    np.asarray(outs[0][0, 0])
    t0 = time.perf_counter()
    for _ in range(rounds):
        params, state, aux, outs = ts.run_steps(params, state, aux, bd,
                                                chunk)
    np.asarray(outs[0][0, 0])
    return batch * (chunk + 1) * rounds / (time.perf_counter() - t0)


def bench_infer(name, batch, image=224, iters=30, rounds=4):
    """EvalStep inference (parity: benchmark_score.py — forward only).

    The ``iters`` forwards are fused into ONE scanned program per
    dispatch, like the training path, so the number is device time and
    not per-call host dispatch.  Each scan step
    multiplies the input by a RUNTIME per-step scale (all ones), which
    keeps the body loop-dependent so XLA's loop-invariant code motion
    cannot hoist the forward out of the loop."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.train import TrainStep, EvalStep
    if name == "inceptionv3":
        image = 299
    net = _symbol(name)
    opt = mx.optimizer.SGD(learning_rate=0.1)
    ts = TrainStep(net, opt, dtype="bfloat16")
    params, _, aux = ts.init({"data": (batch, 3, image, image)},
                             {"softmax_label": (batch,)})
    es = EvalStep(net, dtype="bfloat16")
    rng = np.random.RandomState(0)
    bd = {"data": jnp.asarray(
              rng.uniform(-1, 1, (batch, 3, image, image)).astype(
                  np.float32)),
          "softmax_label": jnp.zeros((batch,), jnp.float32)}
    key = jax.random.PRNGKey(0)

    @jax.jit
    def chain(params, aux, bd, scales):
        def body(acc, s):
            b = dict(bd, data=bd["data"] * s)
            outs = es._fwd(params, aux, b, key)
            return acc + outs[0][0, 0].astype(jnp.float32), None
        acc, _ = jax.lax.scan(body, jnp.float32(0.0), scales)
        return acc

    scales = jnp.ones((iters,), jnp.float32)
    np.asarray(chain(params, aux, bd, scales))   # compile + warm
    t0 = time.perf_counter()
    for _ in range(rounds):
        acc = chain(params, aux, bd, scales)
    np.asarray(acc)
    return batch * rounds * iters / (time.perf_counter() - t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer timing rounds")
    args = ap.parse_args()
    import bench
    from mxnet_tpu.base import enable_compile_cache
    enable_compile_cache()
    device = bench.device_stamp()   # a TPU, or no ladder
    chunk = 10 if args.quick else 20
    rows = [
        ("resnet50_train_b32", lambda: bench_train("resnet50", 32,
                                                   chunk=chunk)),
        ("alexnet_train_b256", lambda: bench_train("alexnet", 256,
                                                   chunk=chunk)),
        ("inceptionv3_train_b32", lambda: bench_train("inceptionv3", 32,
                                                      chunk=chunk)),
        ("resnet50_infer_b32", lambda: bench_infer("resnet50", 32)),
        ("resnet152_infer_b32", lambda: bench_infer("resnet152", 32)),
    ]
    for name, fn in rows:
        val = fn()
        base = BASELINES_P100[name]
        print(json.dumps({"metric": name, "value": round(val, 1),
                          "unit": "img/s", "device": device,
                          "baseline_p100": base,
                          "vs_baseline": round(val / base, 2)}),
              flush=True)


if __name__ == "__main__":
    main()
