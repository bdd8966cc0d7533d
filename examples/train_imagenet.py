#!/usr/bin/env python
"""Train ImageNet-class networks (BASELINE config #2; parity: reference
example/image-classification/train_imagenet.py, incl. `--benchmark 1`
synthetic-data throughput mode that docs/how_to/perf.md numbers use).

Real-data mode reads a RecordIO pack (tools/im2rec.py); benchmark mode
generates synthetic batches on the fly and reports img/s.

The training step is the fused SPMD TrainStep (forward+backward+update+
gradient reduction in one donated XLA computation) — the TPU replacement
for the reference's engine + kvstore path.  Use --module to force the
reference-shaped Module.fit path instead.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import models  # noqa: E402
from mxnet_tpu.train import TrainStep  # noqa: E402


def get_symbol(args):
    name = args.network
    if name.startswith("resnet"):
        return models.resnet.get_symbol(
            num_classes=args.num_classes,
            num_layers=int(name[len("resnet"):] or 50),
            image_shape=args.image_shape)
    if name == "alexnet":
        return models.alexnet.get_symbol(num_classes=args.num_classes)
    if name == "inception-v3":
        return models.inception_v3.get_symbol(num_classes=args.num_classes)
    if name.startswith("vgg"):
        return models.vgg.get_symbol(num_classes=args.num_classes,
                                     num_layers=int(name[3:] or 16))
    raise ValueError("unknown network %s" % name)


def benchmark(args, net):
    """Synthetic-data training throughput (parity: --benchmark 1)."""
    shape = tuple(int(x) for x in args.image_shape.split(","))
    batch = args.batch_size
    opt = mx.optimizer.create(args.optimizer, rescale_grad=1.0 / batch,
                              learning_rate=args.lr, momentum=0.9)
    dtype = "bfloat16" if args.dtype == "bfloat16" else None
    ts = TrainStep(net, opt, dtype=dtype)
    params, state, aux = ts.init({"data": (batch,) + shape},
                                 {"softmax_label": (batch,)})
    rs = np.random.RandomState(0)
    data = rs.uniform(-1, 1, (batch,) + shape).astype(np.float32)
    label = rs.randint(0, args.num_classes, (batch,)).astype(np.float32)
    batch_dev = ts.shard_batch({"data": data, "softmax_label": label})
    import jax
    # warmup / compile
    params, state, aux, outs = ts(params, state, aux, batch_dev)
    jax.block_until_ready(outs)
    t0 = time.time()
    iters = args.benchmark_iters
    for _ in range(iters):
        params, state, aux, outs = ts(params, state, aux, batch_dev)
    jax.block_until_ready(outs)
    dt = time.time() - t0
    ips = batch * iters / dt
    logging.info("benchmark: %s batch=%d %.2f img/s (%.1f ms/step)",
                 args.network, batch, ips, 1000 * dt / iters)
    return ips


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--network", default="resnet50")
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--image-shape", default="3,224,224")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--num-epochs", type=int, default=1)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--dtype", default="float32",
                    choices=("float32", "bfloat16"))
    ap.add_argument("--benchmark", type=int, default=0)
    ap.add_argument("--benchmark-iters", type=int, default=20)
    ap.add_argument("--data-train", default=None,
                    help="RecordIO file from tools/im2rec.py")
    ap.add_argument("--data-train-idx", default=None)
    ap.add_argument("--kv-store", default="local")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    mx.base.enable_compile_cache()

    net = get_symbol(args)
    if args.benchmark:
        benchmark(args, net)
        return
    if not args.data_train:
        raise SystemExit("--data-train required (or use --benchmark 1)")
    shape = tuple(int(x) for x in args.image_shape.split(","))
    train = mx.io.ImageRecordIter(
        path_imgrec=args.data_train, path_imgidx=args.data_train_idx,
        data_shape=shape, batch_size=args.batch_size, shuffle=True,
        rand_crop=True, rand_mirror=True, resize=max(shape[1:]) + 32,
        mean_r=123.68, mean_g=116.78, mean_b=103.94, preprocess_threads=8)
    mod = mx.Module(net, context=mx.tpu(0))
    mod.fit(train, num_epoch=args.num_epochs, optimizer=args.optimizer,
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9},
            kvstore=args.kv_store,
            batch_end_callback=[mx.callback.Speedometer(args.batch_size,
                                                        20)])


if __name__ == "__main__":
    main()
