#!/usr/bin/env python
"""Train MLP/LeNet on MNIST (BASELINE config #1; parity: reference
example/image-classification/train_mnist.py).

Downloads nothing: uses the real MNIST files if present under --data-dir,
otherwise generates a synthetic drop-in (structured digits) so the script
always runs end-to-end.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import models  # noqa: E402


def synthetic_mnist(n=2000, seed=0):
    """Structured stand-in for MNIST: class k = blob at a k-dependent spot."""
    rs = np.random.RandomState(seed)
    x = rs.rand(n, 1, 28, 28).astype(np.float32) * 0.1
    y = rs.randint(0, 10, n).astype(np.float32)
    for i in range(n):
        k = int(y[i])
        r, c = 4 + 2 * (k // 5), 4 + 2 * (k % 5)
        x[i, 0, r:r + 6, c:c + 6] += 0.9
    return x, y


def get_iters(args):
    ubyte = os.path.join(args.data_dir, "train-images-idx3-ubyte")
    if os.path.exists(ubyte) or os.path.exists(ubyte + ".gz"):
        train = mx.io.MNISTIter(
            image=os.path.join(args.data_dir, "train-images-idx3-ubyte"),
            label=os.path.join(args.data_dir, "train-labels-idx1-ubyte"),
            batch_size=args.batch_size, shuffle=True, flat=args.network == "mlp")
        val = mx.io.MNISTIter(
            image=os.path.join(args.data_dir, "t10k-images-idx3-ubyte"),
            label=os.path.join(args.data_dir, "t10k-labels-idx1-ubyte"),
            batch_size=args.batch_size, flat=args.network == "mlp")
        return train, val
    logging.info("MNIST not found in %s — using synthetic digits",
                 args.data_dir)
    x, y = synthetic_mnist(4000)
    xv, yv = synthetic_mnist(1000, seed=1)
    if args.network == "mlp":
        x, xv = x.reshape(len(x), 784), xv.reshape(len(xv), 784)
    train = mx.io.NDArrayIter(x, y, args.batch_size, shuffle=True)
    val = mx.io.NDArrayIter(xv, yv, args.batch_size)
    return train, val


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--network", default="lenet", choices=("mlp", "lenet"))
    ap.add_argument("--data-dir", default="data/mnist")
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--num-epochs", type=int, default=3)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--gpus", default="0",
                    help="chips to train on, e.g. 0,1 (mx.gpu(i) is an "
                         "alias of mx.tpu(i); virtual host devices under "
                         "JAX_PLATFORMS=cpu)")
    ap.add_argument("--kv-store", default="local")
    ap.add_argument("--load-epoch", type=int, default=None)
    ap.add_argument("--model-prefix", default=None)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    mx.base.enable_compile_cache()

    net = (models.mlp if args.network == "mlp" else models.lenet) \
        .get_symbol(num_classes=10)
    devs = [mx.gpu(int(i)) for i in args.gpus.split(",")]
    train, val = get_iters(args)

    mod = mx.Module(net, context=devs)
    arg_params = aux_params = None
    begin = 0
    if args.load_epoch is not None and args.model_prefix:
        _, arg_params, aux_params = mx.model.load_checkpoint(
            args.model_prefix, args.load_epoch)
        begin = args.load_epoch
    cbs = [mx.callback.Speedometer(args.batch_size, 50)]
    epoch_cbs = []
    if args.model_prefix:
        epoch_cbs.append(mx.callback.do_checkpoint(args.model_prefix))
    mod.fit(train, eval_data=val, num_epoch=args.num_epochs,
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9},
            kvstore=args.kv_store, arg_params=arg_params,
            aux_params=aux_params, begin_epoch=begin,
            batch_end_callback=cbs, epoch_end_callback=epoch_cbs)
    score = mod.score(val, mx.metric.Accuracy())
    logging.info("final validation accuracy: %s", dict(score))


if __name__ == "__main__":
    main()
