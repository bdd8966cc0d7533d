#!/usr/bin/env python3
"""sha256 of the StableHLO of three small one-device step programs (the
loss-scaled `TrainStep` of a ResNet-50 at 32x32, a two-layer transformer and
an `ME*E` hybrid; no mesh, no ZeRO), lowered on the CPU.

A change that must not move the one-chip cells' programs prints the same
three lines on the parent and on the change (PERF.md 6 keeps them):

  JAX_PLATFORMS=cpu PYTHONPATH=<tree> python3 tools/step_program_sha.py

Nothing is compiled or run; a count, never a time."""
import hashlib

import numpy as np

import jax
import mxnet_tpu as mx
from mxnet_tpu import amp
from mxnet_tpu.models import hybrid_lm, resnet, transformer
from mxnet_tpu.train import TrainStep


def step_text(net, opt, dshape, lshape):
    ts = TrainStep(net, opt, policy=amp.Policy("bfloat16"))
    p, s, a = ts.init({"data": dshape}, {"softmax_label": lshape})
    b = ts.shard_batch({"data": np.zeros(dshape, np.float32),
                        "softmax_label": np.zeros(lshape, np.float32)})
    return ts._step.lower(p, s, a, ts._scale_state_dev(), b,
                          jax.random.PRNGKey(0), ts.fopt.hyper(0),
                          np.int32(1)).as_text()


def main():
    def sgd():
        return mx.optimizer.SGD(learning_rate=0.1, momentum=0.9, wd=1e-4,
                                rescale_grad=1 / 4)

    def adam():
        return mx.optimizer.Adam(learning_rate=1e-4, rescale_grad=1 / 2)
    programs = {
        "resnet50": step_text(resnet.get_symbol(
            num_classes=10, num_layers=50, image_shape="3,32,32"), sgd(),
            (4, 3, 32, 32), (4,)),
        "transformer": step_text(transformer.get_symbol(
            vocab_size=64, seq_len=16, num_layers=2, num_hidden=32,
            num_heads=2), adam(), (2, 16), (2, 16)),
        "hybrid_ME*E": step_text(hybrid_lm.get_symbol(
            vocab_size=64, seq_len=32, pattern="ME*E", num_hidden=32),
            adam(), (2, 32), (2, 32))}
    for name, text in programs.items():
        print(name, len(text), hashlib.sha256(text.encode()).hexdigest())


if __name__ == "__main__":
    main()
