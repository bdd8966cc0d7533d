"""DCGAN — adversarial training with two Modules and hand-rolled
imperative updates (parity: reference example/gan/dcgan.py).

This example exists to exercise the symbolic+imperative mix end to end:

* two independent Modules (generator / discriminator), each with its own
  Adam optimizer;
* label flipping done imperatively (``label[:] = 0/1``) between forward
  passes of the same bound discriminator;
* discriminator gradients ACCUMULATED across the fake and real batches by
  imperative NDArray arithmetic on the executor's gradient buffers
  (``grad += stashed``) before a single ``update()``;
* the generator trained from the discriminator's input gradients
  (``modD.get_input_grads()`` fed as ``out_grads`` to ``modG.backward``).

Run: ``python examples/gan/dcgan.py [--epochs N] [--batch B]``
(synthetic blob data, so the example is self-contained; swap
``blob_batches`` for an ``ImageRecordIter`` loop to train on real
images).
"""
from __future__ import annotations

import argparse
import logging

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import sym


def make_generator(code_dim=64, ngf=32, channels=1, fix_gamma=False,
                   eps=1e-5):
    """4x4 -> 8x8 -> 16x16 -> 32x32 transposed-conv stack, tanh output."""
    code = sym.Variable("code")
    h = sym.Deconvolution(code, name="g_up0", kernel=(4, 4), num_filter=ngf * 4,
                          no_bias=True)
    h = sym.BatchNorm(h, name="g_bn0", fix_gamma=fix_gamma, eps=eps)
    h = sym.Activation(h, act_type="relu")
    for i, nf in enumerate((ngf * 2, ngf)):
        h = sym.Deconvolution(h, name="g_up%d" % (i + 1), kernel=(4, 4),
                              stride=(2, 2), pad=(1, 1), num_filter=nf,
                              no_bias=True)
        h = sym.BatchNorm(h, name="g_bn%d" % (i + 1), fix_gamma=fix_gamma,
                          eps=eps)
        h = sym.Activation(h, act_type="relu")
    h = sym.Deconvolution(h, name="g_out", kernel=(4, 4), stride=(2, 2),
                          pad=(1, 1), num_filter=channels, no_bias=True)
    return sym.Activation(h, act_type="tanh")


def make_discriminator(ndf=32, fix_gamma=False, eps=1e-5):
    """32x32 -> 1 logit; LogisticRegressionOutput gives sigmoid + BCE grad."""
    x = sym.Variable("data")
    h = sym.Convolution(x, name="d_c0", kernel=(4, 4), stride=(2, 2),
                        pad=(1, 1), num_filter=ndf, no_bias=True)
    h = sym.LeakyReLU(h, act_type="leaky", slope=0.2)
    for i, nf in enumerate((ndf * 2, ndf * 4)):
        h = sym.Convolution(h, name="d_c%d" % (i + 1), kernel=(4, 4),
                            stride=(2, 2), pad=(1, 1), num_filter=nf,
                            no_bias=True)
        h = sym.BatchNorm(h, name="d_bn%d" % (i + 1), fix_gamma=fix_gamma,
                          eps=eps)
        h = sym.LeakyReLU(h, act_type="leaky", slope=0.2)
    h = sym.Convolution(h, name="d_out", kernel=(4, 4), num_filter=1,
                        no_bias=True)
    return sym.LogisticRegressionOutput(sym.Flatten(h), name="dloss")


def blob_batches(batch, steps, size=32, seed=0):
    """Synthetic 'real' images: soft two-blob fields in [-1, 1] — enough
    structure for the discriminator to separate from early noise."""
    rs = np.random.RandomState(seed)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    for _ in range(steps):
        imgs = np.empty((batch, 1, size, size), np.float32)
        for b in range(batch):
            cx, cy = rs.rand(2) * 0.5 + 0.25
            r = 0.08 + 0.1 * rs.rand()
            blob = np.exp(-(((xx - cx) ** 2 + (yy - cy) ** 2) / r ** 2))
            imgs[b, 0] = blob * 2.0 - 1.0
        yield imgs


def train(epochs=1, batch=32, steps_per_epoch=25, code_dim=64, lr=2e-4,
          seed=0, log=None, ctx=None):
    log = log or logging.getLogger("dcgan")
    rs = np.random.RandomState(seed + 1)
    mx.random.seed(seed)   # deterministic init: same seed => same G/D start
    ctx = ctx or mx.tpu(0)

    mod_g = mx.Module(make_generator(code_dim=code_dim),
                      data_names=("code",), label_names=None, context=ctx)
    mod_g.bind(data_shapes=[("code", (batch, code_dim, 1, 1))],
               inputs_need_grad=True)
    mod_g.init_params(mx.initializer.Normal(0.02))
    mod_g.init_optimizer(optimizer="adam",
                         optimizer_params={"learning_rate": lr,
                                           "beta1": 0.5, "wd": 0.0})

    mod_d = mx.Module(make_discriminator(), data_names=("data",),
                      label_names=("dloss_label",), context=ctx)
    mod_d.bind(data_shapes=[("data", (batch, 1, 32, 32))],
               label_shapes=[("dloss_label", (batch, 1))],
               inputs_need_grad=True)
    mod_d.init_params(mx.initializer.Normal(0.02))
    mod_d.init_optimizer(optimizer="adam",
                         optimizer_params={"learning_rate": lr,
                                           "beta1": 0.5, "wd": 0.0})

    # imperative label buffer, flipped in place between D passes
    label = mx.nd.zeros((batch, 1), ctx=ctx)
    history = {"d_loss": [], "g_loss": []}

    def bce(pred, target):
        p = np.clip(pred.reshape(-1), 1e-6, 1 - 1e-6)
        return float(-np.mean(target * np.log(p)
                              + (1 - target) * np.log(1 - p)))

    for epoch in range(epochs):
        for it, real in enumerate(blob_batches(batch, steps_per_epoch,
                                               seed=seed + epoch)):
            code = rs.randn(batch, code_dim, 1, 1).astype(np.float32)
            mod_g.forward(mx.io.DataBatch(data=[mx.nd.array(code)],
                                          label=[]), is_train=True)
            fake = mod_g.get_outputs()[0]

            # --- discriminator on the fake half: backward, stash grads
            label[:] = 0.0
            mod_d.forward(mx.io.DataBatch(data=[fake], label=[label]),
                          is_train=True)
            mod_d.backward()
            stash = [[g.copyto(g.context) if g is not None else None
                      for g in per_arg]
                     for per_arg in mod_d._exec_group.grad_arrays]
            p_fake = mod_d.get_outputs()[0].asnumpy()

            # --- discriminator on the real half: backward, then fold the
            # stashed fake-half gradients in imperatively and step once
            label[:] = 1.0
            mod_d.forward(mx.io.DataBatch(data=[mx.nd.array(real)],
                                          label=[label]), is_train=True)
            mod_d.backward()
            for per_arg, stashed in zip(mod_d._exec_group.grad_arrays,
                                        stash):
                for g, s in zip(per_arg, stashed):
                    if g is not None and s is not None:
                        g += s
            mod_d.update()
            p_real = mod_d.get_outputs()[0].asnumpy()

            # --- generator: D(fake) labelled real; chain D's input grads
            label[:] = 1.0
            mod_d.forward(mx.io.DataBatch(data=[fake], label=[label]),
                          is_train=True)
            mod_d.backward()
            mod_g.backward(mod_d.get_input_grads())
            mod_g.update()
            p_gen = mod_d.get_outputs()[0].asnumpy()

            d_loss = 0.5 * (bce(p_fake, 0.0) + bce(p_real, 1.0))
            g_loss = bce(p_gen, 1.0)
            history["d_loss"].append(d_loss)
            history["g_loss"].append(g_loss)
            if it % 10 == 0:
                log.info("epoch %d iter %d  d_loss %.4f  g_loss %.4f",
                         epoch, it, d_loss, g_loss)
    return mod_g, mod_d, history


def sample(mod_g, n, code_dim=64, seed=123):
    """Generate n images with the trained generator (forward, is_train
    False so BN uses its moving statistics)."""
    code = np.random.RandomState(seed).randn(n, code_dim, 1, 1) \
        .astype(np.float32)
    mod_g.forward(mx.io.DataBatch(data=[mx.nd.array(code)], label=[]),
                  is_train=False)
    return mod_g.get_outputs()[0].asnumpy()


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--steps", type=int, default=25)
    ap.add_argument("--out", type=str, default="/tmp/dcgan_samples.npy")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    mx.base.enable_compile_cache()
    mod_g, _, hist = train(epochs=args.epochs, batch=args.batch,
                           steps_per_epoch=args.steps)
    imgs = sample(mod_g, 16)
    np.save(args.out, imgs)
    logging.info("final d_loss %.4f g_loss %.4f; 16 samples -> %s",
                 hist["d_loss"][-1], hist["g_loss"][-1], args.out)


if __name__ == "__main__":
    main()
