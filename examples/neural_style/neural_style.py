"""Neural style transfer — optimizing the INPUT image (parity: reference
example/neural-style/).

The second imperative-pattern consumer beside the DCGAN: here nothing in
the network trains.  The executor is bound with a gradient buffer for
``data`` only (every weight at grad_req null), the in-graph loss compares
Gram matrices and content features against fixed targets, and the pixel
buffer is updated imperatively with an Adam updater — the
symbolic-backward + imperative-update mix on the *input* side.

The reference uses downloaded VGG-19 weights; this self-contained example
uses a small random-feature network (fixed seed) — random convolutional
features carry enough texture statistics for the mechanism (Stein/Gatys
style losses on input pixels) to demonstrably optimize, which is what the
example and its CI test pin.

Run: ``python examples/neural_style/neural_style.py [--steps N]``
"""
from __future__ import annotations

import argparse
import logging

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import sym

SIZE = 48
CHANNELS = (8, 16, 24)          # feature widths of the three levels


def feature_net():
    """Three conv levels; returns (symbol grouping the level outputs)."""
    x = sym.Variable("data")
    feats = []
    h = x
    for i, c in enumerate(CHANNELS):
        h = sym.Convolution(h, name="feat%d" % i, num_filter=c,
                            kernel=(3, 3), pad=(1, 1),
                            stride=(2, 2) if i else (1, 1), no_bias=True)
        h = sym.Activation(h, act_type="relu")
        feats.append(h)
    return sym.Group(feats)


def gram(feat, channels):
    """(1, C, H, W) feature map -> normalised (C, C) Gram matrix."""
    flat = sym.Reshape(feat, shape=(channels, -1))
    return sym.dot(flat, flat, transpose_b=True) / (channels * SIZE * SIZE)


def style_loss_net(content_weight=1.0, style_weight=50.0):
    """Scalar loss vs fixed targets fed as no-grad variables."""
    feats = feature_net()
    losses = []
    # content: match the deepest level's features directly
    tgt_c = sym.Variable("target_content")
    diff = feats[2] - tgt_c
    losses.append(content_weight * sym.sum(diff * diff))
    # style: match every level's Gram matrix
    for i, c in enumerate(CHANNELS):
        tgt_g = sym.Variable("target_gram%d" % i)
        gdiff = gram(feats[i], c) - tgt_g
        losses.append(style_weight * sym.sum(gdiff * gdiff))
    total = losses[0]
    for l in losses[1:]:
        total = total + l
    return sym.MakeLoss(total)


def _images(seed=0):
    """Synthetic content (soft blob) and style (diagonal stripes)."""
    yy, xx = np.mgrid[0:SIZE, 0:SIZE].astype(np.float32) / SIZE
    content = np.exp(-(((xx - 0.5) ** 2 + (yy - 0.45) ** 2) / 0.05))
    stripes = 0.5 + 0.5 * np.sin((xx + yy) * 24.0)
    def to3(img):
        return np.stack([img, img * 0.8, 1.0 - img])[None].astype(np.float32)
    return to3(content), to3(stripes)


def transfer(steps=60, lr=0.05, seed=0, log=None):
    log = log or logging.getLogger("neural_style")
    mx.random.seed(seed)
    content, style = _images(seed)
    shape = content.shape

    # 1. extract targets with a forward-only binding of the feature net
    feats = feature_net()
    fex = feats.simple_bind(mx.tpu(0), grad_req="null", data=shape)
    init = mx.initializer.Xavier(magnitude=2.0)
    for name, arr in fex.arg_dict.items():
        if name != "data":
            init(mx.initializer.InitDesc(name), arr)
    weight_values = {n: a.asnumpy() for n, a in fex.arg_dict.items()
                     if n != "data"}

    def run_feats(img):
        fex.forward(is_train=False, data=mx.nd.array(img))
        return [o.asnumpy() for o in fex.outputs]

    style_feats = run_feats(style)
    content_feats = run_feats(content)

    def gram_np(f):
        c = f.shape[1]
        flat = f.reshape(c, -1)
        return flat @ flat.T / (c * SIZE * SIZE)

    targets = {"target_content": content_feats[2]}
    for i, f in enumerate(style_feats):
        targets["target_gram%d" % i] = gram_np(f).astype(np.float32)

    # 2. bind the loss with a gradient ONLY for the image pixels
    net = style_loss_net()
    reqs = {n: "write" if n == "data" else "null"
            for n in net.list_arguments()}
    ex = net.simple_bind(mx.tpu(0), grad_req=reqs, data=shape,
                         **{k: v.shape for k, v in targets.items()})
    for n, v in weight_values.items():
        ex.arg_dict[n][:] = v
    for n, v in targets.items():
        ex.arg_dict[n][:] = v

    # 3. optimize the pixels imperatively (Adam updater on the buffer)
    img = mx.nd.array(content + 0.1 *
                      np.random.RandomState(seed).randn(*shape)
                      .astype(np.float32))
    updater = mx.optimizer.get_updater(
        mx.optimizer.Adam(learning_rate=lr))
    history = []
    for step in range(steps):
        ex.arg_dict["data"][:] = img.asnumpy()
        ex.forward(is_train=True)
        ex.backward()
        loss = float(ex.outputs[0].asnumpy().sum())
        history.append(loss)
        updater(0, ex.grad_dict["data"], img)
        if step % 10 == 0:
            log.info("step %d loss %.4f", step, loss)
    return img.asnumpy(), history


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--out", type=str, default="/tmp/neural_style.npy")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    mx.base.enable_compile_cache()
    img, hist = transfer(steps=args.steps)
    np.save(args.out, img)
    logging.info("loss %0.4f -> %0.4f; stylised image -> %s",
                 hist[0], hist[-1], args.out)


if __name__ == "__main__":
    main()
