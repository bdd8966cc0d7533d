"""Minimal Faster-RCNN-style detection pipeline (parity: the reference's
example/rcnn capability axis — RPN + Proposal + ROIPooling exercised in a
real model rather than only in unit tests; reference
example/rcnn/rcnn/symbol.py is the full-scale version of this shape).

Synthetic task: each 1-channel 64x64 image contains one bright axis-aligned
square; the label is its class by size (small/large).  The network:

  backbone convs -> RPN head (objectness + bbox deltas)
                 -> _contrib_Proposal (anchors -> NMS'd ROIs)
                 -> ROIPooling over the backbone features
                 -> classifier head -> SoftmaxOutput

The RPN is trained with a companion objectness head (MakeLoss on a simple
center-heat target) while the classifier trains through the ROI features —
both in ONE symbol, demonstrating the multi-loss Group + the detection ops
end to end.  Runs on CPU in under a minute.

Usage: JAX_PLATFORMS=cpu python examples/rcnn/train_toy_rcnn.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx  # noqa: E402


def make_data(n, size=64, rng=None):
    rng = rng or np.random.RandomState(0)
    x = rng.rand(n, 1, size, size).astype(np.float32) * 0.1
    labels = np.zeros((n,), np.float32)
    heat = np.zeros((n, 1, size // 8, size // 8), np.float32)
    for i in range(n):
        big = rng.randint(0, 2)
        side = rng.randint(18, 26) if big else rng.randint(6, 12)
        y0 = rng.randint(0, size - side)
        x0 = rng.randint(0, size - side)
        x[i, 0, y0:y0 + side, x0:x0 + side] += 1.0
        labels[i] = big
        cy, cx = (y0 + side // 2) // 8, (x0 + side // 2) // 8
        heat[i, 0, cy, cx] = 1.0
    return x, labels, heat


def build_symbol(batch, num_anchors=6):
    data = mx.sym.Variable("data")
    # backbone: stride-8 feature map
    body = data
    for i, nf in enumerate((8, 16, 32)):
        body = mx.sym.Convolution(body, kernel=(3, 3), stride=(2, 2),
                                  pad=(1, 1), num_filter=nf,
                                  name="conv%d" % i)
        body = mx.sym.Activation(body, act_type="relu", name="relu%d" % i)
    # RPN head
    rpn = mx.sym.Convolution(body, kernel=(3, 3), pad=(1, 1), num_filter=16,
                             name="rpn_conv")
    rpn = mx.sym.Activation(rpn, act_type="relu", name="rpn_relu")
    rpn_cls = mx.sym.Convolution(rpn, kernel=(1, 1),
                                 num_filter=2 * num_anchors,
                                 name="rpn_cls_score")
    rpn_bbox = mx.sym.Convolution(rpn, kernel=(1, 1),
                                  num_filter=4 * num_anchors,
                                  name="rpn_bbox_pred")
    # objectness probabilities for Proposal: softmax over {bg, fg}
    cls_resh = mx.sym.Reshape(rpn_cls, shape=(0, 2, -1), name="rpn_resh")
    cls_prob = mx.sym.softmax(cls_resh, axis=1, name="rpn_prob")
    cls_prob = mx.sym.Reshape(cls_prob,
                              shape=(batch, 2 * num_anchors, 8, 8),
                              name="rpn_prob_resh")
    im_info = mx.sym.Variable("im_info")
    rois = mx.sym.Proposal(
        cls_prob=cls_prob, bbox_pred=rpn_bbox, im_info=im_info,
        feature_stride=8, scales=(2, 4), ratios=(0.5, 1, 2),
        rpn_pre_nms_top_n=64, rpn_post_nms_top_n=8, threshold=0.7,
        rpn_min_size=4, name="proposal")
    # ROI features -> classifier
    pooled = mx.sym.ROIPooling(mx.sym.BlockGrad(body),
                               mx.sym.BlockGrad(rois),
                               pooled_size=(4, 4), spatial_scale=1.0 / 8,
                               name="roi_pool")
    # (post_nms * batch, C, 4, 4) -> pool over ROIs per image via reshape
    flat = mx.sym.Flatten(mx.sym.Reshape(pooled, shape=(batch, -1)),
                          name="roi_flat")
    fc = mx.sym.FullyConnected(flat, num_hidden=32, name="fc1")
    fc = mx.sym.Activation(fc, act_type="relu", name="fc_relu")
    cls = mx.sym.FullyConnected(fc, num_hidden=2, name="cls")
    label = mx.sym.Variable("softmax_label")
    cls_loss = mx.sym.SoftmaxOutput(cls, label, name="softmax")
    # RPN objectness auxiliary loss: push the fg map toward the heat target
    heat = mx.sym.Variable("rpn_heat")
    fg = mx.sym.slice_axis(cls_prob, axis=1, begin=num_anchors,
                           end=num_anchors + 1, name="fg_slice")
    rpn_loss = mx.sym.MakeLoss(
        mx.sym.mean(mx.sym.square(fg - heat)), grad_scale=8.0,
        name="rpn_loss")
    return mx.sym.Group([cls_loss, rpn_loss])


def main():
    batch, size = 8, 64
    np.random.seed(0)
    x, y, heat = make_data(192, size)
    im_info = np.tile(np.array([[size, size, 1.0]], np.float32), (batch, 1))

    net = build_symbol(batch)
    it = mx.io.NDArrayIter({"data": x,
                            "im_info": np.tile(im_info[:1], (192, 1)),
                            "rpn_heat": heat},
                           {"softmax_label": y}, batch_size=batch)
    mod = mx.Module(net, data_names=("data", "im_info", "rpn_heat"),
                    label_names=("softmax_label",), context=mx.tpu(0))
    # the Group emits (cls_prob, rpn_loss); score on the classifier head
    def head_acc(label, pred):
        return float((pred.argmax(axis=1) == label).mean())
    metric = mx.metric.np(head_acc, name="accuracy",
                          allow_extra_outputs=True)
    # SGD(0.05, momentum 0.9) drove every fc1 unit negative within three
    # epochs (fc_relu live fraction -> 0.0): the 4096-dim ROI-concat
    # features give the fc head gradients ~64x the conv layers', so one
    # global rate either kills the head (dead-ReLU collapse; the head
    # then predicts the class-0 fraction 0.432 forever) or is too slow
    # for the convs.  The runtime is faithful — the pin diverged; Adam's
    # per-parameter scaling absorbs the imbalance and trains the head to
    # ~0.98 across seeds in the same 12 epochs.
    mod.fit(it, num_epoch=12, optimizer="adam",
            optimizer_params={"learning_rate": 1e-3,
                              "rescale_grad": 1.0 / batch},
            initializer=mx.initializer.Xavier(magnitude=2.0),
            eval_metric=metric)
    score = mod.score(mx.io.NDArrayIter(
        {"data": x, "im_info": np.tile(im_info[:1], (192, 1)),
         "rpn_heat": heat}, {"softmax_label": y}, batch_size=batch),
        metric)
    acc = dict(score)["accuracy"]
    print("toy rcnn train accuracy: %.3f" % acc)
    assert acc > 0.8, "detection head did not learn (%.3f)" % acc
    print("PASS")


if __name__ == "__main__":
    main()
