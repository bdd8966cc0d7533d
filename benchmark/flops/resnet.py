"""Required FLOPs of one training step of the ``resnet`` family: the
convolutions and the classifier, forward and backward, nothing recomputed.
A multiply-add is two FLOPs.  BatchNorm, ReLU, pooling and the optimizer are
left out: they are bound by memory and add under 1%."""


def conv_flops(n, c_in, c_out, k, h_out, w_out):
    """Forward FLOPs of one k x k convolution onto an h_out x w_out map."""
    return 2 * n * h_out * w_out * c_out * c_in * k * k


def fc_flops(n, c_in, c_out):
    return 2 * n * c_in * c_out


def forward_flops(cfg, batch):
    c_in, h, w = cfg["image_shape"]
    fl = cfg["filter_list"]
    h, w = h // 2, w // 2                       # conv0, 7x7 stride 2
    total = conv_flops(batch, c_in, fl[0], 7, h, w)
    h, w = h // 2, w // 2                       # max pool, stride 2
    prev = fl[0]
    for s, units in enumerate(cfg["units"]):
        out, mid = fl[s + 1], fl[s + 1] // 4
        for u in range(units):
            stride = 2 if (s > 0 and u == 0) else 1
            total += conv_flops(batch, prev, mid, 1, h, w)      # conv1
            h2, w2 = h // stride, w // stride
            total += conv_flops(batch, mid, mid, 3, h2, w2)     # conv2
            total += conv_flops(batch, mid, out, 1, h2, w2)     # conv3
            if u == 0:
                total += conv_flops(batch, prev, out, 1, h2, w2)  # shortcut
            h, w, prev = h2, w2, out
    return total + fc_flops(batch, prev, cfg["num_classes"])


def step_flops(cfg, batch):
    """Forward, and a backward of twice the forward: every convolution's
    input gradient (conv0's too: the input BatchNorm's shift is trained)
    and weight gradient."""
    return 3 * forward_flops(cfg, batch)


def items_per_step(cfg, batch):
    return batch
