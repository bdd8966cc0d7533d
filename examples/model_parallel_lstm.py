#!/usr/bin/env python
"""Model-parallel LSTM (BASELINE config #5; parity: reference
example/model-parallel-lstm/lstm.py:48-145).

Each LSTM layer is pinned to a device group with mx.AttrScope(ctx_group=...)
and the executor is bound with group2ctx — the TPU rebuild's eager
multi-device walk places each op on its group's device and inserts the
cross-device transfers (the reference's _CrossDeviceCopy nodes).

Run under the virtual CPU mesh to see real multi-device placement:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/model_parallel_lstm.py
"""
from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402


def lstm_unroll(num_layers, seq_len, input_size, num_hidden, num_embed,
                vocab_size, group_of_layer):
    """Unrolled multi-layer LSTM with each layer in its own ctx group."""
    cells = []
    for i in range(num_layers):
        with mx.AttrScope(ctx_group=group_of_layer(i)):
            cells.append(mx.rnn.LSTMCell(num_hidden=num_hidden,
                                         prefix="lstm_l%d_" % i))
    with mx.AttrScope(ctx_group=group_of_layer(0)):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data=data, input_dim=vocab_size,
                                 output_dim=num_embed, name="embed")
        outputs = mx.sym.SliceChannel(embed, num_outputs=seq_len,
                                      squeeze_axis=True)
    for i, cell in enumerate(cells):
        with mx.AttrScope(ctx_group=group_of_layer(i)):
            cell.reset()
            new_outputs = []
            states = cell.begin_state()
            for t in range(seq_len):
                out, states = cell(outputs[t], states)
                new_outputs.append(out)
            outputs = new_outputs
    with mx.AttrScope(ctx_group=group_of_layer(num_layers - 1)):
        concat = mx.sym.Concat(*[mx.sym.expand_dims(o, axis=1)
                                 for o in outputs], dim=1)
        pred = mx.sym.Reshape(concat, shape=(-1, num_hidden))
        pred = mx.sym.FullyConnected(data=pred, num_hidden=vocab_size,
                                     name="pred")
        label_r = mx.sym.Reshape(label, shape=(-1,))
        sm = mx.sym.SoftmaxOutput(data=pred, label=label_r, name="softmax")
    return sm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--num-hidden", type=int, default=32)
    ap.add_argument("--num-embed", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=12)
    ap.add_argument("--vocab-size", type=int, default=40)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--num-batches", type=int, default=30)
    ap.add_argument("--lr", type=float, default=0.2)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    mx.base.enable_compile_cache()

    import jax
    n_dev = max(1, len(jax.devices()))
    group2ctx = {"layer%d" % i: mx.gpu(i % n_dev)
                 for i in range(args.num_layers)}
    logging.info("placing %d layers on %d device(s)", args.num_layers, n_dev)

    net = lstm_unroll(args.num_layers, args.seq_len, args.vocab_size,
                      args.num_hidden, args.num_embed, args.vocab_size,
                      lambda i: "layer%d" % i)

    ex = net.simple_bind(mx.tpu(0), grad_req="write", group2ctx=group2ctx,
                         data=(args.batch_size, args.seq_len),
                         softmax_label=(args.batch_size, args.seq_len))
    init = mx.init.Xavier(magnitude=2.0)
    for name, arr in ex.arg_dict.items():
        if name not in ("data", "softmax_label"):
            init(mx.init.InitDesc(name), arr)

    rs = np.random.RandomState(0)
    # rescale per token: SoftmaxOutput's default normalization is 'null',
    # so the raw gradient sums over batch*seq_len rows
    opt = mx.optimizer.SGD(learning_rate=args.lr,
                           rescale_grad=1.0 / (args.batch_size
                                               * args.seq_len))
    updater = mx.optimizer.get_updater(opt)
    metric = mx.metric.Perplexity(ignore_label=None)
    for step in range(args.num_batches):
        # synthetic next-token task: y_t = (x_t * 3 + 1) % V
        x = rs.randint(1, args.vocab_size,
                       (args.batch_size, args.seq_len)).astype(np.float32)
        y = (x * 3 + 1) % args.vocab_size
        ex.arg_dict["data"][:] = x
        ex.arg_dict["softmax_label"][:] = y
        ex.forward(is_train=True)
        ex.backward()
        for i, name in enumerate(ex._symbol.list_arguments()):
            if name in ("data", "softmax_label"):
                continue
            updater(i, ex.grad_dict[name], ex.arg_dict[name])
        metric.update([mx.nd.array(y.reshape(-1))], [ex.outputs[0]])
        if (step + 1) % 10 == 0 or step + 1 == args.num_batches:
            logging.info("batch %d perplexity %.2f", step + 1,
                         metric.get()[1])
            metric.reset()


if __name__ == "__main__":
    main()
