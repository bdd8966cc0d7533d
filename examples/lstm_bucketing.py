#!/usr/bin/env python
"""Bucketed LSTM language model (BASELINE config #3; parity: reference
example/rnn/lstm_bucketing.py on PTB).

Reads PTB text files if given, otherwise synthesises a corpus with a
learnable bigram structure so the script always runs end-to-end.  Uses
BucketingModule: one executor per sentence-length bucket, parameters shared
across buckets (the reference's shared memory pool becomes XLA executable
reuse + shared parameter arrays).
"""
from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402


def tokenize_text(fname, vocab=None, invalid_label=-1, start_label=0):
    """(parity: example/rnn tokenize_text)"""
    with open(fname) as f:
        lines = [ln.split() for ln in f]
    if vocab is None:
        vocab = {}
    sentences = []
    for words in lines:
        sent = []
        for w in words:
            if w not in vocab:
                vocab[w] = len(vocab) + start_label
            sent.append(vocab[w])
        if sent:
            sentences.append(np.array(sent, np.float32))
    return sentences, vocab


def synthetic_corpus(n_sent=500, vocab_size=50, seed=0):
    """Markov-chain corpus: next word = (word * 3 + 1) % V with noise."""
    rs = np.random.RandomState(seed)
    sents = []
    for _ in range(n_sent):
        length = rs.randint(5, 20)
        w = rs.randint(1, vocab_size)
        sent = [w]
        for _ in range(length - 1):
            w = (w * 3 + 1) % vocab_size if rs.rand() < 0.9 \
                else rs.randint(1, vocab_size)
            sent.append(w)
        sents.append(np.array(sent, np.float32))
    return sents


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--train-data", default=None, help="PTB text file")
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--num-hidden", type=int, default=64)
    ap.add_argument("--num-embed", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--num-epochs", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--buckets", default="10,20,30,40")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    mx.base.enable_compile_cache()

    invalid_label = 0
    if args.train_data and os.path.exists(args.train_data):
        sentences, vocab = tokenize_text(args.train_data, start_label=1)
        vocab_size = len(vocab) + 1
    else:
        logging.info("no --train-data: using synthetic Markov corpus")
        vocab_size = 50
        sentences = synthetic_corpus(vocab_size=vocab_size)
    buckets = [int(b) for b in args.buckets.split(",")]
    train = mx.rnn.BucketSentenceIter(sentences, args.batch_size,
                                      buckets=buckets,
                                      invalid_label=invalid_label)

    stack = mx.rnn.SequentialRNNCell()
    for i in range(args.num_layers):
        stack.add(mx.rnn.LSTMCell(num_hidden=args.num_hidden,
                                  prefix="lstm_l%d_" % i))

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data=data, input_dim=vocab_size,
                                 output_dim=args.num_embed, name="embed")
        stack.reset()
        outputs, states = stack.unroll(seq_len, inputs=embed,
                                       merge_outputs=True)
        pred = mx.sym.Reshape(outputs, shape=(-1, args.num_hidden))
        pred = mx.sym.FullyConnected(data=pred, num_hidden=vocab_size,
                                     name="pred")
        label = mx.sym.Reshape(label, shape=(-1,))
        pred = mx.sym.SoftmaxOutput(data=pred, label=label, name="softmax")
        return pred, ("data",), ("softmax_label",)

    mod = mx.module.BucketingModule(sym_gen,
                                    default_bucket_key=train.default_bucket_key,
                                    context=mx.tpu(0))
    mod.fit(train, num_epoch=args.num_epochs,
            eval_metric=mx.metric.Perplexity(invalid_label),
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9,
                              "wd": 1e-5,
                              "rescale_grad": 1.0 / args.batch_size},
            initializer=mx.init.Xavier(factor_type="in", magnitude=2.34),
            batch_end_callback=[mx.callback.Speedometer(args.batch_size,
                                                        20)])


if __name__ == "__main__":
    main()
