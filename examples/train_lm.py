#!/usr/bin/env python
"""Decoder-only transformer language model — the long-context training demo
(no reference analogue: SURVEY.md §5.7 notes the reference has no attention
op at all; this is the TPU-native capability that replaces bucketed BPTT).

The same symbol graph runs through three attention lowerings:
- single chip, short T: fused XLA attention;
- single chip, long T:  the Pallas flash kernel (blocked online softmax);
- --sequence-parallel N: ring attention over an `sp` mesh axis — K/V blocks
  rotate between devices via ppermute, so sequence length scales with the
  number of chips.

Training runs through TrainStep.run_steps: chunks of steps fused into one
XLA program (lax.scan), weights resident in HBM throughout.

Synthetic corpus: a fixed random bigram table, so perplexity has a known
floor and convergence is quickly visible.
"""
from __future__ import annotations

import argparse
import ast
import logging
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import models  # noqa: E402
from mxnet_tpu.train import TrainStep  # noqa: E402
from mxnet_tpu.parallel import mesh as mesh_mod  # noqa: E402


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="transformer",
                   choices=("transformer", "hybrid_lm"),
                   help="module under mxnet_tpu.models whose get_symbol "
                        "builds the net (hybrid_lm: the parts --pattern "
                        "names)")
    p.add_argument("--pattern", default="M*E",
                   help="hybrid_lm: one letter a part on the residual "
                        "stream, each on its own pre-norm: M a Mamba-2 "
                        "mixer, K a Kimi Delta Attention mixer (gated delta "
                        "rule), * grouped-query attention, L latent "
                        "attention (MLA, no positions), E an expert layer, "
                        "D a dense MLP; a layer of a mixer and a "
                        "feed-forward is two letters, e.g. KDKEKELEKE")
    p.add_argument("--hybrid-arg", action="append", default=[],
                   metavar="NAME=VALUE",
                   help="hybrid_lm: any further size of "
                        "models/hybrid_lm.get_symbol, e.g. kda_heads=4 "
                        "kda_head_dim=32 kv_lora_rank=64 qk_nope_head_dim=32 "
                        "qk_rope_head_dim=16 v_head_dim=32 mlp_hidden=512 "
                        "mlp_act=silu mlp_gated=1 num_experts=8 "
                        "experts_per_token=2 (repeatable)")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--num-hidden", type=int, default=128)
    p.add_argument("--num-heads", type=int, default=4)
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--chunk", type=int, default=9,
                   help="steps fused per XLA program (run_steps)")
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--sequence-parallel", type=int, default=0,
                   help="shard the sequence over this many devices "
                        "(ring attention); 0 = off")
    return p.parse_args()


def bigram_corpus(vocab, n_tokens, seed=0):
    rng = np.random.RandomState(seed)
    # each token has 4 likely successors
    succ = rng.randint(0, vocab, (vocab, 4))
    toks = np.empty(n_tokens, np.int64)
    toks[0] = 0
    choices = rng.randint(0, 4, n_tokens)
    for i in range(1, n_tokens):
        toks[i] = succ[toks[i - 1], choices[i]]
    return toks


def main():
    logging.basicConfig(level=logging.INFO)
    mx.base.enable_compile_cache()
    args = parse_args()
    T, B = args.seq_len, args.batch_size

    if args.sequence_parallel:
        import jax
        n = args.sequence_parallel
        assert jax.device_count() >= n, (
            "need %d devices for --sequence-parallel" % n)
        mesh_mod.set_sequence_mesh(
            mesh_mod.make_mesh({"sp": n},
                               devices=jax.devices()[:n]))
        logging.info("ring attention over sp=%d devices", n)

    size = dict(vocab_size=args.vocab, seq_len=T, num_hidden=args.num_hidden,
                num_heads=args.num_heads)
    if args.model == "transformer":     # hybrid_lm's depth is its pattern
        size["num_layers"] = args.num_layers
    else:
        size["pattern"] = args.pattern
        for pair in args.hybrid_arg:
            name, value = pair.split("=", 1)
            try:
                size[name] = ast.literal_eval(value)
            except (ValueError, SyntaxError):       # a name, e.g. mlp_act
                size[name] = value
    net = getattr(models, args.model).get_symbol(**size)
    opt = mx.optimizer.Adam(learning_rate=args.lr)
    ts = TrainStep(net, opt)
    params, state, aux = ts.init({"data": (B, T)},
                                 {"softmax_label": (B, T)})

    toks = bigram_corpus(args.vocab, B * (T + 1) * 8)
    windows = toks[:B * 8 * (T + 1)].reshape(B * 8, T + 1)

    logging.info("training %d steps (chunks of %d) ...", args.steps,
                 args.chunk + 1)
    t0 = time.time()
    done = 0
    chunk = args.chunk
    while done < args.steps:
        sel = np.random.RandomState(done).randint(0, windows.shape[0], B)
        x = windows[sel, :-1].astype(np.float32)
        y = windows[sel, 1:].astype(np.float32)
        bd = ts.shard_batch({"data": x, "softmax_label": y})
        params, state, aux, outs = ts.run_steps(params, state, aux, bd,
                                                chunk)
        done += chunk + 1
        probs = np.asarray(outs[0]).reshape(B, T, args.vocab)
        picked = np.take_along_axis(
            probs, y.astype(int)[..., None], axis=2)[..., 0]
        ppl = float(np.exp(-np.log(np.clip(picked, 1e-9, 1)).mean()))
        logging.info("step %d: train ppl %.2f (%.1f tok/s)", done, ppl,
                     done * B * T / (time.time() - t0))

    mesh_mod.set_sequence_mesh(None)
    # bigram with 4 uniform successors -> ppl floor ~4
    logging.info("final train perplexity: %.2f (floor ~4 for this corpus)",
                 ppl)
    return 0 if ppl < args.vocab / 4 else 1


if __name__ == "__main__":
    sys.exit(main())
