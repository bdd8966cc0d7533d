"""Traffic entry ``run_steps``: ``TrainStep.run_steps(stacked=True)`` in scan
chunks, each step its own batch of token ids drawn from the seed and staged
on the device.  The loop keeps one chunk in flight: it dispatches chunk k+1
and then waits on chunk k, so the harness's own wait never idles the chip.
The number of chunks is fixed in set-up from one calibrated chunk; the last
wait closes the window.

The first steps, which the reference follows, are the first whole chunk of
the window's own program, from the seed's weights and an empty optimizer
state: the scan body, its slicing of the stacked batch, the optimizer's step
index and the loss-scale carry are in what is compared.  A chunk hands back
the state after its last step alone, so what is read is the last step's
loss, the optimizer's first slot (every step's gradient folded by the
optimizer's own rule) and the parameters' change after the chunk; no second
program of one step is built for a first gradient."""
import contextlib
import math
import time

import numpy as np

from benchmark import gen, observe


class Entry:
    def __init__(self, cell, seed, seconds, tracer=None):
        self.cell, self.seed, self.seconds = cell, seed, float(seconds)
        self.tracer = tracer
        self.tr, self.cfg = cell.traffic, cell.config
        self.batch = int(self.tr["batch"])
        self.chunk = int(self.tr["chunk"])
        self.observed = None
        self.on_window_start = lambda: None

    def build(self):
        import importlib
        import mxnet_tpu as mx
        from mxnet_tpu import amp
        from mxnet_tpu.train import TrainStep
        from benchmark.reference.train import family
        cfg = self.cfg
        self.shapes = family(cfg).param_shapes(cfg)
        net = importlib.import_module(cfg["symbol"]["module"]).get_symbol(
            **cfg["symbol"]["args"])
        opt = dict(cfg["optimizer"])
        name = opt.pop("name")
        items = self.batch * cfg["max_position_embeddings"]
        optimizer = mx.optimizer.create(name, rescale_grad=1.0 / items,
                                        **opt)
        dn, ln = cfg["data"]["name"], cfg["label"]["name"]
        self.ts = TrainStep(net, optimizer, data_names=(dn,),
                            label_names=(ln,),
                            policy=amp.Policy(cfg["precision"]["compute"]))
        have = {n: None for n in self.ts.param_names}
        if set(have) != set(self.shapes):
            raise SystemExit("the program's parameters are not the "
                             "reference's: %s" % sorted(
                                 set(have) ^ set(self.shapes))[:6])
        self.slots = {k: len(v) for k, v in self.ts.fopt.init_state(
            {k: np.zeros(1, np.float32) for k in self.shapes}).items()}
        self.names = (dn, ln)
        self.loss_fn = observe.mean_loss_fn()
        splits = getattr(family(cfg), "SPLIT", None)
        self.moment_fn = observe.moment_norms_fn(self.shapes, splits)
        self.change_fn = observe.change_norms_fn(self.shapes, cfg["init"],
                                                 splits)
        self.load(self.seed)

    def load(self, seed):
        """The seed's weights, an empty optimizer state and the seed's
        token batches: the first chunk, which the reference follows, and
        the pool that the window cycles."""
        import jax
        cfg, tr, chunk = self.cfg, self.tr, self.chunk
        self.params = self.state = self.pool = None
        self.ts.num_update = 0       # the optimizer's step index, with its state
        self.seed, self.key = seed, gen._key(seed)
        self.params = gen.make_weights(self.shapes, cfg["init"], seed)
        slots = self.slots
        self.state = jax.jit(lambda p: {
            k: tuple(jax.numpy.zeros_like(v) for _ in range(slots[k]))
            for k, v in p.items()})(self.params)
        self.aux = {}
        chunks = 1 + int(tr["pool_chunks"])
        data, label = gen.device_tokens(
            seed, chunks * chunk, self.batch,
            cfg["max_position_embeddings"], cfg["vocab_size"])
        dn, ln = self.names
        self.first, *self.pool = [
            {dn: data[c * chunk:(c + 1) * chunk],
             ln: label[c * chunk:(c + 1) * chunk]} for c in range(chunks)]

    def reference_batches(self):
        dn, ln = self.names
        return [(self.first[dn][i], self.first[ln][i])
                for i in range(self.chunk)]

    def _chunk_call(self, batch):
        self.params, self.state, self.aux, outs = self.ts.run_steps(
            self.params, self.state, self.aux, batch, self.chunk - 1,
            stacked=True)
        return outs

    def first_steps(self):
        """The first chunk through the window's own call; what the
        comparison reads of it, on the host."""
        outs = self._chunk_call(self.first)
        last = self.first[self.names[1]][self.chunk - 1]
        return observe.to_host({
            "loss": {self.chunk: self.loss_fn(outs[0], last)},
            "moment": self.moment_fn(self.state),
            "change": self.change_fn(self.params, self.key)})

    def run(self, t_process):
        import jax
        self.observed = self.first_steps()
        # warm-up on the window's own program, then one calibrated chunk
        for c in range(int(self.tr["warmup_chunks"])):
            outs = self._chunk_call(self.pool[c % len(self.pool)])
        jax.block_until_ready(outs)
        t = time.perf_counter()
        outs = self._chunk_call(self.pool[0])
        jax.block_until_ready(outs)
        per_chunk = time.perf_counter() - t
        n = max(2, int(math.ceil(self.seconds / per_chunk)))
        tracer = self.tracer
        span = tracer.span if tracer is not None \
            else lambda name: contextlib.nullcontext()
        stamps = np.zeros(n + 1)
        if tracer is not None:
            tracer.start()
        self.on_window_start()
        t0 = stamps[0] = time.perf_counter()
        prev = None
        for c in range(n):
            with span("bench:dispatch_chunk"):
                outs = self._chunk_call(self.pool[c % len(self.pool)])
            if prev is not None:
                with span("bench:wait_chunk"):
                    jax.block_until_ready(prev)
                stamps[c] = time.perf_counter()
            prev = outs
        with span("bench:wait_chunk"):
            jax.block_until_ready(prev)
        t1 = stamps[n] = time.perf_counter()
        if tracer is not None:
            tracer.stop()
        steps = n * self.chunk
        return {
            "seconds": t1 - t0, "steps": steps,
            "items": steps * self.cell.flops().items_per_step(self.cfg,
                                                              self.batch),
            "setup_s": t0 - t_process, "stamps": stamps}

    def release(self):
        import gc
        self.params = self.state = self.aux = self.ts = None
        self.first = self.pool = None
        gc.collect()
