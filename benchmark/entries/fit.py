"""Traffic entry ``fit``: one ``Module.fit`` call drives the first steps, the
warm-up and the window.  Its batches come from a pool made from the seed,
which lies on the device and is cycled through an epoch of the data set's
length by an iterator of the benchmark's own (input staging is bypassed).
The loop is the program's own; the benchmark only listens at the batch-end
callback: it checks that the fused step ran, reads the first steps, takes a
time, and ends the call when the window's batches are done."""
import math
import time

import numpy as np

from benchmark import gen, observe


class WindowClosed(Exception):
    """Raised from the batch-end callback to end ``Module.fit``."""


def block(tree):
    import jax
    from mxnet_tpu.ndarray import NDArray
    jax.block_until_ready([x.value if isinstance(x, NDArray) else x
                           for x in jax.tree_util.tree_leaves(
                               tree, is_leaf=lambda l: isinstance(l, NDArray))])


def device_pool_iter(mx, data, label, names, epoch_batches):
    """A ``DataIter`` that hands out, in turn, batches that already lie on
    the device: ``mx.io.NDArrayIter`` copies whatever it is given to the
    host, so feeding from the device needs an iterator of the benchmark's
    own.  An epoch has ``epoch_batches`` batches; the pool is cycled."""
    class DevicePoolIter(mx.io.DataIter):
        def __init__(self):
            super().__init__(int(data.shape[1]))
            ctx = mx.tpu(0)
            self.pool = [mx.io.DataBatch(
                [mx.nd.NDArray(data[i], ctx=ctx)],
                [mx.nd.NDArray(label[i], ctx=ctx)], pad=0)
                for i in range(int(data.shape[0]))]
            self.provide_data = [mx.io.DataDesc(names[0],
                                                tuple(data.shape[1:]))]
            self.provide_label = [mx.io.DataDesc(names[1],
                                                 tuple(label.shape[1:]))]
            self.cur = 0

        def reset(self):
            self.cur = 0

        def next(self):
            if self.cur >= epoch_batches:
                raise StopIteration
            self.cur += 1
            return self.pool[(self.cur - 1) % len(self.pool)]
    return DevicePoolIter()


class Entry:
    def __init__(self, cell, seed, seconds, tracer=None):
        self.cell, self.seed, self.seconds = cell, seed, float(seconds)
        self.tracer = tracer
        self.tr = cell.traffic
        self.cfg = cell.config
        self.batch = int(self.tr["batch"])
        self.observed = None
        self.on_window_start = lambda: None

    # ------------------------------------------------------------- set-up
    def build(self):
        import importlib
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu import amp
        from benchmark.reference.train import family
        cfg, tr = self.cfg, self.tr
        self.shapes = family(cfg).param_shapes(cfg)
        sym_mod = importlib.import_module(cfg["symbol"]["module"])
        net = sym_mod.get_symbol(**cfg["symbol"]["args"])
        d = cfg["data"]
        names = (d["name"], cfg["label"]["name"])
        pool, epoch = int(tr["pool_batches"]), int(tr["epoch_batches"])
        # an epoch as long as the data set's (ImageNet: 1.28M images); the
        # pool is cycled inside it, so that the epoch's end (parameters
        # synced back to the host) comes as rarely as in a real job
        self.x, self.y = gen.device_images(
            self.seed, pool, self.batch, cfg["image_shape"],
            cfg["num_classes"], d["low"], d["high"])
        self.it = device_pool_iter(mx, self.x, self.y, names, epoch)
        self.key = gen._key(self.seed)
        w0 = jax.device_get(gen.make_weights(self.shapes, cfg["init"],
                                             self.seed))
        self.mod = mx.Module(net, context=mx.tpu(0), data_names=names[:1],
                             label_names=names[1:])
        self.mod.bind(data_shapes=self.it.provide_data,
                      label_shapes=self.it.provide_label)
        have = {k: tuple(v.shape) for k, v in
                self.mod._exec_group.execs[0].arg_dict.items()
                if k in self.shapes}
        want = {k: tuple(v) for k, v in self.shapes.items()}
        if have != want:
            raise SystemExit("the program's parameters are not the "
                             "reference's: %s" % sorted(
                                 set(have.items()) ^ set(want.items()))[:6])
        self.arg_params = {k: mx.nd.array(v) for k, v in w0.items()}
        self.policy = amp.Policy(cfg["precision"]["compute"])
        opt = dict(cfg["optimizer"])
        self.opt_name = opt.pop("name")
        self.opt_params = opt
        self.loss_fn = observe.mean_loss_fn()
        splits = getattr(family(cfg), "SPLIT", None)
        self.grad_fn = observe.grad_norms_fn(self.shapes, cfg["init"],
                                             cfg["optimizer"], splits)
        self.moment_fn = observe.moment_norms_fn(self.shapes, splits)
        self.change_fn = observe.change_norms_fn(self.shapes, cfg["init"],
                                                 splits)

    def reference_batches(self):
        """The first steps' batches, for the reference, on the device."""
        import jax.numpy as jnp
        return [(jnp.asarray(self.x[i]), jnp.asarray(self.y[i]))
                for i in range(int(self.tr["check_steps"]))]

    # ------------------------------------------------- the one fit() call
    def run(self, t_process):
        """Set-up's steps and the window, through one ``Module.fit``.
        Returns the window: seconds, batches, items, callback times."""
        tr = self.tr
        checks = int(tr["check_steps"])
        warm_end = checks + int(tr["warmup_batches"])
        calib_end = warm_end + int(tr["calibrate_batches"])
        obs = {"loss": {}}
        st = {"g": 0, "t_a": None, "end": None, "t0": None, "t1": None}
        mod = self.mod
        stamps = np.zeros(200000)

        def each_batch(param):
            g = st["g"]
            st["g"] = g + 1
            fast = param.locals["fast"]
            if fast is None or mod._active_fused is not fast:
                raise SystemExit("fit batch %d ran on the general executor "
                                 "path, not the fused step" % g)
            if g < checks:
                outs = param.locals["outputs"]
                label = param.locals["dev_labels"] \
                    or param.locals["data_batch"].label
                obs["loss"][g + 1] = self.loss_fn(outs[0].value,
                                                  label[0].value)
                if g == 0:
                    obs["grad"] = self.grad_fn(fast._state, self.key)
                if g == checks - 1:
                    obs["moment"] = self.moment_fn(fast._state)
                    obs["change"] = self.change_fn(fast._params, self.key)
                return
            if st["end"] is not None:
                i = g - calib_end          # batches of the window done - 1
                stamps[i] = time.perf_counter()
                if self.tracer is not None:
                    self.tracer.batch_boundary()
                if g == st["end"]:
                    block((param.locals["outputs"],
                           param.eval_metric.sum_metric))
                    st["t1"] = time.perf_counter()
                    if self.tracer is not None:
                        self.tracer.stop()
                    raise WindowClosed()
                return
            if g == warm_end:
                block(param.locals["outputs"])
                st["t_a"] = time.perf_counter()
            elif g == calib_end:
                block(param.locals["outputs"])
                per = (time.perf_counter() - st["t_a"]) \
                    / (calib_end - warm_end)
                n = max(2, int(math.ceil(self.seconds / per)))
                st["end"] = calib_end + n
                st["n"] = n
                self.observed = observe.to_host(obs)
                if self.tracer is not None:
                    self.tracer.start()
                    block(param.locals["outputs"])
                self.on_window_start()
                st["t0"] = time.perf_counter()
                if self.tracer is not None:
                    self.tracer.batch_boundary()

        try:
            mod.fit(self.it, num_epoch=10 ** 9, eval_metric=tr["eval_metric"],
                    optimizer=self.opt_name, optimizer_params=self.opt_params,
                    arg_params=self.arg_params, aux_params=None,
                    allow_missing=True, initializer=None,
                    policy=self.policy, batch_end_callback=each_batch)
        except WindowClosed:
            pass
        n = st["n"]
        return {
            "seconds": st["t1"] - st["t0"], "steps": n,
            "items": n * self.cell.flops().items_per_step(self.cfg,
                                                          self.batch),
            "setup_s": st["t0"] - t_process,
            "stamps": np.concatenate([[st["t0"]], stamps[1:n], [st["t1"]]]),
        }

    def release(self):
        """Free what the program holds on the device, before the reference
        runs there."""
        import gc
        self.mod = self.it = self.arg_params = self.x = self.y = None
        gc.collect()
