"""BaseModule — the training API contract (parity: reference
python/mxnet/module/base_module.py:79-951, incl. the fit loop at :369-518)."""
from __future__ import annotations

import logging
import time

import numpy as np

from ..base import MXNetError, string_types
from .. import io as _io
from .. import metric as metric_mod
from .. import ndarray as nd
from .. import sanitize as _san
from ..model import BatchEndParam

__all__ = ["BaseModule"]


def _as_list(obj):
    if obj is None:
        return []
    return obj if isinstance(obj, list) else [obj]


def _lr_point(module, default_step):
    """(lr, step) for the fit loop's ``lr`` curve point, or (None, _).

    The step axis is the optimizer's UPDATE COUNT — the axis schedules
    are functions of and the one the scheduler's decay-boundary pins use
    (lr_scheduler._record_decay) — so a checkpoint-resumed run
    (begin_num_update > 0) keeps one consistent lr axis instead of
    folding back to 0.  On the fused fit path
    the live counter is the TrainStep's, not the optimizer's (which only
    syncs back at epoch end) — read it from the active fused trainer.
    Schedulers are pure functions of ``num_update``, so querying here is
    side-effect-free apart from their own decay-boundary logging."""
    opt = getattr(module, "_optimizer", None)
    if opt is None:
        return None, default_step
    ff = getattr(module, "_active_fused", None)
    num_update = ff._ts.num_update if ff is not None \
        else getattr(opt, "num_update", None)
    step = default_step if num_update is None else num_update
    sched = getattr(opt, "lr_scheduler", None)
    if sched is not None and num_update is not None:
        return sched(num_update), step
    return getattr(opt, "lr", None), step


def _check_input_names(symbol, names, typename, throw):
    """Verify declared data/label names exist in the symbol's arguments."""
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        candidates = [arg for arg in args if not arg.endswith("_weight")
                      and not arg.endswith("_bias") and not arg.endswith("_gamma")
                      and not arg.endswith("_beta")]
        msg = "\033[91mYou created Module with Module(..., %s_names=%s) but " \
              "input with name '%s' is not found in symbol.list_arguments(). " \
              "Did you mean one of:\n\t%s\033[0m" % (
                  typename, str(names), name, "\n\t".join(candidates))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


class BaseModule(object):
    """The module API: high-level (fit/predict/score) over intermediate
    (forward/backward/update) over low-level (bind/init_params)."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # ------------------------------------------------------------- high level
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Evaluate over a data iterator (parity surface:
        base_module.score).  Metric accumulation is lazy-on-device (see
        metric.EvalMetric), so the loop itself never syncs the host."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        eval_metric = metric_mod.create(eval_metric) \
            if not isinstance(eval_metric, metric_mod.EvalMetric) \
            else eval_metric
        eval_metric.reset()

        def notify(cbs, n, loc):
            # loc is the scoring loop's locals(): callbacks reach
            # eval_batch and loop state through param.locals (reference
            # BatchEndParam contract)
            for cb in _as_list(cbs or []):
                cb(BatchEndParam(epoch=epoch, nbatch=n,
                                 eval_metric=eval_metric, locals=loc))

        from .. import diagnostics as _diag
        seen = 0
        for eval_batch in eval_data:
            if num_batch is not None and seen == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if _diag._armed:
                # a long validation pass is progress, not a hang — keep
                # the watchdog fed between training epochs
                _diag.heartbeat(epoch=epoch, eval_nbatch=seen)
            notify(batch_end_callback, seen, locals())
            seen += 1
        if score_end_callback:
            notify(score_end_callback, seen, locals())
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """Yield (pred_outputs, i_batch, batch) (parity: iter_predict)."""
        from .. import diagnostics as _diag
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            if _diag._armed:
                # long inference passes are progress too (same contract
                # as the score() loop)
                _diag.heartbeat(predict_nbatch=nbatch)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Collect forward outputs over a data iterator, de-padded
        (parity surface: base_module.predict)."""
        # iter_predict yields views of the executor's output buffers, which
        # the NEXT batch's forward overwrites: own each batch's rows as it
        # is yielded, not after the loop
        per_batch = [[o.copy() for o in outs] for outs, _, _
                     in self.iter_predict(eval_data, num_batch=num_batch,
                                          reset=reset)]
        if not per_batch or not merge_batches:
            return per_batch
        widths = {len(outs) for outs in per_batch}
        if len(widths) != 1:
            raise MXNetError(
                "predict(merge_batches=True): batches produced differing "
                "output counts %s (bucketing?)" % sorted(widths))
        merged = [nd.concatenate([outs[i] for outs in per_batch])
                  for i in range(widths.pop())]
        if len(merged) == 1 and not always_output_list:
            return merged[0]
        return merged

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, policy=None):
        """The training loop (parity: base_module.fit:369-518).  When the
        diagnostics layer is active (MXNET_WATCHDOG_SEC /
        MXNET_CHECK_NUMERICS / MXNET_DIAG_DIR — docs/observability.md),
        any exception escaping the loop leaves a forensic bundle behind
        before re-raising.

        ``policy`` (amp.Policy | True | dtype string; default: consult
        MXNET_AMP) selects mixed-precision training on the fused fast
        path — bf16 compute, f32 master weights, dynamic loss scaling
        (docs/perf.md "Mixed precision & input pipeline")."""
        from .. import diagnostics as _diag
        try:
            return self._fit_impl(
                train_data, eval_data=eval_data, eval_metric=eval_metric,
                epoch_end_callback=epoch_end_callback,
                batch_end_callback=batch_end_callback, kvstore=kvstore,
                optimizer=optimizer, optimizer_params=optimizer_params,
                eval_end_callback=eval_end_callback,
                eval_batch_end_callback=eval_batch_end_callback,
                initializer=initializer, arg_params=arg_params,
                aux_params=aux_params, allow_missing=allow_missing,
                force_rebind=force_rebind, force_init=force_init,
                begin_epoch=begin_epoch, num_epoch=num_epoch,
                validation_metric=validation_metric, monitor=monitor,
                policy=policy)
        except BaseException as exc:
            # BaseException: Ctrl-C on a stalled fit is the most common
            # forensic moment of all — it must leave a bundle too
            _diag.crash_snapshot(exc, where="module.fit")
            raise

    def _fit_impl(self, train_data, *, eval_data, eval_metric,
                  epoch_end_callback, batch_end_callback, kvstore,
                  optimizer, optimizer_params, eval_end_callback,
                  eval_batch_end_callback, initializer, arg_params,
                  aux_params, allow_missing, force_rebind, force_init,
                  begin_epoch, num_epoch, validation_metric, monitor,
                  policy):
        # no defaults here on purpose: fit() owns the public signature and
        # always passes every argument — one source of truth
        assert num_epoch is not None, "please specify number of epochs"
        from .. import initializer as init_mod
        if initializer is None:
            initializer = init_mod.Uniform(0.01)

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)

        # fused fast path (Module only): forward+backward+update as one
        # donated XLA program per batch — see Module._start_fused_fit
        # (which also resolves the mixed-precision policy / MXNET_AMP,
        # and serves a default-stat Monitor from the step's on-device
        # numerics stats instead of forcing the general path)
        fast = getattr(self, "_start_fused_fit",
                       lambda policy=None, monitor=None: None)(
                           policy=policy, monitor=monitor)
        if fast is None:
            if monitor is not None:
                # general path: per-op observation through the executor
                # callback (the fused path has no executors to hook)
                self.install_monitor(monitor)
            from .. import amp as _amp
            if _amp.resolve_policy(policy) is not None:
                # never train f32 silently while the operator believes
                # bf16 — covers monitor-forced and non-Module fits, where
                # _start_fused_fit's own fallback note can't fire
                self.logger.warning(
                    "fit: mixed-precision policy (MXNET_AMP/policy=) "
                    "ignored — the general path trains f32%s",
                    " (a custom Monitor stat_func forces the general "
                    "path)" if monitor is not None else "")

        from .. import telemetry as _tel
        from .. import diagnostics as _diag
        from .. import sentinel as _sen
        from .. import cost as _cost
        # sentinel mode is read once per fit(), not per batch; None (the
        # default) keeps the loop body free of any numerics work
        check_mode = _diag.check_numerics_mode()
        # per-step MFU: only when roofline peaks resolve (MXNET_PEAK_FLOPS
        # or the TPU device-kind table) and the timed path is live to
        # carry the gauges.  Arming cost attribution here is what lets
        # the fused step's first dispatch capture its FLOP count — the
        # MFU numerator.  Peaks unset keeps all of this strictly off.
        mfu_on = False
        peak_flops = None
        if fast is not None and (_tel._enabled or _sen._on) \
                and _cost.enabled():
            _san.cost_arm()
            mfu_on = True
            peak_flops = _cost.resolve_peaks()[0]
        # batch axis for sample counting: time-major iterators (layout
        # 'TN') put batch on axis 1, so shape[0] would count timesteps
        _desc0 = (train_data.provide_data or [None])[0]
        _batch_axis = max(0, _io.DataDesc.get_batch_axis(
            getattr(_desc0, "layout", None))) if _desc0 is not None else 0

        # global batch index across the whole fit (epochs don't reset it):
        # the step axis of the training-curve scalars, so run_compare can
        # align two runs' curves point by point
        gstep = 0
        for epoch in range(begin_epoch, num_epoch):
            tic = time.time()
            eval_metric.reset()
            nbatch = 0
            epoch_samples = 0
            data_iter = iter(train_data)
            if fast is not None:
                # device-side double buffering: batch N+1's host->HBM
                # transfer is issued while step N computes; the data_wait
                # span below then times only the residual queue wait
                # (MXNET_DEVICE_PREFETCH=0 restores the synchronous path)
                data_iter = fast.prefetch(data_iter)
            try:
                while True:
                    # ONE loop body, recording or not: every span is the
                    # profiler's annotation (telemetry.span; an atomic check
                    # while nobody traces), and recording changes neither the
                    # path nor the dispatch.  Under `telem` sit only what the
                    # registry alone consumes: counters, scalars, the
                    # whole-batch `step` event, the sentinel and MFU folds.
                    # `batch` is the iteration (the parent whose self time a
                    # trace gives); the rest, cat "step", are its phases,
                    # which the step-anatomy tools add up against `step`.
                    telem = _tel._enabled
                    tags = {"epoch": epoch, "nbatch": nbatch}
                    if telem:
                        # live sentinel (sentinel.py): arming it armed at
                        # least the flight recorder, so `telem` is on
                        sent = _sen._on and _sen._detect
                        step_wall = time.time()
                        step_t0 = time.perf_counter()
                    with _tel.span("batch", cat="fit", **tags) as bsp:
                        # the iterator fetch is timed separately so the
                        # breakdown distinguishes input starvation from compute
                        with _tel.span("data_wait", cat="step", **tags) as dsp:
                            try:
                                data_batch = next(data_iter)
                            except StopIteration:
                                dsp.cancel()
                                bsp.cancel()
                                break
                        if telem and sent:
                            # the sentinel's whole added cost on the hot
                            # path: two perf_counter reads per step
                            c0 = time.perf_counter()
                            dw_s = c0 - step_t0
                        if monitor is not None:
                            monitor.tic()
                            if fast is not None:
                                # bridge: an armed tic() force-samples the
                                # step's on-device stats for this batch
                                fast.monitor_tic(monitor)
                        if fast is not None:
                            with _tel.span("fused_step", cat="step", **tags):
                                outputs, dev_labels = fast.step(data_batch)
                            with _tel.span("metric", cat="step", **tags):
                                eval_metric.update(
                                    dev_labels or data_batch.label, outputs)
                        else:
                            if type(self).forward_backward is not \
                                    BaseModule.forward_backward:
                                # a subclass hooked the public forward_backward
                                # extension point — ONE span (it can't be split
                                # from outside)
                                with _tel.span("forward_backward", cat="step",
                                               **tags):
                                    self.forward_backward(data_batch)
                            else:
                                with _tel.span("forward", cat="step", **tags):
                                    self.forward(data_batch, is_train=True)
                                with _tel.span("backward", cat="step", **tags):
                                    self.backward()
                            if check_mode is not None:
                                # non-finite sentinel BEFORE update(): `raise`
                                # halts with the weights still clean, naming
                                # this batch
                                try:
                                    _diag.check_fit_step(self, epoch, nbatch,
                                                         check_mode)
                                except _diag.NonFiniteError:
                                    if monitor is not None:
                                        # surface the armed batch's per-tensor
                                        # rows (Monitor names the first bad
                                        # tensor) before the halt discards
                                        # them; the monitor's own raise must
                                        # not displace the batch-context error
                                        try:
                                            monitor.toc_print()
                                        except _diag.NonFiniteError:
                                            pass
                                    raise
                            with _tel.span("update", cat="step", **tags):
                                self.update()
                            with _tel.span("metric", cat="step", **tags):
                                self.update_metric(eval_metric,
                                                   data_batch.label)
                        if telem and sent:
                            # compute-exclusive phase ends here; monitor dumps,
                            # numerics checks, heartbeats and callbacks below
                            # fold into the sentinel's "stall" residual
                            comp_s = time.perf_counter() - c0
                        if monitor is not None:
                            if fast is not None:
                                # bridge: rows for toc() from the sampled
                                # step's published stats (parameter RMS)
                                fast.monitor_feed(monitor)
                            monitor.toc_print()
                        if fast is not None and check_mode is not None:
                            # fused path: update is inside the donated XLA
                            # program, so the check runs on the step's outputs
                            # afterwards
                            _diag.check_fit_step(self, epoch, nbatch, check_mode,
                                                 outputs=outputs,
                                                 check_grads=False)
                        if _diag._armed:
                            # step heartbeat: the watchdog counts silence from
                            # the last completed batch
                            _diag.heartbeat(epoch=epoch, nbatch=nbatch)
                        if telem:
                            # counters advance before callbacks so the
                            # Speedometer reads a sample position that includes
                            # this batch; padded rows of a final short batch
                            # aren't real samples
                            bs = data_batch.data[0].shape[_batch_axis] \
                                if data_batch.data else 0
                            bs -= getattr(data_batch, "pad", None) or 0
                            epoch_samples += bs
                            _tel.counter("fit_batches")
                            _tel.counter("fit_samples", bs)
                            if _tel.scalar_due(gstep):
                                # training-curve points: the metric's running
                                # values and the current lr.  get_name_value()
                                # reduces on device and syncs scalars — the
                                # cost MXNET_SCALARS_EVERY exists to bound.  No
                                # epoch tag: tags are series identity, and one
                                # curve must not shatter into per-epoch series
                                for mname, mval in \
                                        eval_metric.get_name_value():
                                    _tel.scalar("train_%s" % mname, gstep, mval)
                                lr, lr_step = _lr_point(self, gstep)
                                if lr is not None:
                                    _tel.scalar("lr", lr_step, lr)
                                amp = fast.amp_stats() if fast is not None \
                                    else None
                                if amp is not None:
                                    # a collapsing loss scale shows up as a
                                    # curve (run_compare-visible), the gauge
                                    # feeds the live endpoint, the counter
                                    # names how many updates were skipped
                                    _tel.scalar("train_loss_scale", gstep,
                                                amp[0])
                                    _tel.gauge("loss_scale", amp[0])
                                    if amp[1]:
                                        _tel.counter("amp_overflow_steps",
                                                     amp[1])
                                        if _sen._on:
                                            # an overflow burst legitimately
                                            # perturbs every watched series —
                                            # quiet window, not an anomaly
                                            _sen.note_overflow()
                        if batch_end_callback is not None:
                            with _tel.span("callback", cat="step", **tags):
                                batch_end_params = BatchEndParam(
                                    epoch=epoch, nbatch=nbatch,
                                    eval_metric=eval_metric, locals=locals())
                                for callback in _as_list(batch_end_callback):
                                    callback(batch_end_params)
                        if telem:
                            # whole-batch wall time: data_wait + dispatch +
                            # callbacks.  Nothing here waits for the device, so
                            # one iteration is the host's time until the
                            # runtime's queue pushes back; it equals the
                            # device's step only on average over many batches
                            total_s = time.perf_counter() - step_t0
                            _tel.record_span("step", step_wall, total_s,
                                             cat="step", **tags)
                            mfu = None
                            if mfu_on and total_s > 0:
                                # the MFU fold: ledger FLOPs over this
                                # iteration's wall time (see above: a mean
                                # over batches is the number, one batch is
                                # not), against the resolved peak.  The cost
                                # row appears at the step program's first
                                # dispatch (this very loop), so the gauges
                                # start on step 1.
                                flops = fast.step_flops()
                                if flops:
                                    achieved = flops / total_s
                                    mfu = achieved / peak_flops
                                    _tel.gauge("model_flops", flops)
                                    _tel.gauge("achieved_flops",
                                               round(achieved, 3))
                                    _tel.gauge("mfu", round(mfu, 4))
                            if sent:
                                # fold the step into the rolling baseline and
                                # run the anomaly check (sentinel.step_close
                                # derives comm from the wire-ledger delta and
                                # stall as the residual; may warn or raise a
                                # SentinelError in :raise mode).  MFU joins
                                # the watched series when computed above.
                                _sen.step_close(total_s, dw_s, comp_s,
                                                epoch=epoch, nbatch=nbatch,
                                                mfu=mfu,
                                                grad_norm=(fast.grad_norm()
                                                           if fast is not None
                                                           else None))
                        # live-resize membership gate (parallel/resize.py,
                        # installed by fit_elastic under the --elastic
                        # supervisor): a step BOUNDARY is the quiesce point —
                        # the optimizer step above fully committed, the next
                        # one has not begun, so a world transition here
                        # re-shards a consistent state and the loop resumes
                        # on the same (rebuilt-in-place) fast engine
                        rz = getattr(self, "_resize_controller", None)
                        if rz is not None:
                            rz.step_gate(fast, epoch=epoch, nbatch=nbatch)
                    nbatch += 1
                    gstep += 1

            finally:
                # a mid-epoch exception (sentinel raise, callback
                # error, Ctrl-C) must not leave the prefetch producer
                # blocked in queue.put holding staged device batches
                drain = getattr(data_iter, "drain", None)
                if drain is not None:
                    drain()
            for name, val in eval_metric.get_name_value():
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            toc = time.time()
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))
            if _tel._enabled:
                _tel.counter("fit_epochs")
                _tel.gauge("epoch_time", toc - tic, epoch=epoch)
                _tel.record_span("epoch", tic, toc - tic, cat="epoch",
                                 epoch=epoch, batches=nbatch,
                                 samples=epoch_samples)
                if epoch_samples and toc > tic:
                    # epoch-level throughput point; the Speedometer's
                    # in-epoch `throughput` scalar has finer grain but
                    # only exists when the callback is installed
                    _tel.scalar("samples_per_sec", gstep,
                                epoch_samples / (toc - tic))
                # per-epoch device-memory trajectory (live-array stats;
                # host-side bookkeeping, no device sync)
                _diag.sample_device_memory(epoch=epoch)

            if _diag._armed:
                # beat BEFORE the epoch-end work (param sync-back,
                # checkpoint callbacks), like dist does before a
                # collective: a dump during a slow checkpoint then names
                # the phase in flight instead of the last batch
                _diag.heartbeat(epoch=epoch, phase="epoch_end")
            if _san._collective_on:
                # epoch-boundary hash-chain exchange (the other exchange
                # points are barrier entries): ranks whose collective
                # dispatch streams diverged during the epoch are named
                # here with the first divergent ledger entry, before the
                # next epoch's collectives can deadlock on the skew
                _san.collective_sync("epoch%d" % epoch)
            # parameters synced back, epoch callbacks (checkpoints), score
            with _tel.span("epoch_end", cat="epoch", epoch=epoch):
                if fast is not None:
                    fast.sync_back()
                arg_params_, aux_params_ = self.get_params()
                self.set_params(arg_params_, aux_params_)
                if epoch_end_callback is not None:
                    for callback in _as_list(epoch_end_callback):
                        callback(epoch, self.symbol, arg_params_, aux_params_)

                if eval_data:
                    res = self.score(eval_data, validation_metric,
                                     score_end_callback=eval_end_callback,
                                     batch_end_callback=eval_batch_end_callback,
                                     epoch=epoch)
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f", epoch,
                                         name, val)
                        if _tel._enabled:
                            # per-epoch eval curve, on the same step axis as
                            # the train_* scalars (never sampled away —
                            # epoch-end points are rare and load-bearing)
                            _tel.scalar("val_%s" % name, gstep, val)
            train_data.reset()

    # ------------------------------------------------------------ param API
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        nd.save(fname, save_dict)

    def load_params(self, fname):
        save_dict = nd.load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return []

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        assert not states and not value

    def install_monitor(self, mon):
        raise NotImplementedError()

    # ----------------------------------------------------------- computation
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    # ----------------------------------------------------------------- setup
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()
