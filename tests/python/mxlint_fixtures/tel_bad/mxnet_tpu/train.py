"""TEL001 seeded violations: ungated telemetry emission on the hot path."""
from . import sanitize as _san
from . import telemetry as _tel


class TrainStep(object):
    def __call__(self, params, batch):
        loss, grads = self._step(params, batch)
        _tel.counter("train_steps")                     # ungated: finding
        _tel.gauge("loss_scale", self.scale)            # ungated: finding
        with _tel.span("train_step", cat="executor"):   # ungated by design
            res = self._finish(loss, grads)
        return res


class EvalStep(object):
    def __call__(self, params, batch):
        out = self._fwd(params, batch)
        _tel.scalar("val_loss", self.step, 0.0)         # ungated: finding
        return out


def gather_params(params, plan):
    _san.record_wire_bytes("mxtpu_zero_gather", axes="dp",  # ungated
                           nbytes=sum(plan.values()))
    return params
