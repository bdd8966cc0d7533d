"""Device context (parity: reference python/mxnet/context.py, include/mxnet/base.h:103-130).

A Context names a JAX device, and what it names does not depend on what
else is running:

- ``mx.cpu(i)`` / ``mx.cpu_pinned(i)`` is the host: device ``i`` of JAX's
  ``cpu`` backend.  Beside a chip that is the real host CPU (reference
  parity: data loading, f32 references), so the ``cpu`` backend must be
  initialised there — ``JAX_PLATFORMS=tpu,cpu``, not ``tpu`` alone.
- ``mx.tpu(i)`` is local device ``i`` of the default backend, and that
  backend must be a TPU.  ``mx.gpu(i)`` is an alias so reference example
  scripts run unchanged.  The one exception is the test harness: with
  ``JAX_PLATFORMS=cpu`` set explicitly (and
  ``--xla_force_host_platform_device_count=N``) the N virtual host devices
  stand in for chips, which is how multi-device semantics are tested
  without hardware.  Anywhere else a default backend that is not a TPU —
  no chip, or a chip held by another process — is an ``MXNetError``, never
  a silent move to the host.
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context"]

_DEVTYPE2ID = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}
_ID2DEVTYPE = {v: k for k, v in _DEVTYPE2ID.items()}


class Context(object):
    """A device context. ``Context('tpu', 0)`` or via helpers ``mx.tpu(0)``."""

    _default_ctx = threading.local()
    devtype2str = _ID2DEVTYPE
    devstr2type = _DEVTYPE2ID

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            if device_type not in _DEVTYPE2ID:
                raise MXNetError("unknown device type %s" % device_type)
            self.device_type = device_type
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_typeid(self):
        return _DEVTYPE2ID[self.device_type]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # -- JAX mapping ------------------------------------------------------
    def jax_device(self):
        """Resolve this context to a concrete jax.Device (see the module
        docstring for what each device type means)."""
        if self.device_type in ("cpu", "cpu_pinned"):
            devs = _host_devices()
        else:
            devs = _accelerator_devices(self)
        if self.device_id >= len(devs):
            raise MXNetError("no device for context %r: the %s backend has "
                             "%d local device(s)"
                             % (self, devs[0].platform, len(devs)))
        return devs[self.device_id]


# local_devices, not devices: under multi-process distributed training each
# process may only place data on its own addressable devices (global devices
# are reachable solely through collectives over the mesh).
def _host_devices():
    import jax
    try:
        return jax.local_devices(backend="cpu")
    except RuntimeError as e:
        raise MXNetError(
            "mx.cpu() needs JAX's host backend beside the accelerator, and "
            "JAX_PLATFORMS=%r leaves it out: use JAX_PLATFORMS=tpu,cpu on a "
            "machine with a chip (%s)"
            % (jax.config.jax_platforms, e)) from e


def cpu_harness():
    """True when JAX was explicitly held to the host (``JAX_PLATFORMS=cpu``
    or the equivalent ``jax.config`` update): the test harness, where
    virtual host devices stand in for chips."""
    import jax
    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def _accelerator_devices(ctx):
    import jax
    devs = jax.local_devices()
    if devs[0].platform != "tpu" and not cpu_harness():
        raise MXNetError(
            "context %r needs a TPU, but the default JAX backend is %r "
            "(JAX_PLATFORMS=%r): no chip is visible to this process, or "
            "another process holds it.  Only an explicit JAX_PLATFORMS=cpu "
            "(the test harness) maps %s contexts onto host devices."
            % (ctx, devs[0].platform, jax.config.jax_platforms,
               ctx.device_type))
    return devs


def announce_placement(who, contexts, logger):
    """Say where an entry point computes: one INFO line naming the resolved
    devices, and a WARNING when they are the host while the process has a
    TPU — the reference's default context is ``cpu()``, and beside a chip
    that trains and serves on the host unless ``mx.tpu(i)`` is passed."""
    import jax
    devs = [c.jax_device() for c in contexts]
    logger.info("%s: computing on %s = %s (%s)", who,
                ", ".join(str(c) for c in contexts),
                ", ".join(str(d) for d in devs), devs[0].device_kind)
    if devs[0].platform == "cpu" and jax.default_backend() == "tpu":
        logger.warning(
            "%s: context %s is the host CPU, but this process has a TPU "
            "(%s) — pass context=mx.tpu(0) (dev_type='tpu') to compute on "
            "the chip", who, contexts[0], jax.devices()[0].device_kind)


def cpu(device_id=0):
    """Return a CPU context (parity: mx.cpu)."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Accelerator alias (parity: mx.gpu); resolves exactly like ``mx.tpu``."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """First-class TPU context (north star: BASELINE.json mx.tpu())."""
    return Context("tpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def current_context():
    """The active default context (parity: mx.current_context)."""
    ctx = getattr(Context._default_ctx, "value", None)
    return ctx if ctx is not None else Context("cpu", 0)


Context.default_ctx = property(lambda self: current_context())
