"""Roofline peaks and MFU arithmetic for per-program cost attribution.

The cost ledger (:mod:`mxnet_tpu.sanitize`) records what each compiled
program *costs* — model FLOPs, bytes accessed — but an efficiency claim
needs a denominator: the hardware's peak FLOP rate and memory bandwidth.
This module resolves that pair from what the process runs on:

- On a TPU backend, the device-kind table below (per-chip dense peak
  FLOP/s and HBM bandwidth, from published chip specs), and nothing else:
  a ``device_kind`` the table does not know is an ``MXNetError``, and
  ``MXNET_PEAK_FLOPS`` / ``MXNET_PEAK_BW`` cannot assert other peaks for a
  chip (a utilization figure against a made-up peak is worse than none).
- Off a TPU (the CPU harness), ``MXNET_PEAK_FLOPS`` / ``MXNET_PEAK_BW`` —
  explicit peaks (FLOP/s and bytes/s; SI suffixes K/M/G/T/P accepted,
  e.g. ``275T`` and ``1228G``) that arm the accounting so its arithmetic
  can be tested.  Either alone is honoured; MFU needs only FLOPS.  With
  neither set every consumer degrades to None — the strict no-op
  contract: no gauges, no roofline verdicts, no sentinel MFU watch.

Nothing here imports or initializes jax at module import; the device
probe runs only when a caller (the fused fit, diagnostics) asks after
the backend already exists.

Definitions (docs/observability.md "Cost attribution & MFU"):

- MFU            = (model FLOPs / step seconds) / peak FLOP/s
- intensity      = program FLOPs / bytes accessed       [FLOP/byte]
- ridge point    = peak FLOP/s / peak bytes/s           [FLOP/byte]
- a program is compute-bound when intensity >= ridge, else memory-bound
"""
from __future__ import annotations

from .base import MXNetError, get_env

__all__ = ["resolve_peaks", "enabled", "mfu", "ridge", "verdict",
           "DEVICE_PEAKS"]

# per-chip dense peak FLOP/s (bf16 where the MXU supports it) and HBM
# bandwidth in bytes/s, keyed by a lowercase substring of
# ``device.device_kind`` — checked most-specific first
DEVICE_PEAKS = (
    ("v5p",      459e12, 2765e9),
    ("v5 lite",  197e12,  819e9),
    ("v5e",      197e12,  819e9),
    ("v4",       275e12, 1228e9),
    ("v3",       123e12,  900e9),
    ("v2",        45e12,  700e9),
)

_SUFFIX = {"k": 1e3, "m": 1e6, "g": 1e9, "t": 1e12, "p": 1e15}

_cache = None             # (peak_flops|None, peak_bw|None) once resolved


def _parse_rate(raw):
    """``'275e12'`` / ``'275T'`` / ``'1228G'`` -> float, None on junk."""
    if raw is None:
        return None
    raw = str(raw).strip()
    if not raw:
        return None
    mult = 1.0
    if raw[-1].lower() in _SUFFIX:
        mult = _SUFFIX[raw[-1].lower()]
        raw = raw[:-1]
    try:
        val = float(raw) * mult
    except ValueError:
        return None
    return val if val > 0 else None


def _device_peaks():
    """(peak_flops, peak_bw) from the TPU device-kind table, or None off
    a TPU.  A TPU the table does not list is an error, not a null."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    kind = str(dev.device_kind).lower()
    for key, flops, bw in DEVICE_PEAKS:
        if key in kind:
            return (flops, bw)
    raise MXNetError(
        "cost: no roofline peaks for TPU device_kind %r — add its "
        "published per-chip peaks to cost.DEVICE_PEAKS (known: %s)"
        % (dev.device_kind, ", ".join(k for k, _, _ in DEVICE_PEAKS)))


def resolve_peaks(refresh=False):
    """The active ``(peak_flops, peak_bw)`` pair.  The device table on a
    TPU; the ``MXNET_PEAK_*`` variables (each possibly None) elsewhere.
    Cached after the first call (``refresh=True`` re-reads — tests)."""
    global _cache
    if _cache is not None and not refresh:
        return _cache
    peaks = _device_peaks()
    if peaks is None:
        peaks = (_parse_rate(get_env("MXNET_PEAK_FLOPS")),
                 _parse_rate(get_env("MXNET_PEAK_BW")))
    _cache = peaks
    return _cache


def enabled():
    """True when a peak FLOP rate is known (MFU is computable)."""
    return resolve_peaks()[0] is not None


def mfu(flops, seconds):
    """Model-FLOP utilization of one step, or None when peaks are unset
    or the inputs don't define a rate."""
    peak = resolve_peaks()[0]
    if peak is None or not flops or not seconds or seconds <= 0:
        return None
    return (float(flops) / float(seconds)) / peak


def ridge():
    """The machine ridge point in FLOP/byte, or None without both
    peaks."""
    flops, bw = resolve_peaks()
    if flops is None or bw is None or bw <= 0:
        return None
    return flops / bw


def verdict(intensity):
    """'compute-bound' | 'memory-bound' for a program's arithmetic
    intensity, or None when the ridge point is unknown."""
    r = ridge()
    if r is None or intensity is None:
        return None
    return "compute-bound" if float(intensity) >= r else "memory-bound"
