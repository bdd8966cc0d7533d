"""RNG state (parity: reference python/mxnet/random.py, src/resource.cc kRandom).

TPU-first: a single splittable JAX PRNG key replaces per-device mshadow generators.
Every imperative sample op and every executor forward draws a fresh split, so results
are reproducible after ``mx.random.seed(s)`` regardless of async dispatch order —
stronger than the reference, whose parallel sampling is nondeterministic.
"""
from __future__ import annotations

import threading

__all__ = ["seed", "next_key"]

_state = threading.local()
_DEFAULT_SEED = 0


def _host():
    """Key bookkeeping runs on the host backend: the keys are 8 bytes, and a
    split per imperative sample op is not worth a device dispatch.  A step
    program receives the key as an ordinary (uncommitted) argument."""
    import jax
    from .context import _host_devices
    return jax.default_device(_host_devices()[0])


def _get():
    key = getattr(_state, "key", None)
    if key is None:
        import jax
        with _host():
            key = jax.random.PRNGKey(_DEFAULT_SEED)
        _state.key = key
    return _state.key


def seed(seed_state):
    """Seed the global generator (parity: mx.random.seed, MXRandomSeed)."""
    import jax
    with _host():
        _state.key = jax.random.PRNGKey(int(seed_state))


def next_key():
    """Draw a fresh subkey from the global stream."""
    import jax
    key = _get()
    with _host():
        key, sub = jax.random.split(key)
    _state.key = key
    return sub
