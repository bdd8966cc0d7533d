"""Test harness: run everything on an 8-device virtual CPU mesh so multi-chip
sharding semantics are exercised without TPU hardware (the driver's
dryrun_multichip uses the same mechanism).  JAX_PLATFORMS=cpu set explicitly
is also what lets ``mx.tpu(i)`` resolve to virtual host device ``i``
(mxnet_tpu/context.py); the config update covers a jax imported before this
file ran.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
