"""What the chip bring-up added, checked on the CPU harness: device
resolution (``mx.cpu`` is the host, ``mx.tpu`` the default backend or an
error — never a silent host fallback), the placeable compile cache, the
launcher's one-chip-per-rank env, and ``chip_smoke.py``'s phases at a tiny
size.  The full-size run is ``python chip_smoke.py`` on the chip."""
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu import base, context
from mxnet_tpu.base import MXNetError

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))
sys.path.insert(0, ROOT)


# ------------------------------------------------------- context resolution
class _FakeDev(object):
    def __init__(self, platform, i=0):
        self.platform, self.id, self.device_kind = platform, i, platform


def test_cpu_is_the_host_and_tpu_the_default_backend_under_the_harness():
    import jax
    assert mx.cpu(1).jax_device() is jax.local_devices(backend="cpu")[1]
    # conftest set JAX_PLATFORMS=cpu explicitly: virtual devices are chips
    assert context.cpu_harness()
    assert mx.tpu(3).jax_device() is jax.local_devices()[3]
    assert mx.gpu(3).jax_device() is mx.tpu(3).jax_device()
    with pytest.raises(MXNetError, match="8 local device"):
        mx.tpu(8).jax_device()


def test_tpu_context_never_falls_back_to_the_host(monkeypatch):
    """Default backend is the CPU WITHOUT the explicit harness setting (no
    chip, or a chip another process holds): an error, not a host device."""
    import jax
    monkeypatch.setattr(context, "cpu_harness", lambda: False)
    for ctx in (mx.tpu(0), mx.gpu(0)):
        with pytest.raises(MXNetError, match="needs a TPU"):
            ctx.jax_device()
    assert mx.cpu(0).jax_device().platform == "cpu"   # the host still is
    # with a TPU as the default backend it resolves there, harness or not
    chips = [_FakeDev("tpu", i) for i in range(2)]
    monkeypatch.setattr(
        jax, "local_devices",
        lambda backend=None: chips if backend is None else [_FakeDev("cpu")])
    assert mx.tpu(1).jax_device() is chips[1]
    assert mx.cpu(0).jax_device().platform == "cpu"


def test_cpu_context_names_the_missing_host_backend(monkeypatch):
    """JAX_PLATFORMS=tpu alone leaves out the host backend mx.cpu() and the
    RNG key bookkeeping need; the failure says which value to use."""
    import jax

    def only_tpu(backend=None):
        if backend == "cpu":
            raise RuntimeError("Unknown backend cpu")
        return [_FakeDev("tpu")]
    monkeypatch.setattr(jax, "local_devices", only_tpu)
    with pytest.raises(MXNetError, match="JAX_PLATFORMS=tpu,cpu"):
        mx.cpu(0).jax_device()
    with pytest.raises(MXNetError, match="JAX_PLATFORMS=tpu,cpu"):
        mx.random.seed(0)


# ------------------------------------------------------------ compile cache
_CACHE_PROBE = """
import os, sys
sys.path.insert(0, %r)
import jax
from mxnet_tpu.base import enable_compile_cache
got = enable_compile_cache()
print(got == jax.config.jax_compilation_cache_dir, got,
      jax.config.jax_persistent_cache_min_compile_time_secs)
""" % ROOT


def _cache_probe(env_dir, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         cwd=cwd, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_compile_cache_is_placed_from_outside_or_fixed(tmp_path):
    # variable set: jax reads it, the helper names no directory of its own
    outside = str(tmp_path / "outside")
    assert _cache_probe(outside, ROOT) == ["True", outside, "0.0"]
    # unset: ONE fixed path inside the checkout — the same in this process
    # and in another one started from another directory
    fixed = os.path.join(ROOT, ".jax_cache")
    assert base.COMPILE_CACHE_DIR == fixed
    assert _cache_probe(None, str(tmp_path)) == ["True", fixed, "0.0"]
    # and git ignores it
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ----------------------------------------------------- launcher: one chip/rank
def test_launch_gives_each_local_rank_its_own_chip(monkeypatch):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import launch
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 4)
    envs = [launch.local_chip_env(r, 2) for r in range(2)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 2
    assert all(e["TPU_PROCESS_BOUNDS"] == "2,1,1"
               and e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               for e in envs)
    assert launch.local_chip_env(0, 1) == {}       # one rank drives them all
    for bad in (3, 8):          # no grid / more ranks than chips: refused
        with pytest.raises(SystemExit, match="cannot seat"):
            launch.local_chip_env(0, bad)
    # the CPU harness and hosts without chips keep today's shared env
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launch.local_chip_env(1, 3) == {}
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 0)
    assert launch.local_chip_env(1, 3) == {}


# ------------------------------------------------------------ chip_smoke.py
def test_chip_smoke_phases_tiny():
    """Every phase of chip_smoke.py — fused Module.fit, run_steps,
    checkpoint -> Server -> concurrent requests vs Module.predict, and the
    dp step over all (virtual) devices — at a tiny size, expecting the
    platform the harness provides."""
    import chip_smoke
    tiny = dict(num_layers=8, image=16, classes=10, batch=4, fit_batches=2,
                fit_epochs=1, chunk=2, requests=6, serve_max_batch=2)
    chip_smoke.run("cpu", **tiny)
    # a phase that finds its arrays elsewhere than expected fails
    with pytest.raises(chip_smoke.SmokeFailure, match="expected platform"):
        chip_smoke.phase_run_steps(dict(chip_smoke.FULL, **tiny), "tpu")


def test_chip_smoke_main_expects_a_tpu_unconditionally(tmp_path):
    """__main__ has no flag or variable that relaxes the platform: under
    the harness (JAX_PLATFORMS=cpu) it exits non-zero before any phase and
    prints no result line."""
    out = subprocess.run([sys.executable, os.path.join(ROOT,
                                                       "chip_smoke.py")],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout and '"ok"' not in out.stdout
    assert "before any phase" in out.stderr
