"""Layer: set-up (the package's import, jax's trace, lowering and compile of
every program, the persistent compile cache).  Read from the program's own
account, ``mxnet_tpu.sanitize.setup_account``, between the two ends of
``setup_s``: the process's start and the window's first dispatch.  So the
reference's compiles, which come after the window, are left out, and the
phases plus ``rest_s`` are ``setup_s``.  A program without the account (one
from before it) reads as nothing, and so does a context without a window."""
import sys


def _account(ctx):
    """``(account, setup_s)``, or None where there is nothing to read."""
    window = getattr(ctx, "window", None) or {}
    stamps = window.get("stamps")
    sanitize = sys.modules.get("mxnet_tpu.sanitize")
    read = getattr(sanitize, "setup_account", None)
    if read is None or stamps is None or len(stamps) == 0 \
            or "setup_s" not in window:
        return None
    until = float(stamps[0])
    setup_s = float(window["setup_s"])
    return read(since=until - setup_s, until=until), setup_s


def phase_s(ctx, phase):
    """Seconds of one phase (``import``, ``trace``, ``lower``,
    ``compile``) before the window; each instant counts once."""
    read = _account(ctx)
    return None if read is None else read[0][phase]


def cache_misses(ctx):
    """Compile requests the persistent cache did not answer before the
    window: 0 on a warm run."""
    read = _account(ctx)
    return None if read is None else float(read[0]["misses"])


def rest_s(ctx):
    """``setup_s`` less the four phases: process start, jax's import and
    backend, weights, the first steps and warm-up.  The phases are
    disjoint and cut to ``setup_s``'s interval, so this is never below 0;
    an account that says otherwise does not reconcile."""
    read = _account(ctx)
    if read is None:
        return None
    account, setup_s = read
    rest = setup_s - sum(account[p] for p in
                         ("import", "trace", "lower", "compile"))
    if rest < -1e-6:
        raise ctx.Inconsistent("set-up phases %.6f s over setup_s %.6f s"
                               % (setup_s - rest, setup_s))
    return max(rest, 0.0)
