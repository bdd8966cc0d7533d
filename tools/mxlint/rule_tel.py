"""TEL001 — telemetry emission discipline in hot paths.

The zero-overhead contract (docs/observability.md): with the telemetry
envs unset, the per-step hot path pays ONE module-global bool check per
site — never a tag-dict build, a clock read, or a string format.  The
enforced idiom is an explicit gate around every emission:

    if _tel._enabled:
        _tel.counter("fit_batches")

Inside the configured hot-path functions this rule flags telemetry /
wire-bytes emission calls —

    telemetry.counter/gauge/scalar/hist/record_span
    sanitize.record_wire_bytes

— that do not sit under such a gate (an ``if`` consulting the
environment, ``_tel._enabled`` / a ``telem`` snapshot of it,
``scalar_due``, or the sanitizer's ``_collective_on``).  The emission
functions DO no-op internally when disabled, but reaching that early
return still pays argument evaluation (tag dicts, ``nbytes_of`` sums)
on every step — exactly the cost the contract forbids.

``telemetry.span`` is not policed: it is the one primitive that is on by
design (it enters the profiler's ``TraceAnnotation`` whether or not the
registry records, about a microsecond), so a hot path opens it ungated
and has one body, recording or not.
"""
from __future__ import annotations

import ast

from . import astutil
from .core import Finding

RULE = "TEL001"

# qualnames of the hot-path bodies, per repo-relative file — the same
# per-step surfaces SYNC001 polices, plus the collective dispatch path
# that carries the wire-bytes ledger
HOT_PATHS = {
    "mxnet_tpu/module/base_module.py": ("BaseModule._fit_impl",
                                        "BaseModule.forward_backward"),
    "mxnet_tpu/module/module.py": ("Module.forward", "Module.backward",
                                   "Module.update"),
    "mxnet_tpu/module/executor_group.py": (
        "DataParallelExecutorGroup.forward",
        "DataParallelExecutorGroup.backward"),
    "mxnet_tpu/executor.py": ("Executor.forward", "Executor.backward"),
    "mxnet_tpu/train.py": ("TrainStep.__call__", "EvalStep.__call__",
                           "PipelineTrainStep.__call__", "gather_params"),
    "mxnet_tpu/serving.py": ("ServedModel._batch_loop",
                             "ServedModel._run_batch"),
    "mxnet_tpu/io.py": ("DevicePrefetchIter._producer", "_count_batch"),
    "mxnet_tpu/parallel/dist.py": ("allreduce_arrays",),
}

# telemetry-module emission entry points (resolved through the import
# table: ``from . import telemetry as _tel`` -> 'telemetry.counter')
_EMITS = ("counter", "gauge", "scalar", "hist", "record_span")

# identifiers that mark an opt-in telemetry/ledger branch; ``telem`` is
# the fit loop's local snapshot of ``_tel._enabled``
GATE_NAMES = ("_enabled", "enabled", "telem", "telemetry", "_tel",
              "scalar_due", "_collective_on", "flight_recorder_armed")


def _gate_test(fi, test):
    if astutil.mentions_env(fi, test):
        return True
    for n in ast.walk(test):
        if isinstance(n, ast.Name) and n.id in GATE_NAMES:
            return True
        if isinstance(n, ast.Attribute) and n.attr in GATE_NAMES:
            return True
    return False


def _early_return_guarded(fi, node):
    """The other sanctioned idiom — a dominating early return:

        if not _tel._enabled:
            return self._impl(...)
        with _tel.span(...): ...

    True when a preceding sibling ``if`` (at any enclosing block level)
    tests a gate and every path through its body leaves the block
    (return/raise/continue/break), so the emission only runs enabled."""
    cur = node
    for anc in fi.ancestors(node):
        for blk in ("body", "orelse", "finalbody"):
            stmts = getattr(anc, blk, None)
            if not isinstance(stmts, list) or cur not in stmts:
                continue
            for prev in stmts[:stmts.index(cur)]:
                if isinstance(prev, ast.If) and _gate_test(fi, prev.test) \
                        and prev.body and isinstance(
                            prev.body[-1], (ast.Return, ast.Raise,
                                            ast.Continue, ast.Break)):
                    return True
        cur = anc
    return False


def _emit_call(fi, n):
    """Display name of a telemetry/wire-bytes emission call, or None."""
    if not isinstance(n, ast.Call):
        return None
    d = fi.dotted(n.func)
    if d.startswith("telemetry.") and d.split(".", 1)[1] in _EMITS:
        return d
    if d == "sanitize.record_wire_bytes":
        return d
    return None


def run(project):
    findings = []
    for fi in project.files:
        wanted = HOT_PATHS.get(fi.rel)
        if not wanted:
            continue
        funcs = fi.functions()
        for q in wanted:
            node = funcs.get(q)
            if node is None:
                continue
            for n in ast.walk(node):
                what = _emit_call(fi, n)
                if what is None:
                    continue
                if astutil.under_env_guard(fi, n, extra_names=GATE_NAMES):
                    continue
                if _early_return_guarded(fi, n):
                    continue
                findings.append(Finding(
                    RULE, fi.rel, n.lineno, q,
                    "unguarded telemetry emission (%s) in hot path %s — "
                    "wrap it in `if _tel._enabled:` (or the ledger's "
                    "`_san._collective_on` gate) so the disabled path "
                    "pays one bool check, not argument evaluation"
                    % (what, q)))
    return findings
