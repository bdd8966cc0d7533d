"""CKEY001 — jit cache-key completeness.

The PR-7 bug class, caught statically: a jit cache whose traced body
consults an env lever that its key expression does not carry silently
reuses the program compiled under the old value (and its dual — step
state in the key — recompiles forever; mxsan's RECOMPILE checker owns
that dynamic half).  This rule generalizes JIT001's executor-only
``TRACE_ENV_DEFAULTS`` exemption into a per-cache contract: for every
registered jit cache, each ``get_env`` read *reachable from a function
whose jit lands in that cache* must appear in that cache's key
expression.

A cache's key expression "covers" a var when the key-building function
reads it directly (``get_env("MXNET_X")``) or snapshots the shared
trace-env registry (``base.trace_env_key()`` — expands to every var in
``TRACE_ENV_DEFAULTS``).

``CACHES`` mirrors the repo's ``sanitize.register_cache`` call sites the
way SYNC001's ``HOT_PATHS`` mirrors its hot loops; entries whose files
are absent from the analyzed tree are skipped, so fixture trees carrying
only ``mxnet_tpu/executor.py`` exercise the rule in isolation.  The
serving rung ladder is registered with no traced roots on purpose: its
rung Predictors bind Executors, so their jits land in (and are keyed by)
the executor cache — the PR-9 audit found no sibling bug there, and
``EvalStep`` holds no cross-call cache at all (one jit per instance,
config frozen at construction by contract).
"""
from __future__ import annotations

import ast

from . import astutil
from .core import Finding

RULE = "CKEY001"

# Each registered jit cache: where its key is built, and the traced
# roots whose env reads the key must cover.  Roots may live in OTHER
# files than the key (the fused-fit cache keys programs that trace
# executor._Lowered.run).  roots == "ops" means every registered
# operator body under mxnet_tpu/ops/ (the imperative dispatch cache).
CACHES = (
    {"name": "executor._jit_cache",
     "key": ("mxnet_tpu/executor.py", "Executor._get_jit"),
     "roots": (("mxnet_tpu/executor.py", "_Lowered.run"),
               ("mxnet_tpu/executor.py", "Executor._walk"))},
    {"name": "ops.registry._JIT_CACHE",
     "key": ("mxnet_tpu/ops/registry.py", "jitted"),
     "roots": "ops"},
    {"name": "module fused-fit TrainStep cache",
     "key": ("mxnet_tpu/module/module.py", "_fused_fit_key_fields"),
     "roots": (("mxnet_tpu/executor.py", "_Lowered.run"),)},
    {"name": "TrainStep._multi_cache",
     "key": ("mxnet_tpu/train.py", "TrainStep.run_steps"),
     "roots": (("mxnet_tpu/executor.py", "_Lowered.run"),)},
    {"name": "PipelineTrainStep._progs",
     "key": ("mxnet_tpu/train.py", "PipelineTrainStep._get_prog"),
     "roots": (("mxnet_tpu/executor.py", "_Lowered.run"),)},
    # the sampled numerics-monitor step (MXNET_MONITOR): one extra jit
    # per trace-env snapshot, traced over the same forward as the plain
    # step plus the on-device stats tree — MXNET_MONITOR itself sits in
    # TRACE_ENV_DEFAULTS so the stats layout (grad/update/act) is keyed
    {"name": "TrainStep._mon_cache (numerics monitor)",
     "key": ("mxnet_tpu/train.py", "TrainStep._monitored_step"),
     "roots": (("mxnet_tpu/executor.py", "_Lowered.run"),
               ("mxnet_tpu/numerics.py", "spec"))},
    # the schedule dispatch-plan cache (schedule-v2 PR): pure host-side
    # python —
    # the work-item generators in parallel/schedule.py read no env — but
    # its key carries trace_env_key() for contract uniformity with the
    # stage-program cache the plan drives (the programs themselves are
    # keyed by PipelineTrainStep._progs above)
    {"name": "PipelineTrainStep._plans",
     "key": ("mxnet_tpu/train.py", "PipelineTrainStep._get_plan"),
     "roots": (("mxnet_tpu/parallel/schedule.py", "stage_orders"),)},
    {"name": "serving bucket-rung ladder",
     "key": ("mxnet_tpu/serving.py", "ServedModel._predictor"),
     "roots": ()},     # rung jits land in the executor cache (see above)
    # the ZeRO-3 params all-gather (zero.gather): one program per
    # TrainStep instance — a pure reshape + sharding constraint over the
    # flat (dp, chunk) shards, no env reads at trace time (the
    # gather-forward step itself lands in the fused-fit / pipeline
    # caches above, keyed by their trace-env snapshots)
    {"name": "zero.gather param all-gather",
     "key": ("mxnet_tpu/train.py", "TrainStep.gather_params"),
     "roots": ()},
)


def _project_trace_vars(project):
    out = set()
    for fi in project.files:
        out.update(astutil.trace_env_vars(fi))
    return out


def _key_vars(project, fi, qualname, trace_vars):
    """Env vars the key expression covers, or None when the key fn is
    missing from this tree.  Nested function defs are EXCLUDED: for key
    sites that are whole hot functions (``TrainStep.run_steps``) the
    nested bodies are the *traced* side — an env read there must not
    mark itself covered."""
    node = fi.functions().get(qualname)
    if node is None:
        return None
    nested = {n for sub in ast.walk(node)
              if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
              and sub is not node
              for n in ast.walk(sub)}
    covered = set()
    for n in ast.walk(node):
        if n in nested:
            continue
        if astutil.is_env_read(fi, n):
            v = astutil.env_read_var(fi, n)
            if v:
                covered.add(v)
        d = ""
        if isinstance(n, ast.Call):
            d = fi.dotted(n.func)
        elif isinstance(n, (ast.Attribute, ast.Name)):
            d = fi.dotted(n)
        if d.endswith("trace_env_key"):
            covered |= trace_vars
    return covered


def _reachable_env_reads(fi, root_qual):
    """{var: (line, context)} for literal env reads reachable from the
    root through same-file calls/nested defs (JIT001's propagation)."""
    from . import rule_jit
    funcs = fi.functions()
    if root_qual not in funcs:
        return {}
    traced = rule_jit._propagate(fi, {root_qual})
    out = {}
    for q in sorted(traced):
        node = funcs.get(q)
        if node is None:
            continue
        for n in ast.walk(node):
            if astutil.is_env_read(fi, n):
                v = astutil.env_read_var(fi, n)
                if v and v.startswith(("MXNET_", "MXTPU_")):
                    out.setdefault(v, (n.lineno, q))
    return out


def _ops_roots(project):
    """(fi, qualname) for every registered operator body under
    mxnet_tpu/ops/ — the functions the imperative dispatch cache jits."""
    from . import rule_jit
    roots = []
    for fi in project.files:
        if not fi.rel.startswith("mxnet_tpu/ops/"):
            continue
        funcs = fi.functions()
        for q, node in funcs.items():
            if any(rule_jit._decorator_is_register(fi, dec, fi.rel)
                   for dec in node.decorator_list):
                roots.append((fi, q))
    return roots


def run(project):
    findings = []
    trace_vars = _project_trace_vars(project)
    for spec in CACHES:
        key_rel, key_qual = spec["key"]
        key_fi = project.file(key_rel)
        if key_fi is None:
            continue
        covered = _key_vars(project, key_fi, key_qual, trace_vars)
        if covered is None:
            continue
        key_node = key_fi.functions()[key_qual]
        if spec["roots"] == "ops":
            roots = _ops_roots(project)
        else:
            roots = []
            for root_rel, root_qual in spec["roots"]:
                root_fi = project.file(root_rel)
                if root_fi is not None:
                    roots.append((root_fi, root_qual))
        for root_fi, root_qual in roots:
            for var, (line, ctx) in sorted(
                    _reachable_env_reads(root_fi, root_qual).items()):
                if var in covered:
                    continue
                findings.append(Finding(
                    RULE, key_rel, key_node.lineno, key_qual,
                    "%s is read at trace time by %s (%s) but missing "
                    "from the %s key expression — a toggle would silently "
                    "reuse the stale compiled program; add it to the "
                    "cache key or register it in base.TRACE_ENV_DEFAULTS"
                    % (var, root_qual, root_fi.rel, spec["name"])))
    return findings
