"""JIT001 — tracer purity.

Functions that jax.jit traces run ONCE per cache entry; anything they do
besides building the computation is frozen into the compiled program.
Inside traced code this rule flags:

  * environment reads (get_env / os.environ / os.getenv) — the flag value
    freezes at first compile; resolve it at dispatch time or key the jit
    cache on base.trace_env_key().  Reads of vars registered in
    base.TRACE_ENV_DEFAULTS are exempt inside TRACE_KEYED_FILES (the
    executor lowering), where that key is already on every cache lookup.
  * wall-clock reads (time.time / perf_counter / monotonic)
  * print() — executes at trace, silent on every cached call
  * telemetry emission (counter/gauge/span/scalar/histogram) — records
    once at trace, never again
  * ``global`` / ``nonlocal`` declarations — trace-time state capture

"Traced" is computed per file: seeds are functions decorated with
jax.jit / jax.custom_vjp / functools.partial(jax.jit|custom_vjp, ...),
functions registered as operators (@register in mxnet_tpu/ops), functions
passed by name to jax.jit(...) or *.defvjp(...), plus the known executor
trace roots (EXTRA_TRACED — the bodies _get_jit wraps).  Tracing
propagates through same-file calls (bare names, self.method) and into
nested defs.
"""
from __future__ import annotations

import ast

from . import astutil
from .core import Finding

RULE = "JIT001"

# Known traced bodies the seeding heuristics can't see statically:
# executor._get_jit jits thin wrappers whose work happens in these.
EXTRA_TRACED = {
    "mxnet_tpu/executor.py": ("_Lowered.run", "Executor._walk"),
}

# Files where EVERY jit dispatch keys its cache on base.trace_env_key():
# reads of vars registered in base.TRACE_ENV_DEFAULTS are legitimate at
# trace time there (a toggle lands on a new cache key and retraces).
# Registered vars read at trace time anywhere ELSE are still findings —
# other jit caches (registry._JIT_CACHE, TrainStep's per-instance jit)
# do not carry the trace-env snapshot in their keys.
TRACE_KEYED_FILES = {"mxnet_tpu/executor.py"}

_CLOCKS = {"time.time", "time.perf_counter", "time.monotonic",
           "time.process_time"}
_TELEMETRY_TAILS = {"counter", "gauge", "span", "scalar", "histogram"}


def _decorator_traced(fi, dec):
    """Does this decorator expression jit or custom_vjp the function?"""
    for n in ast.walk(dec):
        d = fi.dotted(n.func) if isinstance(n, ast.Call) else (
            fi.dotted(n) if isinstance(n, (ast.Attribute, ast.Name)) else "")
        if not d:
            continue
        if d in ("jax.jit", "jax.custom_vjp", "jax.custom_jvp"):
            return True
        if d.endswith(("jit", "custom_vjp", "custom_jvp")) \
                and d.startswith("jax."):
            return True
    return False


def _decorator_is_register(fi, dec, rel):
    if not rel.startswith("mxnet_tpu/ops/"):
        return False
    target = dec.func if isinstance(dec, ast.Call) else dec
    d = fi.dotted(target)
    return d == "register" or d.endswith(".register")


def _seeds(fi):
    funcs = fi.functions()
    traced = set()
    for q, node in funcs.items():
        for dec in node.decorator_list:
            if _decorator_traced(fi, dec) \
                    or _decorator_is_register(fi, dec, fi.rel):
                traced.add(q)
    # functions passed by name: jax.jit(f), X.defvjp(fwd, bwd)
    by_name = {}
    for q, node in funcs.items():
        by_name.setdefault(node.name, q)
    for n in ast.walk(fi.tree):
        if not isinstance(n, ast.Call):
            continue
        d = fi.dotted(n.func)
        takes_fns = (d == "jax.jit" or d.endswith(".defvjp")
                     or d == "jax.checkpoint")
        if not takes_fns:
            continue
        for a in n.args:
            if isinstance(a, ast.Name) and a.id in by_name:
                traced.add(by_name[a.id])
    traced.update(q for q in EXTRA_TRACED.get(fi.rel, ()) if q in funcs)
    return traced


def _propagate(fi, traced):
    """Fixpoint: callees (same-file) and nested defs of traced functions
    are traced too."""
    funcs = fi.functions()
    classes = set(fi.classes())
    changed = True
    while changed:
        changed = False
        for q in list(traced):
            node = funcs.get(q)
            if node is None:
                continue
            cls = q.rsplit(".", 1)[0] if "." in q else None
            cls_prefix = cls if cls in classes else None
            for callee in astutil.call_targets(fi, node, cls_prefix):
                for cand in (callee, (q + "." + callee)):
                    if cand in funcs and cand not in traced:
                        traced.add(cand)
                        changed = True
            for sub, subq in fi.qualnames.items():
                if subq.startswith(q + ".") and subq not in traced \
                        and isinstance(sub, (ast.FunctionDef,
                                             ast.AsyncFunctionDef)):
                    traced.add(subq)
                    changed = True
    return traced


def _violations(fi, q, node, findings, trace_keyed_vars=()):
    own = {n for sub in ast.walk(node)
           if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
           and sub is not node
           for n in ast.walk(sub)}
    for n in ast.walk(node):
        if n in own:
            continue       # nested defs are reported under their own name
        if astutil.is_env_read(fi, n):
            var = astutil.env_read_var(fi, n) or "env"
            if fi.rel in TRACE_KEYED_FILES and var in trace_keyed_vars:
                continue   # registered in base.TRACE_ENV_DEFAULTS; the
                           # cache key retraces on toggle
            findings.append(Finding(
                RULE, fi.rel, n.lineno, q,
                "env read (%s) inside jit-traced code freezes the value at "
                "first compile; resolve at dispatch time or key the cache "
                "via base.trace_env_key()" % var))
        elif isinstance(n, ast.Call):
            d = fi.dotted(n.func)
            if d in _CLOCKS:
                findings.append(Finding(
                    RULE, fi.rel, n.lineno, q,
                    "wall-clock read (%s) inside jit-traced code runs at "
                    "trace time, not per step" % d))
            elif d == "print":
                findings.append(Finding(
                    RULE, fi.rel, n.lineno, q,
                    "print() inside jit-traced code fires once at trace; "
                    "use jax.debug.print for per-call output"))
            elif "." in d:
                head, tail = d.rsplit(".", 1)
                if tail in _TELEMETRY_TAILS and (
                        head.endswith("telemetry") or head == "_tel"):
                    findings.append(Finding(
                        RULE, fi.rel, n.lineno, q,
                        "telemetry emission (%s) inside jit-traced code "
                        "records once at trace, never per step — emit from "
                        "the dispatching caller" % d))
        elif isinstance(n, (ast.Global, ast.Nonlocal)):
            findings.append(Finding(
                RULE, fi.rel, n.lineno, q,
                "%s declaration inside jit-traced code is trace-time state "
                "capture — traced functions must be pure"
                % type(n).__name__.lower()))


def run(project):
    findings = []
    trace_keyed_vars = set()
    for fi in project.files:
        trace_keyed_vars.update(astutil.trace_env_vars(fi))
    for fi in project.files:
        funcs = fi.functions()
        traced = _propagate(fi, _seeds(fi))
        for q in sorted(traced):
            node = funcs.get(q)
            if node is not None:
                _violations(fi, q, node, findings, trace_keyed_vars)
    return findings
