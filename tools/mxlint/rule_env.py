"""ENV001 — the MXNET_* env-var contract.

Two halves:

1. Read discipline: every MXNET_* read in product code goes through
   ``base.get_env`` (or the ``base.TRACE_ENV_DEFAULTS`` table).  Direct
   ``os.environ`` / ``os.getenv`` reads bypass the one choke point the
   typed parsing, docs, and trace-key machinery hang off.

2. Bidirectional code <-> docs/env_var.md sync: every var the code reads
   appears in a doc table row; every table row has a live reader.  Vars
   listed under a heading containing "reference parity" or "not
   implemented" (or after an ``<!-- mxlint: reference-only -->`` marker)
   are the documented-absent set: they must have NO reader, and a reader
   appearing for one is itself a finding (implement it -> move it to a
   real table row).
"""
from __future__ import annotations

import ast
import os
import re

from . import astutil
from .core import Finding

RULE = "ENV001"

_TABLE_ROW = re.compile(r"^\|\s*`(MXNET_[A-Z0-9_]+)`")
_ANY_VAR = re.compile(r"`(MXNET_[A-Z0-9_]+)")
_REFONLY_HEAD = re.compile(r"reference\s+parity|not\s+implemented|"
                           r"absorbed|mxlint:\s*reference-only", re.I)


def _code_readers(project):
    """{var: [(rel, line)]} for every registered MXNET_* read site."""
    readers = {}

    def add(var, fi, line):
        if var and var.startswith("MXNET_"):
            readers.setdefault(var, []).append((fi.rel, line))

    for fi in project.files:
        for n in ast.walk(fi.tree):
            if astutil.is_env_read(fi, n):
                add(astutil.env_read_var(fi, n), fi, n.lineno)
        # the registration table base.TRACE_ENV_DEFAULTS
        for var, line in astutil.trace_env_vars(fi).items():
            add(var, fi, line)
    return readers


def _doc_vars(doc_path):
    """(documented_table_vars, reference_only_vars); both {var: line}."""
    table, refonly = {}, {}
    if not os.path.exists(doc_path):
        return table, refonly
    with open(doc_path, "r", encoding="utf-8") as f:
        lines = f.read().splitlines()
    in_refonly = False
    for i, text in enumerate(lines, 1):
        if text.startswith("#") or "mxlint:" in text:
            in_refonly = bool(_REFONLY_HEAD.search(text))
        m = _TABLE_ROW.match(text)
        if m and not in_refonly:
            table.setdefault(m.group(1), i)
            continue
        if in_refonly:
            for v in _ANY_VAR.findall(text):
                refonly.setdefault(v, i)
    return table, refonly


def run(project):
    findings = []
    # ---- half 1: read discipline
    for fi in project.files:
        if fi.rel == "mxnet_tpu/base.py":
            continue              # get_env's own implementation
        for n in ast.walk(fi.tree):
            if not astutil.is_env_read(fi, n):
                continue
            d = fi.dotted(n.func if isinstance(n, ast.Call) else n.value)
            if d.endswith("get_env"):
                continue
            var = astutil.env_read_var(fi, n)
            if var and var.startswith("MXNET_"):
                findings.append(Finding(
                    RULE, fi.rel, n.lineno, fi.context_of(n),
                    "%s read via %s bypasses base.get_env — the env "
                    "contract's single choke point" % (var, d)))
    # ---- half 2: code <-> doc sync
    readers = _code_readers(project)
    table, refonly = _doc_vars(project.doc_path)
    doc_rel = os.path.relpath(project.doc_path, project.root) \
        .replace(os.sep, "/")
    for var in sorted(readers):
        if var not in table and var not in refonly:
            rel, line = readers[var][0]
            findings.append(Finding(
                RULE, rel, line, "<module>",
                "%s is read by code but undocumented — add a row to %s"
                % (var, doc_rel)))
        elif var in refonly:
            rel, line = readers[var][0]
            findings.append(Finding(
                RULE, rel, line, "<module>",
                "%s has a live code reader but %s lists it as reference-"
                "parity/not-implemented — promote it to a real table row"
                % (var, doc_rel)))
    for var, line in sorted(table.items()):
        if var not in readers:
            findings.append(Finding(
                RULE, doc_rel, line, "<doc>",
                "%s is documented as implemented but nothing in the code "
                "reads it — drop the row or move it to the reference-"
                "parity section" % var))
    return findings
