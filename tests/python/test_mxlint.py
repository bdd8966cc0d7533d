"""mxlint (tools/mxlint): the tier-1 semantic lint gate.

Three layers:

1. per-rule fixture pairs — every rule family must FLAG its seeded-
   violation fixture (with the expected message) and pass its clean twin;
2. machinery — inline suppressions, baseline accept/shrink, --json
   stability, CLI exit codes;
3. the repo gate — the analyzer runs in-process over ``mxnet_tpu/``,
   ``tools/`` and ``bench.py`` and FAILS this suite on any finding not in
   the committed ``tools/mxlint/baseline.json``.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.mxlint import lint  # noqa: E402
from tools.mxlint.core import (json_safe, load_baseline,  # noqa: E402
                               split_baselined, write_baseline)
from tools.mxlint.__main__ import main as mxlint_main  # noqa: E402

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "mxlint_fixtures")

# (fixture-pair stem, rule, lint targets inside the fixture tree,
#  substring every seeded finding set must contain)
CASES = [
    ("jit", "JIT001", ("pkg", "mxnet_tpu"), "inside jit-traced code"),
    ("sync", "SYNC001", ("mxnet_tpu",), "host sync"),
    ("env", "ENV001", ("pkg",), "base.get_env"),
    ("noop", "NOOP001", ("pkg",), "without an env guard"),
    ("thr", "THR001", ("pkg",), "lock-free"),
    ("ckey", "CKEY001", ("mxnet_tpu",), "cache key"),
    ("coll", "COLL001", ("pkg",), "rank-dependent"),
    ("coll2", "COLL002", ("pkg",), "single-use"),
    ("thr2", "THR002", ("pkg",), "off-main-thread"),
    ("tel", "TEL001", ("mxnet_tpu",), "unguarded telemetry emission"),
]


def run_fixture(tree, rule, targets):
    return lint(os.path.join(FIX, tree), targets=targets, rules=[rule])


# ------------------------------------------------------------ rule fixtures
@pytest.mark.parametrize("stem,rule,targets,needle", CASES,
                         ids=[c[1] for c in CASES])
def test_rule_flags_seeded_fixture(stem, rule, targets, needle):
    findings, _, errors = run_fixture(stem + "_bad", rule, targets)
    assert not errors
    assert findings, "%s found nothing in its seeded fixture" % rule
    assert all(f.rule == rule for f in findings)
    assert any(needle in f.message for f in findings), \
        [f.message for f in findings]


@pytest.mark.parametrize("stem,rule,targets,needle", CASES,
                         ids=[c[1] for c in CASES])
def test_rule_passes_clean_twin(stem, rule, targets, needle):
    findings, _, errors = run_fixture(stem + "_clean", rule, targets)
    assert not errors
    assert findings == [], [str(f) for f in findings]


def test_jit_seeds_cover_every_impurity_class():
    findings, _, _ = run_fixture("jit_bad", "JIT001", ("pkg", "mxnet_tpu"))
    msgs = " / ".join(f.message for f in findings)
    for needle in ("env read", "wall-clock", "print()", "telemetry emission",
                   "global declaration"):
        assert needle in msgs, needle
    # propagation: the violation inside _helper (only reached via
    # jax.jit(outer)) is attributed to _helper itself
    assert any(f.context == "_helper" for f in findings)


def test_jit_trace_keyed_contract():
    """In the executor (every jit keys on base.trace_env_key()) a read of
    a REGISTERED var is the contract; an unregistered read still flags."""
    findings, _, _ = run_fixture("jit_bad", "JIT001", ("mxnet_tpu",))
    assert any("MXNET_FIXTURE_ROGUE" in f.message
               and f.rel == "mxnet_tpu/executor.py" for f in findings)
    clean, _, _ = run_fixture("jit_clean", "JIT001", ("mxnet_tpu",))
    assert clean == [], [str(f) for f in clean]


def test_env_catches_every_drift_class():
    """The 3-missing/11-stale style drift ENV001 exists to prevent: each
    class fires on the seeded doc/code pair."""
    findings, _, _ = run_fixture("env_bad", "ENV001", ("pkg",))
    msgs = " / ".join(f.message for f in findings)
    assert "bypasses base.get_env" in msgs
    assert "is read by code but undocumented" in msgs
    assert "nothing in the code reads it" in msgs
    assert "promote it to a real table row" in msgs


def test_ckey_names_the_missing_lever_and_propagates():
    """CKEY001 = the PR-7 cache-key class, statically: both the lever
    read directly in the traced root and the one read a call deep must
    be named, anchored at the key-building function."""
    findings, _, _ = run_fixture("ckey_bad", "CKEY001", ("mxnet_tpu",))
    msgs = " / ".join(f.message for f in findings)
    assert "MXNET_FIXTURE_FLAVOR" in msgs
    assert "MXNET_FIXTURE_MODE" in msgs          # via call propagation
    assert all(f.context == "Executor._get_jit" for f in findings)
    # the clean twin covers one var literally in the key expression and
    # the other through the trace_env_key() registry snapshot
    clean, _, _ = run_fixture("ckey_clean", "CKEY001", ("mxnet_tpu",))
    assert clean == [], [str(f) for f in clean]


def test_ckey_repo_caches_cover_their_trace_reads():
    """The repo-level contract CKEY001 now enforces: every env var
    executor._Lowered.run consults while tracing is covered by the
    fused-fit and run_steps cache keys (the PR-9 fixes)."""
    from tools.mxlint.core import Project
    from tools.mxlint import rule_ckey
    p = Project(ROOT)
    reads = set(rule_ckey._reachable_env_reads(
        p.file("mxnet_tpu/executor.py"), "_Lowered.run"))
    assert reads, "expected trace-time env reads in _Lowered.run"
    tv = rule_ckey._project_trace_vars(p)
    for rel, qual in (("mxnet_tpu/module/module.py",
                       "_fused_fit_key_fields"),
                      ("mxnet_tpu/train.py", "TrainStep.run_steps"),
                      ("mxnet_tpu/executor.py", "Executor._get_jit")):
        covered = rule_ckey._key_vars(p, p.file(rel), qual, tv)
        assert reads <= covered, (rel, qual, sorted(reads - covered))


def test_thr_module_scope_and_class_scope():
    findings, _, _ = run_fixture("thr_bad", "THR001", ("pkg",))
    assert any("attribute 'count'" in f.message for f in findings)
    assert any("global '_beats'" in f.message for f in findings)


def test_coll_covers_both_divergence_classes():
    """COLL001's two SPMD deadlock shapes: a collective under a
    rank-dependent branch without a matching dispatch on the other path
    (direct read AND name-taint propagation), and a collective made
    unreachable by a rank-dependent early return."""
    findings, _, _ = run_fixture("coll_bad", "COLL001", ("pkg",))
    msgs = " / ".join(f.message for f in findings)
    assert "never reach a matching dispatch" in msgs
    assert "early return" in msgs
    assert any(f.context == "merge" for f in findings)   # via name taint
    assert any(f.context == "publish" for f in findings)


def test_coll_sanctioned_rank0_save_shape_passes():
    """The rank-0-writes-while-peers-barrier pattern is the sanctioned
    shape: paired barriers in both branches, or the collective hoisted
    after the rank branch — the clean twin carries both and must not
    fire."""
    findings, _, errors = run_fixture("coll_clean", "COLL001", ("pkg",))
    assert not errors
    assert findings == [], [str(f) for f in findings]


def test_coll2_exempts_module_scope_and_once_latch():
    """COLL002's two exemptions — module scope (one run per import) and
    the once-latched init_process_group shape — live in the clean twin;
    the bad twin fires on both the positional and keyword name forms."""
    findings, _, _ = run_fixture("coll2_bad", "COLL002", ("pkg",))
    assert any("'elastic-ckpt'" in f.message for f in findings)
    assert any("'ckpt-flush'" in f.message for f in findings)
    clean, _, _ = run_fixture("coll2_clean", "COLL002", ("pkg",))
    assert clean == [], [str(f) for f in clean]


def test_thr2_seeds_closures_methods_and_submissions():
    """THR002's three thread-body seeds: a nested closure Thread target,
    a self-method target with propagation one call deep, and a
    concurrent.futures submission."""
    findings, _, _ = run_fixture("thr2_bad", "THR002", ("pkg",))
    ctxs = {f.context for f in findings}
    assert "probe._barrier" in ctxs
    assert "Writer._flush" in ctxs            # _drain -> _flush
    assert "_reduce_on_pool" in ctxs          # pool.submit
    # coordination_barrier (service RPC) is exempt — the clean twin's
    # writer thread uses it freely
    clean, suppressed, _ = run_fixture("thr2_clean", "THR002", ("pkg",))
    assert clean == []
    assert len(suppressed) == 1               # the documented probe


def test_multi_rule_module_filters_to_selected_rule():
    """rule_coll hosts COLL001+COLL002; selecting one must not leak the
    other's findings (core's multi-rule filtering)."""
    f1, _, _ = run_fixture("coll2_bad", "COLL001", ("pkg",))
    assert f1 == [], [str(f) for f in f1]
    f2, _, _ = run_fixture("coll2_bad", "COLL002", ("pkg",))
    assert f2 and all(f.rule == "COLL002" for f in f2)


def test_repo_has_zero_thr2_sites():
    """THR002 holds repo-wide BY CONSTRUCTION: elastic health_check —
    historically the one waived site (a daemon-thread device barrier
    racing a timeout) — now rides dist.membership_barrier, a bounded
    coordination-service RPC on the calling thread.  No findings, and
    no suppressions hiding any."""
    from tools.mxlint.core import Project
    from tools.mxlint import rule_thr2
    p = Project(ROOT)
    assert [(f.rel, f.context) for f in rule_thr2.run(p)] == []
    fi = p.file("mxnet_tpu/parallel/elastic.py")
    assert not any("THR002" in rules
                   for rules in fi.suppressions.values())


# ---------------------------------------------------------------- machinery
def test_inline_suppression_lands_in_suppressed_not_findings():
    findings, suppressed, _ = run_fixture("thr_clean", "THR001", ("pkg",))
    assert findings == []
    assert len(suppressed) == 1 and suppressed[0].rule == "THR001"


def test_baseline_accepts_then_shrinks(tmp_path):
    findings, _, _ = run_fixture("sync_bad", "SYNC001", ("mxnet_tpu",))
    assert findings
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), findings)
    keys = load_baseline(str(bl))
    new, accepted = split_baselined(findings, keys)
    assert new == [] and len(accepted) == len(findings)
    # a fixed finding disappears; a shrunk baseline must not resurrect it
    new2, accepted2 = split_baselined(findings[1:], keys)
    assert new2 == [] and len(accepted2) == len(findings) - 1


def test_baseline_keys_survive_line_drift():
    """Keys carry no line numbers, so edits above a baselined finding
    don't invalidate the committed baseline."""
    findings, _, _ = run_fixture("sync_bad", "SYNC001", ("mxnet_tpu",))
    f = findings[0]
    assert str(f.line) not in f.key().split("|")[0]
    assert f.key() == "|".join((f.rule, f.rel, f.context, f.message))


def test_cli_check_fails_on_each_seeded_fixture(capsys):
    for stem, rule, targets, _ in CASES:
        rc = mxlint_main(["--root", os.path.join(FIX, stem + "_bad"),
                          "--rules", rule, "--check", "--no-baseline",
                          "--doc", "docs/env_var.md"] + list(targets))
        capsys.readouterr()
        assert rc == 1, "%s_bad must fail --check" % stem


def test_cli_check_passes_on_each_clean_twin(capsys):
    for stem, rule, targets, _ in CASES:
        rc = mxlint_main(["--root", os.path.join(FIX, stem + "_clean"),
                          "--rules", rule, "--check", "--no-baseline",
                          "--doc", "docs/env_var.md"] + list(targets))
        capsys.readouterr()
        assert rc == 0, "%s_clean must pass --check" % stem


def test_json_output_stable_and_parseable(capsys):
    argv = ["--root", os.path.join(FIX, "env_bad"), "--rules", "ENV001",
            "--json", "--no-baseline", "pkg"]
    rc = mxlint_main(argv)
    out1 = capsys.readouterr().out
    assert rc == 0                       # --json without --check lists only
    doc = json.loads(out1)               # RFC-8259 parseable
    assert doc["version"] == 1
    assert doc["counts"] == {"ENV001": len(doc["findings"])}
    assert doc["findings"], "expected seeded findings"
    for f in doc["findings"]:
        assert set(f) == {"rule", "path", "line", "context", "message",
                          "key"}
    # byte-stable across runs (sorted findings, sorted keys)
    mxlint_main(argv)
    assert capsys.readouterr().out == out1


def test_json_safe_stringifies_non_finite():
    doc = json_safe({"a": float("nan"), "b": [float("inf"), 1.5],
                     "c": float("-inf")})
    dumped = json.dumps(doc)             # must not emit bare NaN/Infinity
    assert json.loads(dumped) == {"a": "nan", "b": ["inf", 1.5],
                                  "c": "-inf"}


def test_module_entrypoint_runs():
    """`python -m tools.mxlint` is the documented invocation."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", "--rules", "THR001",
         "--check", "--no-baseline", "--root",
         os.path.join(FIX, "thr_bad"), "pkg"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "THR001" in proc.stdout


# ---------------------------------------------------------------- repo gate
def test_repo_is_clean_modulo_baseline():
    """THE gate: zero non-baselined findings over mxnet_tpu/, tools/ and
    bench.py.  Fix the finding, suppress it inline with a reason, or (for
    accepted legacy debt only) add it to tools/mxlint/baseline.json."""
    findings, _suppressed, errors = lint(ROOT)
    assert not errors, errors
    baseline = load_baseline(os.path.join(ROOT, "tools", "mxlint",
                                          "baseline.json"))
    new, _accepted = split_baselined(findings, baseline)
    assert new == [], "non-baselined mxlint findings:\n" + \
        "\n".join("  %s" % f for f in new)


def test_repo_baseline_has_no_stale_entries():
    """Every committed baseline key still matches a live finding —
    otherwise the debt was paid and the entry must be deleted (keeps the
    baseline meaningful instead of ever-growing)."""
    findings, _, _ = lint(ROOT)
    live = {f.key() for f in findings}
    baseline = load_baseline(os.path.join(ROOT, "tools", "mxlint",
                                          "baseline.json"))
    stale = sorted(baseline - live)
    assert stale == [], "stale baseline entries (fixed for real — " \
        "delete them):\n" + "\n".join("  %s" % k for k in stale)
