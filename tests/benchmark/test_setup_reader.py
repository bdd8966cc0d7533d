"""The set-up metrics: ``setup.import_s``, ``setup.trace_s``,
``setup.lower_s``, ``setup.compile_s``, ``setup.cache_misses`` and
``setup.rest_s``, read by ``readers/setup.py`` from the program's own
account (``mxnet_tpu.sanitize.setup_account``) between the process's start
and the window's first dispatch.  ``ENTRIES`` are their entries as
``BENCHMARK.json`` holds them: appended once, in this order, right after
``optimizer.update_ms``, with no ``workloads`` list.  Held here: the
entries and their files, that every cell's line carries them, the reading
of a made account, nothing without an account, and on the harness's own
run at the tiny CPU size that the phases and the rest are ``setup_s``."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import cells, run  # noqa: E402
from benchmark.readers import setup  # noqa: E402
from test_benchmark import _run, no_env, tiny  # noqa: E402,F401

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
PHASES = ["import", "trace", "lower", "compile"]
METRICS = ["setup.%s_s" % p for p in PHASES] + ["setup.cache_misses",
                                                "setup.rest_s"]


ENTRIES = [{"name": m, "unit": "count" if m == "setup.cache_misses" else "s",
            "better": "lower",
            "source": "program_counter" if m == "setup.cache_misses"
            else "program_span",
            "layer": "set-up", "moves": "setup_s"} for m in METRICS]


def test_the_entries_are_appended_once_in_order():
    """Each name once; the six together and in order, directly after the
    entry that was last before them, so that no entry before them moved.
    Held by name, not by place from the end: later entries go after them."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert all(names.count(m) == 1 for m in METRICS)
    first = names.index(METRICS[0])
    assert names[first - 1] == "optimizer.update_ms"
    assert BENCH["per_layer"][first:first + len(ENTRIES)] == ENTRIES


@pytest.mark.parametrize("metric", METRICS)
def test_each_metric_file_is_data_and_names_the_reader(metric):
    path = os.path.join(ROOT, "benchmark", "metrics", metric + ".json")
    spec = json.load(open(path))
    assert spec["module"] == "setup"
    fn, args = cells.Cell(BENCH["workloads"][0]["name"]).reader(metric)
    assert fn is getattr(setup, spec["function"])
    if metric.endswith("_s") and metric != "setup.rest_s":
        assert args == {"phase": metric[len("setup."):-len("_s")]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_line_carries_them_once_appended(cell, monkeypatch):
    """Every cell reports ``setup_s``, so with no ``workloads`` list every
    cell's traced line carries the six, each in its unit (read here on
    their own: the others need a trace)."""
    c = cells.Cell(cell)
    assert "setup_s" in [m["name"] for m in c.end_to_end()]
    assert [m for m in c.per_layer() if m["layer"] == "set-up"] == ENTRIES
    c.bench = dict(c.bench, per_layer=ENTRIES)
    _made_account(monkeypatch)
    got = run.per_layer_metrics(c, _made_window())
    assert list(got) == METRICS
    assert {k: v["unit"] for k, v in got.items()} == {
        m["name"]: m["unit"] for m in ENTRIES}


def _read(ctx, cell="resnet50-fit"):
    c = cells.Cell(cell)
    out = {}
    for metric in METRICS:
        fn, args = c.reader(metric)
        out[metric] = fn(ctx, **args)
    return out


def _made_account(monkeypatch):
    """Process start at 10 s, first dispatch at 30 s: the import, a trace
    with a compile of an eager program inside it, a lowering, a compile
    that holds two cache requests and a hit, and what comes after the
    window's first dispatch (the reference's compiles)."""
    from mxnet_tpu import sanitize
    acct = sanitize.SetupAccount()
    acct.imported = (11.0, 12.5)
    acct.intervals.extend([
        ("trace", "mxtpu_step", 14.0, 18.0),
        ("compile", "tril", 15.0, 15.5),
        ("lower", "mxtpu_step", 18.0, 20.0),
        ("compile", "mxtpu_step", 20.0, 23.0),
        ("trace", "reference", 31.0, 33.0),
        ("compile", "reference", 33.0, 40.0)])
    acct.events.extend([(15.2, "requests"), (20.5, "requests"),
                        (22.9, "hits"), (34.0, "requests")])
    monkeypatch.setattr(sanitize, "_setup", acct)


def _made_window():
    return run.Context(window={"stamps": np.array([30.0, 31.0, 32.0]),
                               "setup_s": 20.0})


def test_a_made_account_is_read_between_the_ends_of_setup_s(monkeypatch):
    """What comes after the window's first dispatch is left out."""
    _made_account(monkeypatch)
    got = _read(_made_window())
    assert got == {"setup.import_s": pytest.approx(1.5),
                   "setup.trace_s": pytest.approx(3.5),
                   "setup.lower_s": pytest.approx(2.0),
                   "setup.compile_s": pytest.approx(3.5),
                   "setup.cache_misses": 1.0,
                   "setup.rest_s": pytest.approx(9.5)}
    assert sum(got[m] for m in METRICS if m != "setup.cache_misses") \
        == pytest.approx(20.0)


def test_without_an_account_nothing_is_read(monkeypatch):
    """A context without a window, and a program from before the account,
    read nothing and do not raise: the line then leaves the metrics out."""
    assert set(_read(run.Context()).values()) == {None}
    from mxnet_tpu import sanitize
    monkeypatch.delattr(sanitize, "setup_account")
    ctx = run.Context(window={"stamps": np.array([30.0]), "setup_s": 20.0})
    assert set(_read(ctx).values()) == {None}


def test_the_harness_run_splits_its_setup_s(tiny, no_env, monkeypatch):
    """The harness's own run of the tiny language-model cell: its window,
    read by the six readers, gives phases that with the rest are its
    ``setup_s``; the package was imported before the run's start, so the
    import reads 0, and the run compiled its programs."""
    seen = {}
    intervals = run.intervals

    def spy(win, cell):
        seen["win"] = win
        return intervals(win, cell)
    monkeypatch.setattr(run, "intervals", spy)
    result, _ = _run(tiny, "opt-1.3b-steps")
    win = seen["win"]
    assert result["metrics"]["setup_s"]["value"] == win["setup_s"]
    got = _read(run.Context(window=win), "opt-1.3b-steps")
    assert got["setup.import_s"] == 0.0
    assert got["setup.trace_s"] > 0 and got["setup.lower_s"] > 0
    assert got["setup.compile_s"] > 0 and got["setup.rest_s"] >= 0
    assert got["setup.cache_misses"] >= 0
    assert sum(got[m] for m in METRICS if m != "setup.cache_misses") \
        == pytest.approx(win["setup_s"], abs=1e-6)
