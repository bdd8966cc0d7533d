"""The readers of the program's own spans (``benchmark/readers/
program_spans.py``) on a small hand-made profile: planes, lines and events
as plain objects with the fields of ``jax.profiler.ProfileData``."""
import json
import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import cells, run, trace  # noqa: E402
from benchmark.readers import program_spans  # noqa: E402

MS = 1000000          # nanoseconds


def event(name, start_ms, dur_ms):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS))


def profile():
    """A window of 0-100 ms holding two batches.  Main thread: two launches
    of 2 and 4 ms; three waits for data, the first straddling the window's
    start (3 of its 5 ms inside), the last its end (1 of 6 ms inside); one
    launch and one wait wholly outside.  A second thread stages.  The
    device's plane holds an event of a span's name, which is no host span."""
    main = NS(name="python", events=[
        event("mx:data_wait", -2, 5), event("mx:train_step", 10, 2),
        event("mx:metric", 13, 1), event("mx:data_wait", 50, 2),
        event("mx:train_step", 60, 4), event("mx:metric", 65, 3),
        event("mx:data_wait", 99, 6), event("mx:train_step", 120, 50),
        event("mx:data_wait", -30, 10), event("bench:fit_batch", 0, 50),
        event("PjitFunction(mxtpu_step_amp)", 10, 2)])
    producer = NS(name="Thread-1", events=[event("mx:input.stage", 20, 7)])
    return NS(planes=[
        NS(name="/host:CPU", lines=[main, producer]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=[event("mx:train_step", 0, 100)])])])


def ctx(**kw):
    kw.setdefault("plain", {"window": [0.0, 0.1]})
    kw.setdefault("reduced", {"steps": 2})
    return run.Context(**kw)


def test_mean_of_the_spans_inside_the_window():
    c = ctx(profile=profile())
    assert program_spans.mean_ms(c, ["mx:train_step", "mx:train_chunk"]) \
        == pytest.approx(3.0)
    assert program_spans.mean_ms(c, ["mx:metric"]) == pytest.approx(2.0)
    assert program_spans.mean_ms(c, ["mx:input.stage"]) \
        == pytest.approx(7.0)


def test_a_span_across_the_windows_edge_counts_with_its_part_inside():
    c = ctx(profile=profile())
    assert sorted(program_spans._clipped(c, ["mx:data_wait"])) \
        == pytest.approx([0.001, 0.002, 0.003])
    # all the window's waiting over its two batches
    assert program_spans.per_step_ms(c, ["mx:data_wait"]) \
        == pytest.approx(3.0)


@pytest.mark.parametrize("fn", [program_spans.mean_ms,
                                program_spans.per_step_ms])
def test_a_program_without_the_span_reads_as_nothing(fn):
    assert fn(ctx(profile=profile()), ["mx:train_chunk"]) is None
    assert fn(ctx(profile=NS(planes=[])), ["mx:train_step"]) is None
    assert fn(ctx(), ["mx:train_step"]) is None       # no profile at all


BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SPAN_METRICS = {
    "step.dispatch_ms": ({"resnet50-fit", "opt-1.3b-steps"},
                         program_spans.mean_ms,
                         ["mx:train_step", "mx:train_chunk"]),
    "fit.data_wait_ms": ({"resnet50-fit"}, program_spans.per_step_ms,
                         ["mx:data_wait"]),
    "fit.metric_ms": ({"resnet50-fit"}, program_spans.mean_ms,
                      ["mx:metric"]),
    "fit.callback_ms": ({"resnet50-fit"}, program_spans.mean_ms,
                        ["mx:callback"]),
}


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_each_metrics_file_leads_to_its_reader_in_its_cells(name):
    want_cells, fn, names = SPAN_METRICS[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["source"] == "program_span" and entry["unit"] == "ms"
    assert set(entry["workloads"]) == want_cells
    for cell_name in want_cells:
        cell = cells.Cell(cell_name)
        assert entry in cell.per_layer()
        assert cell.reader(name) == (fn, {"names": names})


def test_the_result_line_gains_the_span_metrics_and_only_where_read():
    small = json.load(open(os.path.join(HERE, "small_trace.json")))
    cell = cells.Cell("resnet50-fit")
    c = run.Context(plain=small, reduced=trace.reduce(small, steps=2),
                    cell=cell, peaks=cell.peaks("TPU v5 lite"), chips=1,
                    compiles_in_window=0, profile=profile())
    got = run.per_layer_metrics(cell, c)
    assert got["step.dispatch_ms"] == {"value": pytest.approx(3.0),
                                       "unit": "ms"}
    assert got["fit.data_wait_ms"]["value"] == pytest.approx(3.0)
    assert got["fit.metric_ms"]["value"] == pytest.approx(2.0)
    assert "fit.callback_ms" not in got          # the profile has none
    c.profile = None                             # the parent's program
    assert not set(SPAN_METRICS) & set(run.per_layer_metrics(cell, c))
