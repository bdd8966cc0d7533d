"""``readers/moe_rows.py``: the rows the routed products ran over for every
assignment that landed, from the program's device counters: by hand; as the
parent's program gives them (no ``moe_rows``, or no counters at all: reads
nothing); and where nothing landed."""
import collections
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from benchmark import cells, run  # noqa: E402
from benchmark.readers import moe_rows  # noqa: E402

NAME = "nemotron-twotower-steps-t4096"
METRIC = "moe.rows_computed_over_landed"
# two expert layers over 4 steps: [landed, fullest, absent, uncomputed]
MOE = np.array([[6144.0, 1152.0, 92160.0, 0.0],
                [10240.0, 5120.0, 88064.0, 0.0]], np.float32)


def _ctx():
    cell = cells.Cell(NAME, root=ROOT)
    return run.Context(cell=cell, peaks=cell.peaks("TPU v5 lite"), chips=1,
                       plain={"window": [0.0, 1.0]}, profile=None,
                       reduced={"steps": 8, "slowest": "0"})


def _publish(monkeypatch, *runs):
    from mxnet_tpu import telemetry
    monkeypatch.setattr(telemetry, "_dev_recent", collections.deque(runs))


def test_the_metric_names_the_reader():
    fn, args = _ctx().cell.reader(METRIC)
    assert fn is moe_rows.rows_computed_over_landed and args == {}
    entry = [m for m in _ctx().cell.per_layer() if m["name"] == METRIC]
    assert entry and entry[0]["workloads"] == [NAME]


@pytest.mark.parametrize("rows,want", [
    # every block visited holds 256 rows: 9 + 12 blocks a step
    ([9216.0, 12288.0], 21504.0 / 16384.0),
    # a room of 8,192 rows a layer and step, whatever landed
    ([32768.0, 32768.0], 4.0),
    # no row in vain
    ([6144.0, 10240.0], 1.0)])
def test_rows_over_landed_by_hand(monkeypatch, rows, want):
    """The window's 8 steps are its two newest chunks of 4; an older chunk
    is not the window's."""
    rows = np.asarray(rows, np.float32)
    _publish(monkeypatch, ({"moe": 9 * MOE, "moe_rows": 5 * rows}, 4),
             ({"moe": 0.25 * MOE, "moe_rows": 0.25 * rows}, 4),
             ({"moe": 0.75 * MOE, "moe_rows": 0.75 * rows}, 4))
    assert moe_rows.rows_computed_over_landed(_ctx()) == pytest.approx(want)


def test_the_parents_counters_read_nothing(monkeypatch):
    """The program before this counter publishes ``moe`` alone; one before
    any device counter has no ``device_counters``; no run, nothing."""
    from mxnet_tpu import telemetry
    _publish(monkeypatch, ({"moe": MOE}, 8))
    assert moe_rows.rows_computed_over_landed(_ctx()) is None
    _publish(monkeypatch)
    assert moe_rows.rows_computed_over_landed(_ctx()) is None
    monkeypatch.delattr(telemetry, "device_counters")
    assert moe_rows.rows_computed_over_landed(_ctx()) is None


def test_nothing_landed_reads_nothing(monkeypatch):
    """Every assignment went to absent experts: each held expert's one
    block was still visited, and there is no load to set it against."""
    none = MOE * np.array([0.0, 0.0, 1.0, 0.0], np.float32)
    _publish(monkeypatch, ({"moe": none, "moe_rows": np.array(
        [16384.0, 16384.0], np.float32)}, 8))
    assert moe_rows.rows_computed_over_landed(_ctx()) is None
