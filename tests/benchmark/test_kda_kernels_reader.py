"""``readers/kda_kernels.py``: the device time of the delta rule's Pallas kernels
from a small plain trace: the kernels counted by name, with and without
autodiff's wrappers, inside the window and on the slowest device alone; a
program without them (the parent's, a shape the guard refused) reads
nothing."""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from benchmark import cells, run  # noqa: E402
from benchmark.readers import kda_kernels  # noqa: E402

NAME = "kimi-linear-steps-t4096"
METRIC = "kda.kernel_ms"
CALL = ('bf16[1,4096,4096] custom-call(bf16[1,4096,4096] %p), '
        'custom_call_target="tpu_custom_call"')


def _op(name, start, end, hlo=CALL):
    return [name, start, end, "compute", hlo]


# the names are the chip's own (my chip run, PR 34); two steps in the window
KERNELS = [_op("mxtpu_kda_fwd.1", 0.010, 0.012),
           _op("transpose_jvp_mxtpu_kda_states__.1", 0.020, 0.021),
           _op("transpose_jvp_mxtpu_kda_bwd__.1", 0.021, 0.025),
           _op("mxtpu_kda_fwd.1", 0.050, 0.052),
           _op("transpose_jvp_mxtpu_kda_states__.1", 0.060, 0.061),
           _op("transpose_jvp_mxtpu_kda_bwd__.1", 0.061, 0.065)]
OTHERS = [_op("fusion.12", 0.012, 0.020, "bf16[1,4096,6144] fusion()"),
          _op("mxtpu_gmm.3", 0.030, 0.040),
          _op("jvp_mxtpu_flash_fwd__.2", 0.040, 0.050)]


def _ctx(ops, window=(0.0, 0.1), steps=2, other=()):
    cell = cells.Cell(NAME, root=ROOT)
    return run.Context(
        cell=cell, peaks=cell.peaks("TPU v5 lite"), chips=1,
        plain={"window": list(window),
               "devices": {"0": list(ops), "1": list(other)}},
        profile=None, reduced={"steps": steps, "slowest": "0"})


def test_the_metric_names_the_reader():
    ctx = _ctx(KERNELS)
    fn, args = ctx.cell.reader(METRIC)
    assert fn is kda_kernels.kernel_ms and args == {}
    # ``BENCHMARK.json`` does not list the metric yet: the cell's accepted
    # test pins the cell's list of metrics, so the entry is a ``benchmark``
    # PR's to add (``PERF.md`` 7).  When it is there, it has this form.
    for entry in ctx.cell.per_layer():
        if entry["name"] == METRIC:
            assert entry["workloads"] == [NAME]
            assert entry["layer"] == "linear-attention mixer"


def test_the_kernels_are_counted_by_name():
    """2 + 1 + 4 ms a step, whatever else ran, other kernels included."""
    assert kda_kernels.kernel_ms(_ctx(KERNELS + OTHERS)) == \
        pytest.approx(7.0)


def test_only_the_window_and_the_slowest_device_count():
    """The second step's backward is cut by the window's end; the other
    device's kernels are not this one's."""
    ctx = _ctx(KERNELS + OTHERS, window=(0.0, 0.063), steps=2,
               other=[_op("mxtpu_kda_fwd.1", 0.0, 0.05)])
    assert kda_kernels.kernel_ms(ctx) == pytest.approx((7.0 + 5.0) / 2)


def test_a_program_without_the_kernels_reads_nothing():
    assert kda_kernels.kernel_ms(_ctx(OTHERS)) is None
    assert kda_kernels.kernel_ms(_ctx([])) is None
    # a kernel outside the window is not in it
    assert kda_kernels.kernel_ms(_ctx(KERNELS, window=(0.07, 0.1))) is None
