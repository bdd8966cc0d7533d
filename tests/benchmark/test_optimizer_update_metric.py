"""``optimizer.update_ms`` (PR 37): the device time a step of
``resnet50-fit-dp4`` spends under the step function's ``optimizer_update``
scope, read by ``readers/scopes.py``'s ``scope_ms`` through a metric file
that is data alone.  Held here: the entry's form, the file's
reader, that the cell reports the metric and no other cell does, the
reading by hand on a made trace named as the loss-scaled step names its
operations, nothing without a trace or without the scope, and that the
program opens the scope the file names."""
import collections
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from benchmark import cells, run  # noqa: E402
from benchmark.readers import scopes  # noqa: E402

NAME = "resnet50-fit-dp4"
METRIC = "optimizer.update_ms"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_entry_is_appended_in_the_form_the_driver_checks():
    """Looked up by name, not by place: later entries go after it."""
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == METRIC]
    assert entry == {"name": METRIC, "unit": "ms", "better": "lower",
                     "source": "device_trace",
                     "layer": "fused step program",
                     "moves": "train_items_per_s", "workloads": [NAME]}
    assert [m["name"] for m in BENCH["per_layer"]].count(METRIC) == 1
    # the layer's name is the one the accepted entries give, letter for letter
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]
                              if m["name"] != METRIC}


def test_the_metric_file_is_data_and_names_the_reader():
    path = os.path.join(ROOT, "benchmark", "metrics", METRIC + ".json")
    assert json.load(open(path)) == {
        "module": "scopes", "function": "scope_ms",
        "args": {"scopes": ["optimizer_update"]}}
    fn, args = cells.Cell(NAME).reader(METRIC)
    assert fn is scopes.scope_ms and args == {"scopes": ["optimizer_update"]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_the_four_chip_cell_reports_it_and_no_other_does(cell):
    names = [m["name"] for m in cells.Cell(cell).per_layer()]
    assert (METRIC in names) is (cell == NAME)


# ---------------------------------------------------- the reading by hand
def _profile(ops):
    Event = collections.namedtuple("Event", "name start_ns duration_ns")
    Line = collections.namedtuple("Line", "name events")
    Plane = collections.namedtuple("Plane", "name lines")
    Profile = collections.namedtuple("Profile", "planes")
    return Profile([Plane("/device:TPU:0", [Line("XLA Ops", [
        Event("%%%s = f32[512]{0} fusion(f32[512]{0} %%p)" % n, a, d)
        for n, a, d, _ in ops])])])


# (operation, start ns, duration ns, framework name): two steps of a
# loss-scaled step; the update runs inside the overflow branch, whose own
# event spans its body and is left out
STEP = [("fusion.1", 0, 4_000_000,
         "jit(mxtpu_step_amp)/forward/jvp(conv0)/conv_general_dilated"),
        ("fusion.2", 4_000_000, 5_000_000,
         "jit(mxtpu_step_amp)/backward/transpose(jvp(conv0))/mul"),
        ("all-reduce.3", 9_000_000, 1_000_000,
         "jit(mxtpu_step_amp)/backward/transpose(jvp(conv0))/mul"),
        ("fusion.4", 10_000_000, 500_000,
         "jit(mxtpu_step_amp)/overflow_check/is_finite"),
        ("fusion.5", 10_500_000, 1_500_000,
         "jit(mxtpu_step_amp)/cond/branch_1_fun/optimizer_update/sub"),
        ("copy.6", 12_000_000, 250_000, None),
        ("all-gather.7", 12_250_000, 250_000, "jit(mxtpu_step_amp)/cond/"
         "branch_1_fun/optimizer_update/sharding_constraint")]


def _ctx(monkeypatch, ops, steps=2, trace_file="made.xplane.pb"):
    profile = _profile(ops)
    line = profile.planes[0].lines[0]
    names = {"/device:TPU:0": {e.name: fw for e, (_, _, _, fw)
                               in zip(line.events, ops) if fw}}
    monkeypatch.setattr(scopes, "_trace_file", lambda ctx: trace_file)
    monkeypatch.setattr(scopes, "framework_names", lambda path: names)
    cell = cells.Cell(NAME)
    return run.Context(cell=cell, peaks=cell.peaks("TPU v5 lite"), chips=4,
                       plain={"window": [0.0, 1.0]}, profile=profile,
                       reduced={"steps": steps, "slowest": "0"})


def _two_steps():
    second = [(n, a + 20_000_000, d, fw) for n, a, d, fw in STEP]
    return STEP + second


def test_the_updates_operations_are_counted_a_step(monkeypatch):
    """1.5 ms of the rule, the unnamed copy between two operations of the
    scope, the parameters' all-gather: 2 ms a step.  The gradient's
    all-reduce is the backward's."""
    ctx = _ctx(monkeypatch, _two_steps())
    fn, args = ctx.cell.reader(METRIC)
    assert fn(ctx, **args) == pytest.approx(2.0)


def test_nothing_is_read_without_a_trace_or_without_the_scope(monkeypatch):
    """An untraced run, and a program that opens no such scope, read
    nothing and do not raise: ``run.per_layer_metrics`` then leaves the
    metric out of the line."""
    ctx = _ctx(monkeypatch, _two_steps(), trace_file=None)
    assert scopes.scope_ms(ctx, ["optimizer_update"]) is None
    bare = [(n, a, d, fw and fw.replace("optimizer_update", "update"))
            for n, a, d, fw in _two_steps()]
    ctx = _ctx(monkeypatch, bare)
    assert scopes.scope_ms(ctx, ["optimizer_update"]) is None


def test_the_step_program_opens_the_scope_the_file_names():
    """``train.py`` names the update's operations ``optimizer_update``: with
    and without a loss scale, sharded by ZeRO or not."""
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.train import TrainStep
    d = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(d, name="fc", num_hidden=4), name="softmax")
    for kw in ({}, {"policy": amp.Policy("float32", loss_scale=8.0)},
               {"zero": 1, "mesh": make_mesh(
                   {"dp": 4}, devices=jax.devices()[:4]),
                "policy": amp.Policy("float32", loss_scale=8.0)}):
        ts = TrainStep(net, mx.optimizer.SGD(momentum=0.9), **kw)
        p, s, a = ts.init({"data": (8, 6)}, {"softmax_label": (8,)})
        b = ts.shard_batch({"data": np.zeros((8, 6), np.float32),
                            "softmax_label": np.zeros((8,), np.float32)})
        args = (p, s, a) + ((ts._scale_state_dev(),) if "policy" in kw
                            else ()) \
            + (b, jax.random.PRNGKey(0), ts.fopt.hyper(0), np.int32(1))
        text = ts._step.lower(*args).as_text(debug_info=True)
        assert "optimizer_update" in text, kw
