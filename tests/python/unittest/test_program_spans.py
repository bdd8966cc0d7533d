"""The program's own spans on the fused path (telemetry.span: one span, two
sinks).  A live ``jax.profiler`` trace sees them as ``mx:<name>`` host events
whether or not the registry records; a recording registry changes neither the
path (the fused step stays) nor the dispatch (no device sync per batch); with
the registry off a span builds no event and reads no clock; the three flash
kernels carry their names into the lowered program."""
import gc
import glob
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu import telemetry as tel

BATCHES = 4


def _fit(callback=None, epochs=1):
    rs = np.random.RandomState(0)
    x = rs.randn(BATCHES * 8, 1, 28, 28).astype(np.float32)
    y = rs.randint(0, 4, BATCHES * 8).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=8)
    mod = mx.Module(models.get_lenet(num_classes=4), context=mx.cpu())
    mod.fit(it, num_epoch=epochs, optimizer_params={"learning_rate": 0.1},
            batch_end_callback=callback or (lambda p: None))
    return mod


@pytest.fixture()
def clean_registry():
    tel.stop()
    tel.reset()
    yield
    tel.stop()
    tel.reset()


@pytest.fixture(scope="module")
def traced_fit(tmp_path_factory):
    """One fit under a live profiler session, registry off: every host event
    named ``mx:*`` as (name, thread line, start_ns, end_ns)."""
    import jax
    out = str(tmp_path_factory.mktemp("trace"))
    _fit()                                    # compile outside the trace
    jax.profiler.start_trace(out)
    try:
        _fit()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            events += [(e.name, (plane.name, i), e.start_ns,
                        e.start_ns + e.duration_ns)
                       for e in line.events if e.name.startswith("mx:")]
    return events


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(child, parents):
    return any(p[1] == child[1] and p[2] <= child[2] and child[3] <= p[3]
               for p in parents)


@pytest.mark.parametrize("child,parent", [
    ("mx:data_wait", "mx:batch"), ("mx:fused_step", "mx:batch"),
    ("mx:metric", "mx:batch"), ("mx:callback", "mx:batch"),
    ("mx:train_step", "mx:fused_step"), ("mx:label_put", "mx:fused_step")])
def test_a_live_trace_holds_the_fit_loops_spans_nested(traced_fit, child,
                                                       parent):
    parents = _named(traced_fit, parent)
    children = _named(traced_fit, child)
    # data_wait and batch are also opened by the iteration that finds the
    # epoch's end
    assert len(parents) >= BATCHES and len(children) >= BATCHES
    assert all(_inside(c, parents) for c in children)


@pytest.mark.parametrize("name", ["mx:input.source_next", "mx:input.stage",
                                  "mx:input.put"])
def test_the_prefetch_producers_spans_are_on_a_thread_of_their_own(
        traced_fit, name):
    main = {e[1] for e in _named(traced_fit, "mx:batch")}
    staged = _named(traced_fit, name)
    assert len(main) == 1 and len(staged) >= BATCHES
    assert not {e[1] for e in staged} & main


def test_the_epochs_end_is_a_span_outside_any_batch(traced_fit):
    ends = _named(traced_fit, "mx:epoch_end")
    assert len(ends) == 1
    assert not _inside(ends[0], _named(traced_fit, "mx:batch"))


def test_recording_keeps_the_fused_path_and_syncs_nothing(clean_registry,
                                                          monkeypatch):
    import jax
    _fit()                                    # compile first
    syncs = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: syncs.append(1) or real(x))
    fused = []

    def each_batch(param):
        fast = param.locals["fast"]
        fused.append(fast is not None
                     and param.locals["self"]._active_fused is fast)
    tel.start()
    _fit(each_batch)
    spans = [e for e in tel.events() if e["type"] == "span"]
    tel.stop()
    assert fused == [True] * BATCHES
    assert syncs == []
    names = [e["name"] for e in spans]
    for name in ("batch", "data_wait", "fused_step", "train_step",
                 "label_put", "metric", "callback", "step", "input.stage"):
        assert names.count(name) == BATCHES, name
    assert names.count("epoch_end") == 1
    assert not {"forward", "backward", "update"} & set(names)
    assert all(e["tags"]["nbatch"] in range(BATCHES) for e in spans
               if e["name"] == "batch")


def test_the_general_loop_is_asked_for_by_name(clean_registry, monkeypatch):
    monkeypatch.setenv("MXNET_FUSED_FIT", "0")
    tel.start()
    _fit()
    names = [e["name"] for e in tel.events() if e["type"] == "span"]
    tel.stop()
    for name in ("batch", "forward", "backward", "update", "metric"):
        assert names.count(name) == BATCHES, name
    assert "fused_step" not in names


def test_with_the_registry_off_a_span_builds_no_event_and_reads_no_clock(
        clean_registry, monkeypatch):
    tel.span("warm")                  # the first span imports jax

    def refuse(*a, **k):
        raise AssertionError("a span of a registry that is off did this")
    monkeypatch.setattr(tel, "_Span", refuse)
    monkeypatch.setattr(tel, "record_span", refuse)
    monkeypatch.setattr(tel, "time", type("NoClock", (), {
        "time": staticmethod(refuse), "perf_counter": staticmethod(refuse)}))
    with tel.span("off", cat="step", epoch=0, nbatch=1) as sp:
        sp.tags["late"] = 1           # the surface of a recording span
        sp.cancel()
        gc.collect()                  # and the collector's hook likewise
    assert tel.events() == []


def test_a_collector_pass_is_a_span_of_the_registry(clean_registry):
    tel.start()
    with tel.span("around"):
        gc.collect()
    spans = [e for e in tel.events() if e["type"] == "span"]
    tel.stop()
    passes = [e for e in spans if e["name"] == "host.gc"]
    assert passes and passes[-1]["tags"]["generation"] == 2
    around = [e for e in spans if e["name"] == "around"][0]
    assert around["ts"] <= passes[-1]["ts"]
    assert passes[-1]["dur"] <= around["dur"]


def test_run_steps_launch_is_a_span(clean_registry):
    from mxnet_tpu import train
    net = models.get_lenet(num_classes=4)
    opt = mx.optimizer.create("sgd", learning_rate=0.1)
    ts = train.TrainStep(net, opt)
    rs = np.random.RandomState(0)
    shapes = dict(data=(8, 1, 28, 28), softmax_label=(8,))
    arg_shapes, _, aux_shapes = net.infer_shape(**shapes)
    params = {n: rs.randn(*s).astype(np.float32) * 0.05
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n not in shapes}
    aux = {n: np.zeros(s, np.float32)
           for n, s in zip(net.list_auxiliary_states(), aux_shapes)}
    state = ts.fopt.init_state(params)
    batch = {"data": rs.randn(3, 8, 1, 28, 28).astype(np.float32),
             "softmax_label": rs.randint(0, 4, (3, 8)).astype(np.float32)}
    tel.start()
    ts.run_steps(params, state, aux, batch, 2, stacked=True)
    chunks = [e for e in tel.events()
              if e["type"] == "span" and e["name"] == "train_chunk"]
    tel.stop()
    assert len(chunks) == 1
    assert chunks[0]["tags"] == {"num_update": 3, "num_steps": 2}


@pytest.mark.parametrize("name", ["mxtpu_flash_fwd", "mxtpu_flash_dq",
                                  "mxtpu_flash_dkv"])
def test_the_flash_kernels_carry_their_names_into_the_program(name):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    q = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.bfloat16)

    def loss(q, k, v):
        out = pk.flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        q, q, q).lower(lowering_platforms=("tpu",))
    assert 'kernel_name = "%s"' % name in lowered.as_text()


@pytest.mark.parametrize("scope", ["forward", "backward", "overflow_check",
                                   "optimizer_update"])
def test_the_step_programs_parts_are_named_scopes(scope):
    import jax
    from mxnet_tpu import amp, train
    net = models.get_lenet(num_classes=4)
    opt = mx.optimizer.create("sgd", learning_rate=0.1)
    ts = train.TrainStep(net, opt, policy=amp.Policy("bfloat16"))
    shapes = dict(data=(8, 1, 28, 28), softmax_label=(8,))
    arg_shapes, _, aux_shapes = net.infer_shape(**shapes)
    params = {n: np.zeros(s, np.float32)
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n not in shapes}
    aux = {n: np.zeros(s, np.float32)
           for n, s in zip(net.list_auxiliary_states(), aux_shapes)}
    state = ts.fopt.init_state(params)
    batch = {k: np.zeros(s, np.float32) for k, s in shapes.items()}
    text = ts._step.lower(
        params, state, aux, ts._scale_state_dev(), batch,
        jax.random.PRNGKey(0), ts.fopt.hyper(0),
        np.int32(1)).as_text(debug_info=True)
    assert "/%s/" % scope in text


# ------------------------------------------------- the step-anatomy tools
def _tool(name):
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _recorded_steps(path, steps=10):
    """A fused fit's stream by hand: a 10 ms step of 1 + 6 + 1 + 1.5 ms on
    the loop's thread, and 4 ms of staging beside it."""
    tel.start(path)
    t = time.time()
    for i in range(steps):
        tags = dict(epoch=0, nbatch=i)
        tel.record_span("step", t, 10e-3, cat="step", **tags)
        tel.record_span("batch", t, 10e-3, cat="fit", **tags)
        tel.record_span("data_wait", t, 1e-3, cat="step", **tags)
        tel.record_span("fused_step", t, 6e-3, cat="step", **tags)
        tel.record_span("metric", t, 1e-3, cat="step", **tags)
        tel.record_span("callback", t, 1.5e-3, cat="step", **tags)
        tel.record_span("input.stage", t, 4e-3, cat="io")
    tel.stop()


def test_the_report_takes_callback_as_a_phase_and_staging_beside(
        clean_registry, tmp_path, capsys):
    import json
    path = str(tmp_path / "t.jsonl")
    _recorded_steps(path)
    report = _tool("telemetry_report")
    assert report.main([path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert list(doc["components"]) == ["data_wait", "fused_step", "metric",
                                       "callback"]
    assert doc["components"]["callback"]["mean_ms"] == pytest.approx(1.5)
    # the whole-iteration `batch` span and the producer's staging are no
    # part of the step's sum
    assert doc["coverage"] == pytest.approx(0.95)
    assert doc["beside"] == {"input.stage": {
        "total_ms": pytest.approx(40.0), "count": 10}}
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert "callback" in out and "beside the step" in out


def test_the_anatomy_takes_callback_and_staging_as_phases(clean_registry,
                                                          tmp_path):
    path = str(tmp_path / "t.jsonl")
    _recorded_steps(path + ".rank0")
    agg = _tool("telemetry_agg")
    an = agg.aggregate(agg.rank_files(path))["anatomy"]
    assert {"data_wait", "callback", "input.stage"} <= set(an["phases"])
    row = an["ranks"][0]
    assert row["callback_ms"] == pytest.approx(1.5)
    assert row["input.stage_ms"] == pytest.approx(4.0)
    assert row["compute_ms"] == pytest.approx(7.0)
    # 10 - (1 + 7 + 1.5): the staging is beside the step, not in it
    assert row["other_ms"] == pytest.approx(0.5)
