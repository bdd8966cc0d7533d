"""The set-up account (``sanitize.setup_account``): one ``jax.monitoring``
feed, always on, that times each program's trace, lowering and compile (a
persistent-cache load included) and counts the cache's requests and hits,
beside the package's import.  Held here: one interval of each phase a
program, a nested jit's trace counted once, nothing recorded by a cached
dispatch, a cache hit counted with its load under ``compile``, the cut, the
innermost rule on a made account, the caches' ``compile_seconds`` fed in
every run, and the registry's ``xla_compile`` / ``compile.seconds`` spans
written from the feed.  Each test reads only what came after its own first
stamp: the account is the process's."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sanitize as san
from mxnet_tpu import telemetry as tel


@pytest.fixture(autouse=True)
def _clean_state():
    san.cost_disarm()
    tel.stop()
    tel.reset()
    yield
    san.cost_disarm()
    tel.stop()
    tel.reset()


def _since(t):
    return [iv for iv in san._setup.intervals if iv[2] >= t]


def _events_since(t):
    return [kind for at, kind in san._setup.events if at >= t]


def test_the_feed_is_installed_and_the_import_is_noted():
    """The package's import is one interval and compiles nothing into
    ``compile_seconds``; the feed's two listeners are jax's."""
    from jax._src import monitoring
    assert san._on_feed_span in monitoring.get_event_time_span_listeners()
    assert san._on_feed_event in monitoring.get_event_listeners()
    start, end = san._setup.imported
    assert 0 < end - start < 600
    account = san.setup_account(until=end)
    assert account["import"] == pytest.approx(end - start)


def test_one_program_records_one_interval_of_each_phase():
    def mxtpu_probe_once(x):
        return x * 3 + 1
    f = jax.jit(mxtpu_probe_once)
    x = jnp.ones((5,), jnp.float32)
    jax.block_until_ready(x)
    t = time.perf_counter()
    jax.block_until_ready(f(x))
    mine = sorted(p for p, n, _, _ in _since(t) if n == "mxtpu_probe_once")
    assert mine == ["compile", "lower", "trace"]
    account = san.setup_account(since=t)
    for phase in ("trace", "lower", "compile"):
        assert account[phase] > 0
        assert "mxtpu_probe_once" in [n for n, _ in
                                      account["programs"][phase]]
    assert account["import"] == 0.0
    assert account["requests"] == 1 and account["hits"] == 0
    assert account["misses"] == 1


def test_a_nested_jit_is_counted_once_in_its_callers_trace():
    """The inner jit is traced inside the outer one's trace: the phase is
    the union, the outer interval, and the inner program keeps its own
    part of it."""
    def mxtpu_probe_inner(x):
        return jnp.sin(x) * 2
    inner = jax.jit(mxtpu_probe_inner)

    def mxtpu_probe_outer(x):
        return inner(x) + inner(x + 1)
    x = jnp.ones((7,), jnp.float32)
    jax.block_until_ready(x)
    t = time.perf_counter()
    jax.block_until_ready(jax.jit(mxtpu_probe_outer)(x))
    traces = [(n, a, b) for p, n, a, b in _since(t) if p == "trace"]
    [(a, b)] = [(a, b) for n, a, b in traces if n == "mxtpu_probe_outer"]
    inner_s = [ib - ia for n, ia, ib in traces if n == "mxtpu_probe_inner"]
    assert inner_s and all(a <= ia < ib <= b for _, ia, ib in traces)
    account = san.setup_account(since=t, top=1000)
    assert account["trace"] == pytest.approx(b - a, abs=1e-9)
    programs = dict(account["programs"]["trace"])
    assert sum(programs.values()) == pytest.approx(b - a, abs=1e-9)
    assert 0 < programs["mxtpu_probe_inner"] <= sum(inner_s) + 1e-9
    assert programs["mxtpu_probe_outer"] < b - a


@pytest.mark.parametrize("calls", [1, 1000])
def test_a_cached_dispatch_records_nothing(calls):
    """Once compiled, a program's dispatches fire no event at all: the
    steady window pays nothing for the feed."""
    def mxtpu_probe_hot(x):
        return x * 2 - 1
    f = jax.jit(mxtpu_probe_hot)
    x = jnp.ones((3,), jnp.float32)
    jax.block_until_ready(f(x))
    t = time.perf_counter()
    for _ in range(calls):
        y = f(x)
    jax.block_until_ready(y)
    assert _since(t) == [] and _events_since(t) == []


def test_a_persistent_cache_hit_is_counted_with_its_load_under_compile(
        tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    def make():
        def mxtpu_probe_cached(x):
            return jnp.cos(x) * 5
        return jax.jit(mxtpu_probe_cached)
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    try:
        x = jnp.ones((11,), jnp.float32)
        jax.block_until_ready(x)
        t = time.perf_counter()
        jax.block_until_ready(make()(x))
        cold = san.setup_account(since=t)
        assert (cold["requests"], cold["hits"], cold["misses"],
                cold["written"]) == (1, 0, 1, 1)
        t = time.perf_counter()
        jax.block_until_ready(make()(x))
        warm = san.setup_account(since=t)
        assert (warm["requests"], warm["hits"], warm["misses"],
                warm["written"]) == (1, 1, 0, 0)
        assert warm["compile"] > 0
        assert warm["programs"]["compile"][0][0] == "mxtpu_probe_cached"
    finally:
        jax.config.update("jax_compilation_cache_dir", old[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old[1])
        cc.reset_cache()


def test_a_cut_leaves_out_later_intervals_and_events():
    def mxtpu_probe_late(x):
        return x + 4
    x = jnp.ones((2,), jnp.float32)
    jax.block_until_ready(x)
    t = time.perf_counter()
    cut = time.perf_counter()
    jax.block_until_ready(jax.jit(mxtpu_probe_late)(x))
    assert [n for _, n, _, _ in _since(t)].count("mxtpu_probe_late") == 3
    account = san.setup_account(since=t, until=cut)
    assert all(account[p] == 0.0 for p in san.SETUP_PHASES)
    assert account["requests"] == 0
    assert san.setup_account(since=t)["compile"] > 0


def test_the_innermost_span_takes_each_instant_on_a_made_account():
    """Made intervals: an inner trace inside its caller's, a compile of an
    eager program inside a trace, one lowering, a compile that runs past
    the cut, the import; events before and after the cut."""
    acct = san.SetupAccount()
    acct.imported = (0.0, 1.0)
    acct.intervals.extend([
        ("trace", "step", 2.0, 6.0),
        ("trace", "inner", 3.0, 4.0),
        ("compile", "tril", 4.5, 5.0),
        ("lower", "step", 6.0, 7.5),
        ("compile", "step", 8.0, 12.0)])
    acct.events.extend([(4.6, "requests"), (8.5, "requests"),
                        (11.5, "hits"), (11.9, "written")])
    out = acct.read(since=0.5, until=10.0)
    assert out["import"] == pytest.approx(0.5)
    assert out["trace"] == pytest.approx(3.5)
    assert out["lower"] == pytest.approx(1.5)
    assert out["compile"] == pytest.approx(0.5 + 2.0)
    assert (out["requests"], out["hits"], out["misses"], out["written"]) \
        == (2, 0, 2, 0)
    assert dict(out["programs"]["trace"]) == {"step": pytest.approx(2.5),
                                              "inner": pytest.approx(1.0)}
    assert dict(out["programs"]["compile"]) == {
        "step": pytest.approx(2.0), "tril": pytest.approx(0.5)}
    # the phases never overlap: their sum is the union of all intervals
    assert sum(out[p] for p in san.SETUP_PHASES) == pytest.approx(
        0.5 + 4.0 + 1.5 + 2.0)


@pytest.mark.parametrize("name, own", [
    ("mxtpu_step", "mxtpu_step"), ("jit(mxtpu_step)", "mxtpu_step"),
    ("transpose(jvp(mxtpu_grad))", "mxtpu_grad"),
    ("jit(<lambda>)", "<lambda>"), ("<lambda>", "<lambda>")])
def test_a_program_is_named_by_its_function(name, own):
    assert san.program_name(name) == own


def test_compile_seconds_are_fed_in_every_run():
    """No telemetry, no ledger armed: the handle that declares a program's
    name among its ``jit_names`` is charged its trace, lowering and
    compile."""
    h = san.register_cache("test_setup_feed", kind="test",
                           jit_names=("mxtpu_probe_owned",))

    def mxtpu_probe_owned(x):
        return x - 7
    x = jnp.ones((4,), jnp.float32)
    jax.block_until_ready(x)
    t = time.perf_counter()
    jax.block_until_ready(jax.jit(mxtpu_probe_owned)(x))
    mine = sum(b - a for p, n, a, b in _since(t) if n == "mxtpu_probe_owned")
    assert san.compile_seconds()[h.name] == pytest.approx(mine, abs=1e-5)
    assert h.snapshot()["compile_seconds"] > 0


def test_the_registry_spans_are_written_from_the_feed():
    """While the registry records: ``xla_compile`` for a program its first
    dispatch compiles, ``compile.seconds`` for one program_capture
    compiles, each the program's trace + lowering + compile and nothing
    of its execution; the cost row's compile seconds are the same."""
    def mxtpu_probe_first(x):
        return x * x
    def mxtpu_probe_captured(x):
        return (x @ x).sum()
    x = jnp.ones((16, 16), jnp.float32)
    jax.block_until_ready(x)
    tel.start()
    san.cost_arm()
    t = time.perf_counter()
    jax.block_until_ready(jax.jit(mxtpu_probe_first)(x))
    f = jax.jit(mxtpu_probe_captured)
    row = san.program_capture("captured", f, (x,))["cost"]
    jax.block_until_ready(f(x))
    spans = {(e["name"], e["tags"]["kind"]): e for e in tel.events()
             if e["type"] == "span" and e["cat"] == "compile"}
    first = spans[("xla_compile", "mxtpu_probe_first")]
    captured = spans[("compile.seconds", "mxtpu_probe_captured")]
    assert captured["tags"]["program"] == "captured"
    assert ("xla_compile", "mxtpu_probe_captured") not in spans
    for span, name in ((first, "mxtpu_probe_first"),
                       (captured, "mxtpu_probe_captured")):
        mine = sum(b - a for _, n, a, b in _since(t) if n == name)
        assert span["dur"] == pytest.approx(mine * 1e6, abs=100)
        assert span["tags"]["persistent_hit"] is False
    assert row["compile_seconds"] == pytest.approx(
        captured["dur"] * 1e-6, abs=2e-6)


def test_a_recording_session_compiles_the_chunk_program_once():
    """With the cost ledger armed and the registry recording, run_steps'
    chunk program is captured and then dispatched: the feed counts one
    backend compile of ``mxtpu_many``, as without recording."""
    from mxnet_tpu import amp
    from mxnet_tpu.train import TrainStep
    d = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(d, name="fc", num_hidden=4), name="softmax")
    counts = []
    for record in (False, True):
        if record:
            san.cost_arm()
            tel.start()
        ts = TrainStep(net, mx.optimizer.Adam(),
                       policy=amp.Policy("float32", loss_scale=8.0))
        p, s, a = ts.init({"data": (8, 6)}, {"softmax_label": (8,)})
        b = ts.shard_batch({"data": np.zeros((3, 8, 6), np.float32),
                            "softmax_label": np.zeros((3, 8), np.float32)})
        t = time.perf_counter()
        jax.block_until_ready(ts.run_steps(p, s, a, b, 2, stacked=True)[0])
        counts.append([n for p_, n, _, _ in _since(t)
                       if p_ == "compile"].count("mxtpu_many"))
    assert counts == [1, 1]
