#!/usr/bin/env python3
"""Bytes of a benchmark cell's chunk program (``TrainStep.run_steps``, as
``benchmark/entries/run_steps.py`` builds it) compiled for a described v5e:
no chip is attached and nothing runs.  Prints one JSON line of
``memory_analysis()``; with ``--dump DIR`` XLA's buffer assignment is
written there too (``*buffer-assignment.txt``), and ``--live N`` reads from
it the scratch allocation's extent, the largest sum of its buffers live at
one position of the schedule, and the N largest of those buffers.  With
``--sha`` nothing is compiled: the line holds the sha256 and the length of
the program's StableHLO, which two trees run from one path share where the
program did not change (a Pallas kernel's payload carries the source
locations of its call stack).

  JAX_PLATFORMS=cpu python3 tools/step_memory.py kimi-linear-steps-t4096 \
      --dump /tmp/kimi --live 12

The kernels are the chip's: ``jax.default_backend()`` answers "tpu" while
the program is traced, so each op takes the path it takes on the chip.  The
chip books a program's scratch (``temp``) as ``peak_bytes_reserved``: PERF.md
7 (PR 38) holds the two against each other.  A count, never a time; a
compile at the cell's full size takes about a minute and a half here."""
import argparse
import collections
import glob
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


class _Built(Exception):
    pass


class _Capture(dict):
    """Stands for ``TrainStep._multi_cache``: keeps the chunk program that
    ``run_steps`` builds and stops it before the call."""

    def __setitem__(self, key, fn):
        self.fn = fn
        raise _Built


def chunk_program(cell_name, dump=None, compiled=True):
    """The cell's chunk program lowered and compiled (or, without
    ``compiled``, lowered alone) for one described v5e chip."""
    if dump:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_dump_to=%s" % dump).strip()
    import importlib
    import numpy as np
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu.train import TrainStep
    from benchmark.cells import Cell
    from benchmark.reference.train import family

    jax.config.update("jax_enable_compilation_cache", False)
    cell = Cell(cell_name)
    cfg, tr = cell.config, cell.traffic
    if tr["entry"] != "run_steps":
        raise SystemExit("%s: entry %r, not run_steps" % (cell_name,
                                                          tr["entry"]))
    batch, chunk = int(tr["batch"]), int(tr["chunk"])
    seq = cfg["max_position_embeddings"]
    net = importlib.import_module(cfg["symbol"]["module"]).get_symbol(
        **cfg["symbol"]["args"])
    opt = dict(cfg["optimizer"])
    name = opt.pop("name")
    optimizer = mx.optimizer.create(name, rescale_grad=1.0 / (batch * seq),
                                    **opt)
    dn, ln = cfg["data"]["name"], cfg["label"]["name"]
    ts = TrainStep(net, optimizer, data_names=(dn,), label_names=(ln,),
                   policy=amp.Policy(cfg["precision"]["compute"]))
    shapes = family(cfg).param_shapes(cfg)
    slots = {k: len(v) for k, v in ts.fopt.init_state(
        {k: np.zeros(1, np.float32) for k in shapes}).items()}

    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)
    params = {k: spec(s, np.float32) for k, s in shapes.items()}
    state = {k: tuple(params[k] for _ in range(slots[k])) for k in shapes}
    stacked = {dn: spec((chunk, batch, seq), np.int32),
               ln: spec((chunk, batch, seq), np.float32)}
    ts._multi_cache = cap = _Capture()
    try:
        ts.run_steps(params, state, {}, stacked, chunk - 1, stacked=True)
    except _Built:
        pass
    args = [params, state, {}]
    if ts._has_scale:
        args.append(ts._scale_state_dev())
    args += [stacked, jax.random.PRNGKey(0), ts.fopt.hyper(0), np.int32(0)]
    args = jax.tree_util.tree_map(
        lambda x: x if isinstance(x, jax.ShapeDtypeStruct)
        else spec(np.shape(x), np.asarray(x).dtype), args)
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        lowered = cap.fn.lower(*args)
        return lowered.compile() if compiled else lowered
    finally:
        jax.default_backend = real


_ALLOC = re.compile(r"allocation \d+: size (\d+)(.*)")
_VALUE = re.compile(r" value: <\d+ (\S+?)(\{[\d,]*\})? @\d+> "
                    r"\(size=(\d+),offset=(\d+)\): (.*)")
_RANGE = re.compile(r"    (\S+):(\d+)-(\d+)$")


def live_at_peak(text):
    """From a buffer-assignment dump: the scratch allocation's extent (its
    uncoloured ``preallocated-temp``, which the chip books as reserved),
    the largest sum of its buffers live at one schedule position, that
    position, and those buffers as (bytes, name, shape), largest first."""
    values, ranges, inside, in_ranges = [], {}, False, False
    for line in text.splitlines():
        m = _ALLOC.match(line)
        if m:
            inside = "preallocated-temp" in m.group(2) \
                and "color" not in m.group(2)
            continue
        if line == "  BufferLiveRange:":
            in_ranges = True
            continue
        if in_ranges:
            m = _RANGE.match(line)
            if not m:
                in_ranges = False
                continue
            ranges[m.group(1)] = (int(m.group(2)), int(m.group(3)))
        elif inside:
            m = _VALUE.match(line)
            if m:
                values.append((m.group(1) + (m.group(2) or "{}"),
                               int(m.group(3)), int(m.group(4)),
                               m.group(5)))
            else:
                inside = False
    steps = collections.Counter()
    for name, size, _, _ in values:
        start, end = ranges[name]
        steps[start] += size
        steps[end + 1] -= size
    total = peak = at = 0
    for t in sorted(steps):
        total += steps[t]
        if total > peak:
            peak, at = total, t
    live = sorted(((size, name, shape.split("{")[0])
                   for name, size, _, shape in values
                   if ranges[name][0] <= at <= ranges[name][1]),
                  reverse=True)
    return {"extent": max((o + s for _, s, o, _ in values), default=0),
            "live_peak": peak, "at": at, "live": live}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--dump", help="directory for XLA's dumps")
    ap.add_argument("--live", type=int, metavar="N",
                    help="the N largest buffers live at the scratch's "
                    "fullest (needs --dump)")
    ap.add_argument("--sha", action="store_true",
                    help="the StableHLO's sha256 and length; nothing is "
                    "compiled")
    a = ap.parse_args(argv)
    if a.live and not a.dump:
        ap.error("--live reads the dump: give --dump")
    if a.sha:
        text = chunk_program(a.workload, compiled=False).as_text()
        print(json.dumps({"workload": a.workload, "stablehlo_bytes": len(
            text), "stablehlo_sha256": hashlib.sha256(
                text.encode()).hexdigest()}))
        return
    mem = chunk_program(a.workload, a.dump).memory_analysis()
    row = {k: getattr(mem, k + "_size_in_bytes") for k in (
        "argument", "output", "alias", "temp", "generated_code")}
    row["workload"] = a.workload
    if a.live:
        path, = glob.glob(os.path.join(
            a.dump, "*jit_mxtpu_many*after_optimizations-buffer-"
            "assignment.txt"))
        with open(path) as f:
            found = live_at_peak(f.read())
        found["live"] = found["live"][:a.live]
        row.update(found)
    print(json.dumps(row))


if __name__ == "__main__":
    main()
