#!/usr/bin/env python3
"""The readings that the limits are set from, taken on the chip at a cell's
own size, each against the float32 reference of the same seed as
``check.numbers`` reads them, and each judged by ``check.decide`` under the
cell's committed limits (``correct`` in every line printed):

  control   the reference put in the program's place with every product in
            fp8, the precision below the bfloat16 that the configurations
            state
  half      the reference put in the program's place with half of each
            batch left out, the mean taken over the rest
  bfloat16  a witness, not a limit: the reference with every product in the
            bfloat16 that the configurations state, to tell what of the
            program's gap is that precision's own
  program   the program's own first steps, for an entry that can be loaded
            with seed after seed in one process (``run_steps``: the program
            is built once, so a dozen seeds cost one set-up).  Every run of
            ``run.py`` prints the same for its seed.

  python3 benchmark/calibrate.py --workload <name> --seeds 1 2 3 [--out FILE]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import cells, check, gen  # noqa: E402
from benchmark.reference import train as ref  # noqa: E402


def first_batches(cell, seed):
    """The first steps' batches exactly as the cell's entry feeds them."""
    cfg, tr = cell.config, cell.traffic
    b = int(tr["batch"])
    if tr["entry"] == "fit":
        d = cfg["data"]
        x, y = gen.device_images(seed, int(tr["pool_batches"]), b,
                                 cfg["image_shape"], cfg["num_classes"],
                                 d["low"], d["high"])
        return [(x[i], y[i]) for i in range(int(tr["check_steps"]))]
    chunk = int(tr["chunk"])
    data, label = gen.device_tokens(seed, (1 + int(tr["pool_chunks"])) * chunk,
                                    b, cfg["max_position_embeddings"],
                                    cfg["vocab_size"])
    return [(data[i], label[i]) for i in range(chunk)]


def program_readings(cell, seeds):
    """{seed: what the program's first steps read}, the program built once
    and freed before any reference runs."""
    entry = cell.entry().Entry(cell, seeds[0], 0.0)
    entry.build()
    out = {}
    for seed in seeds:
        entry.load(seed)
        out[seed] = entry.first_steps()
    entry.release()
    return out


def readings(cell, seed, which, leaves=None, program=None):
    """{reading: {number: value}}; with ``leaves`` a dict, both sides of
    every comparison are left in it leaf by leaf.  ``program`` is what
    ``program_readings`` read for this seed."""
    cfg = cell.config
    shapes = ref.family(cfg).param_shapes(cfg)

    def params():
        return gen.make_weights(shapes, cfg["init"], seed)
    batches = first_batches(cell, seed)
    matrices = [k for k, v in shapes.items() if len(v) > 1]
    exact = ref.follow(cfg, params, batches)
    out = {}
    for name in which:
        if name == "program":
            other = program
        elif name == "half":
            other = ref.follow(cfg, params, [
                (d[:d.shape[0] // 2], l[:l.shape[0] // 2])
                for d, l in batches])
        else:
            other = ref.follow(cfg, params, batches, quantiser={
                "control": "fp8"}.get(name, name))
        out[name] = {k: v[0] for k, v in
                     check.numbers(other, exact, matrices).items()}
        if leaves is not None:
            leaves[name] = other
    if leaves is not None:
        leaves["exact"] = exact
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--which", nargs="+", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--leaves", action="store_true",
                    help="keep every leaf's norms in --out, not only the "
                         "numbers")
    args = ap.parse_args()
    cell = cells.Cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: readings come from the chip only", file=sys.stderr)
        return 1
    from mxnet_tpu.base import enable_compile_cache
    enable_compile_cache()
    which = args.which or ["control", "half"]
    program = program_readings(cell, args.seeds) if "program" in which \
        else {}
    rows = []
    for seed in args.seeds:
        leaves = {} if args.leaves else None
        t = time.perf_counter()
        read = readings(cell, seed, which, leaves, program.get(seed))
        row = {"workload": cell.name, "seed": seed,
               "seconds": time.perf_counter() - t, "readings": read,
               "correct": {name: check.decide(
                   {k: (v, None) for k, v in nums.items()}, cell.limits)[0]
                   for name, nums in read.items()}}
        print(json.dumps(row), flush=True)
        rows.append(dict(row, leaves=leaves) if leaves else row)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
