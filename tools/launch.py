#!/usr/bin/env python
"""Distributed job launcher (parity: reference tools/launch.py + the dmlc
tracker's `local` launcher — SURVEY.md §2.6).

The reference spawns a ZMQ parameter-server scheduler plus N server and N
worker processes wired together through DMLC_* env vars.  The TPU-native
runtime has no server processes: every process is a worker participating in
XLA collectives, coordinated by the JAX coordination service at process 0.
This launcher therefore only has to start N identical processes with the
MXTPU_* env contract (see mxnet_tpu/parallel/dist.py):

    python tools/launch.py -n 4 python train.py ...

Launch modes:
- ``local`` (default): N processes on this host — the mode the reference's
  nightly dist tests use; on a TPU pod each host runs one process and an
  external scheduler (GKE/SLURM/ray) plays this role instead.  A chip
  belongs to one process at a time, so on a host with TPU chips each of the
  N ranks is given ONE chip of its own (``local_chip_env``); a world the
  chips cannot seat is refused with a message instead of left to hang.
  The launcher itself never imports jax, so it never holds a chip.
- ``ssh``: one process per host listed in --hostfile, sharing the same env
  contract (requires passwordless ssh; mirrors the reference's ssh tracker).
"""
from __future__ import annotations

import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# Observability env vars forwarded to every worker explicitly by launch_ssh,
# which builds a fresh env on the remote side (nothing inherits there);
# launch_local workers receive the launcher's full os.environ, which already
# carries these keys.  MXNET_METRICS_PORT propagates as the BASE endpoint
# verbatim: the per-rank offset (rank N serves on port+N) is applied by
# mxnet_tpu.metrics_server itself from MXTPU_PROCESS_ID, so the offset
# logic lives in exactly one place.
OBSERVABILITY_ENV = ("MXNET_TELEMETRY", "MXNET_METRICS_PORT", "MXNET_DIAG_DIR",
                     "MXNET_WATCHDOG_SEC", "MXNET_CHECK_NUMERICS",
                     # elastic-v2 checkpoint cadence: every worker must
                     # agree on the interval or resume points desync
                     "MXNET_CKPT_EVERY_N_STEPS", "MXNET_CKPT_ASYNC")


def observability_env():
    """The observability contract present in this launcher's environment."""
    return {k: os.environ[k] for k in OBSERVABILITY_ENV if k in os.environ}


# libtpu's process grid for n one-chip ranks on one host (x,y,z)
_TPU_PROCESS_BOUNDS = {1: "1,1,1", 2: "2,1,1", 4: "2,2,1", 8: "2,4,1"}
_TPU_PROCESS_PORT = 8476


def _cpu_world():
    """An explicit JAX_PLATFORMS=cpu: the children never touch a chip."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def local_tpu_chips():
    """How many TPU chips this host exposes, read from the device nodes
    (``/dev/accelN`` up to v4, ``/dev/vfio/N`` from v5e) — never through
    jax, which would claim them.  0 off a TPU host."""
    import glob
    return len(glob.glob("/dev/accel[0-9]*")
               or glob.glob("/dev/vfio/[0-9]*"))


def local_chip_env(rank, n):
    """The env that seats local rank ``rank`` of ``n`` on a chip of its own.

    Without it every child inherits the same environment and claims every
    chip: the first wins and the rest fail at backend start-up or wait for
    a chip that never frees.  Empty off a TPU host, for a single rank (it
    may drive all chips), and under an explicit ``JAX_PLATFORMS=cpu`` (the
    CPU test harness).  Raises SystemExit when the chips cannot seat the
    world."""
    if n == 1 or _cpu_world():
        return {}
    chips = local_tpu_chips()
    if not chips:
        return {}
    if n > chips or n not in _TPU_PROCESS_BOUNDS:
        raise SystemExit(
            "launch.py: cannot seat %d local rank(s) on this host's %d TPU "
            "chip(s): each rank needs a chip of its own (a chip belongs to "
            "one process at a time) and the ranks must form a %s grid.  Run "
            "one process — it drives every chip through a mesh — or set "
            "JAX_PLATFORMS=cpu for a CPU world."
            % (n, chips, "/".join(sorted(_TPU_PROCESS_BOUNDS.values()))))
    return {
        "TPU_VISIBLE_CHIPS": str(rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": _TPU_PROCESS_BOUNDS[n],
        "TPU_PROCESS_ADDRESSES": ",".join(
            "localhost:%d" % (_TPU_PROCESS_PORT + r) for r in range(n)),
        "TPU_PROCESS_PORT": str(_TPU_PROCESS_PORT + rank),
        "CLOUD_TPU_TASK_ID": str(rank),
    }


def launch_local(n, command, env_extra=None, max_restarts=0):
    """Run n copies of `command` locally with the MXTPU_* env contract.

    With ``max_restarts > 0`` acts as an elastic supervisor (parity: the
    role the ps-lite scheduler's heartbeat + re-join machinery plays,
    SURVEY.md §5.3): when any worker dies the whole world is torn down and
    respawned with ``MXTPU_RESTART_COUNT`` incremented, and workers resume
    from their newest checkpoint (mxnet_tpu.parallel.elastic).
    Returns the first non-zero exit code (0 if all succeed)."""
    attempt = 0
    while True:
        port = _free_port()
        procs = []
        for rank in range(n):
            env = dict(os.environ)
            env.update(local_chip_env(rank, n))
            env.update(env_extra or {})
            env["MXTPU_COORDINATOR"] = "localhost:%d" % port
            env["MXTPU_NUM_PROCESSES"] = str(n)
            env["MXTPU_PROCESS_ID"] = str(rank)
            env["MXTPU_RESTART_COUNT"] = str(attempt)
            procs.append(subprocess.Popen(command, env=env))
        rc = 0
        try:
            # poll, don't wait sequentially: a dead worker stalls survivors
            # in collectives forever, so the first non-zero exit must tear
            # the whole world down for the restart to ever fire
            import time
            while True:
                codes = [p.poll() for p in procs]
                failed = [c for c in codes if c not in (None, 0)]
                if failed:
                    rc = failed[0]
                    for p in procs:
                        if p.poll() is None:
                            p.kill()
                    break
                if all(c == 0 for c in codes):
                    break
                time.sleep(0.2)
        except KeyboardInterrupt:
            for p in procs:
                p.send_signal(signal.SIGINT)
            return 1
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        if rc == 0 or attempt >= max_restarts:
            return rc
        attempt += 1
        print("launch.py: worker failed (rc=%d), elastic restart %d/%d"
              % (rc, attempt, max_restarts), file=sys.stderr)


def _write_plan(path, gen, world, coordinator, assign, join=()):
    """Atomically publish a world-plan generation (the supervisor half of
    the protocol mxnet_tpu/parallel/resize.py consumes; same field set as
    resize.write_plan, duplicated so the supervisor stays importable
    without the runtime package).  Write-to-temp + fsync + rename: a
    worker's per-step ``os.stat`` poll never observes a torn plan."""
    import json
    plan = {"gen": int(gen), "world": int(world),
            "coordinator": str(coordinator),
            "assign": {str(k): int(v) for k, v in dict(assign).items()},
            "join": [str(s) for s in join]}
    tmp = "%s.tmp-%d" % (path, os.getpid())
    with open(tmp, "w") as f:
        json.dump(plan, f, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return plan


def launch_elastic(n, command, wmin, wmax, env_extra=None, max_restarts=0,
                   respawn_delay=3.0):
    """Elastic supervisor (elasticity v3, docs/elastic.md "Live resize"):
    run ``n`` workers locally and treat membership changes as LIVE
    TRANSITIONS instead of whole-world restarts.

    Unlike ``launch_local``'s restart mode, a worker death here never
    kills the survivors: as long as ``wmin`` workers remain, the
    supervisor publishes a new world-plan generation (survivors re-rank
    and resize in place via their ResizeController), then — budget
    (``max_restarts``) and cap (``wmax``) permitting — respawns the dead
    slot as a JOIN after ``respawn_delay`` seconds, long enough for the
    survivors to observe the shrink generation first.  A joiner receives
    its resume state over the coordination service from a survivor
    (``MXTPU_ELASTIC_JOIN=1``), not from a checkpoint.

    Every process keeps an immutable ``MXTPU_SLOT`` launch identity; its
    RANK is whatever the current plan generation assigns (a survivor
    becomes rank 0 when the old rank 0 dies).  Each generation gets a
    fresh coordinator port — coordination-service state is single-use.
    Returns the first unrecoverable non-zero exit code (0 otherwise)."""
    import shutil
    import tempfile
    import time
    if not 1 <= wmin <= n <= wmax:
        raise ValueError("--elastic bounds must satisfy 1 <= min <= n <= "
                         "max; got min=%d n=%d max=%d" % (wmin, n, wmax))
    if wmax > 1 and local_tpu_chips() and not _cpu_world():
        # a live resize re-ranks processes and changes the world size;
        # libtpu's process grid is fixed when a process claims its chip
        raise SystemExit(
            "launch.py: --elastic worlds of more than one local rank are "
            "not supported on a TPU host: each rank owns one chip through "
            "a process grid that cannot be resized live.  Use "
            "--max-restarts (whole-world respawn), or JAX_PLATFORMS=cpu.")
    plan_dir = tempfile.mkdtemp(prefix="mxtpu-elastic-")
    plan_path = os.path.join(plan_dir, "world_plan.json")
    gen = 1
    assign = {str(i): i for i in range(n)}
    plan = _write_plan(plan_path, gen, n, "localhost:%d" % _free_port(),
                       assign)
    procs = {}
    respawns = 0

    def spawn(slot, plan, join=False):
        env = dict(os.environ)
        env.update(env_extra or {})
        env["MXTPU_COORDINATOR"] = plan["coordinator"]
        env["MXTPU_NUM_PROCESSES"] = str(plan["world"])
        env["MXTPU_PROCESS_ID"] = str(plan["assign"][slot])
        env["MXTPU_SLOT"] = slot
        env["MXTPU_RESTART_COUNT"] = str(respawns)
        env["MXNET_ELASTIC_PLAN"] = plan_path
        if join:
            env["MXTPU_ELASTIC_JOIN"] = "1"
        else:
            env.pop("MXTPU_ELASTIC_JOIN", None)
        procs[slot] = subprocess.Popen(command, env=env)

    for i in range(n):
        spawn(str(i), plan)
    rc_final = 0
    try:
        while True:
            dead = []
            for slot in sorted(procs):
                c = procs[slot].poll()
                if c == 0:
                    del procs[slot]    # finished cleanly — not a failure
                elif c is not None:
                    dead.append((slot, c))
                    del procs[slot]
            if not procs and not dead:
                return rc_final
            if dead:
                for slot, c in dead:
                    print("launch.py: slot %s died (rc=%d)" % (slot, c),
                          file=sys.stderr)
                survivors = sorted(procs)
                if len(survivors) < wmin:
                    print("launch.py: %d survivor(s) < --elastic min %d — "
                          "tearing the world down" % (len(survivors), wmin),
                          file=sys.stderr)
                    return dead[0][1]
                # SHRINK generation: survivors re-rank 0..k-1 and resize
                # in place — no process is killed or restarted
                gen += 1
                assign = {s: r for r, s in enumerate(survivors)}
                plan = _write_plan(plan_path, gen, len(survivors),
                                   "localhost:%d" % _free_port(), assign)
                print("launch.py: plan gen %d — world shrinks to %d "
                      "(survivors resize in place)" % (gen, len(survivors)),
                      file=sys.stderr)
                # re-GROW: respawn dead slots as JOINS while the restart
                # budget and the world cap allow
                joiners = []
                for slot, _c in dead:
                    if respawns >= max_restarts:
                        break
                    if len(survivors) + len(joiners) >= wmax:
                        break
                    respawns += 1
                    joiners.append(slot)
                if joiners and survivors:
                    # survivors must observe (and complete) the shrink
                    # generation before the join generation lands
                    time.sleep(respawn_delay)
                    gen += 1
                    assign = {s: r for r, s in enumerate(survivors)}
                    for slot in sorted(joiners):
                        assign[slot] = len(assign)
                    plan = _write_plan(plan_path, gen,
                                       len(survivors) + len(joiners),
                                       "localhost:%d" % _free_port(),
                                       assign, join=joiners)
                    print("launch.py: plan gen %d — world grows to %d "
                          "(slot(s) %s join live)"
                          % (gen, plan["world"], ",".join(sorted(joiners))),
                          file=sys.stderr)
                    for slot in joiners:
                        spawn(slot, plan, join=True)
            time.sleep(0.2)
    except KeyboardInterrupt:
        for p in procs.values():
            p.send_signal(signal.SIGINT)
        return 1
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        shutil.rmtree(plan_dir, ignore_errors=True)


def launch_ssh(hosts, command, env_extra=None):
    """One process per host over ssh; process 0's host is the coordinator."""
    port = _free_port()
    coord = "%s:%d" % (hosts[0], port)
    procs = []
    for rank, host in enumerate(hosts):
        env = observability_env()
        env.update({"MXTPU_COORDINATOR": coord,
                    "MXTPU_NUM_PROCESSES": str(len(hosts)),
                    "MXTPU_PROCESS_ID": str(rank)})
        env.update(env_extra or {})
        env_str = " ".join("%s=%s" % (k, shlex.quote(v))
                           for k, v in env.items())
        cmd_str = " ".join(shlex.quote(c) for c in command)
        procs.append(subprocess.Popen(
            ["ssh", "-o", "StrictHostKeyChecking=no", host,
             "cd %s && env %s %s" % (shlex.quote(os.getcwd()), env_str,
                                     cmd_str)]))
    rc = 0
    for p in procs:
        prc = p.wait()
        rc = rc or prc
    return rc


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--launcher", choices=("local", "ssh"), default="local")
    ap.add_argument("--hostfile", default=None,
                    help="file with one host per line (ssh launcher)")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="elastic supervision: respawn the world up to this "
                         "many times after a worker failure (with --elastic: "
                         "the JOIN respawn budget — dead ranks re-enter the "
                         "live world instead of restarting it)")
    ap.add_argument("--elastic", default=None, metavar="MIN:MAX",
                    help="live-resize supervision (local launcher only): "
                         "keep survivors alive through worker deaths while "
                         "at least MIN remain, growing back up to MAX by "
                         "respawning dead slots as live joins "
                         "(docs/elastic.md \"Live resize\")")
    ap.add_argument("--respawn-delay", type=float, default=3.0,
                    help="--elastic: seconds between publishing a shrink "
                         "generation and respawning the dead slot as a join "
                         "(survivors must observe the shrink first)")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")
    if args.elastic is not None and args.launcher != "local":
        ap.error("--elastic requires the local launcher")
    if args.launcher == "local" and args.elastic is not None:
        try:
            wmin, wmax = (int(v) for v in args.elastic.split(":"))
        except ValueError:
            ap.error("--elastic expects MIN:MAX (e.g. 1:4)")
        rc = launch_elastic(args.num_workers, args.command, wmin, wmax,
                            max_restarts=args.max_restarts,
                            respawn_delay=args.respawn_delay)
    elif args.launcher == "local":
        rc = launch_local(args.num_workers, args.command,
                          max_restarts=args.max_restarts)
    else:
        with open(args.hostfile) as f:
            hosts = [h.strip() for h in f if h.strip()]
        rc = launch_ssh(hosts[:args.num_workers], args.command)
    sys.exit(rc)


if __name__ == "__main__":
    main()
