"""The traced window: the benchmark's own host spans written into the
profiler's trace, and the reduction from that trace to what the per-layer
readers use.  The reduction works on a plain form of the trace,

  {"window": [t0, t1],                        seconds on the trace's clock
   "devices": {id: [[name, start, end, kind, hlo], ...]},  device operations
   "modules": {id: [[name, start, end], ...]},         whole programs
   "host": [[name, start, end], ...]}                  the benchmark's spans

which ``from_profile`` makes from ``jax.profiler.ProfileData`` and a test
keeps a small recorded copy of.  ``kind`` is "collective", "transfer" or
"compute", by the operation's HLO name."""
import glob
import os
import re
import shutil

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
TRANSFERS = ("infeed", "outfeed", "copy-start", "copy-done", "host-transfer",
             "transfer")
# operations that only hold others (a scan's loop, the overflow branch): their
# events span their bodies' and would count the same time twice
CONTAINERS = ("while", "conditional", "call")
PREFIX = "bench:"
LAYOUT = re.compile(r"\{[^{}]*\}")


class Inconsistent(Exception):
    """The trace does not reconcile; the run is printed as failed."""


class Tracer:
    """Starts and stops the profiler around the window and writes the
    benchmark's spans into its trace."""

    def __init__(self, directory):
        self.dir = directory
        self._open = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        jax.profiler.stop_trace()

    def span(self, name):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def batch_boundary(self):
        """``bench:fit_batch`` runs from one batch-end callback to the next:
        the only points of the program's loop that the benchmark sees."""
        import jax
        if self._open is not None:
            self._open.__exit__(None, None, None)
        self._open = jax.profiler.TraceAnnotation(PREFIX + "fit_batch")
        self._open.__enter__()

    def profile(self):
        import jax
        paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise Inconsistent("the profiler wrote no trace under %s"
                               % self.dir)
        return jax.profiler.ProfileData.from_file(paths[0])

    def discard(self):
        """The trace is read once; what is written stays small."""
        shutil.rmtree(self.dir, ignore_errors=True)


def split_hlo(text):
    """An operation's event name is its HLO line, ``%name = result
    opcode(operands)``: (name, opcode, "result opcode(" without layouts)."""
    name, _, rest = text.partition(" = ")
    rest = LAYOUT.sub("", rest)
    words = re.findall(r"\)?\s*([a-z][a-z0-9\-]*)\(", rest)
    opcode = words[0] if words else ""
    return name.lstrip("%"), opcode, rest[:240]


def op_kind(opcode):
    if any(opcode.startswith(c) for c in COLLECTIVES):
        return "collective"
    if any(opcode.startswith(t) for t in TRANSFERS):
        return "transfer"
    return "compute"


def from_profile(profile):
    """``ProfileData`` -> the plain form.  Device planes are ``/device:TPU:n``;
    their ``XLA Ops`` line holds the operations and ``XLA Modules`` the whole
    programs.  The benchmark's spans are the host events whose name starts
    with ``bench:``."""
    plain = {"devices": {}, "modules": {}, "host": []}
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name.rsplit(":", 1)[1]
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = []
                    for e in line.events:
                        name, opcode, hlo = split_hlo(e.name)
                        if opcode in CONTAINERS:
                            continue
                        ops.append([name, e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9,
                                    op_kind(opcode), hlo])
                    plain["devices"][dev] = ops
                elif line.name == "XLA Modules":
                    plain["modules"][dev] = [
                        [e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        plain["host"].append(
                            [e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9])
    plain["host"].sort(key=lambda s: s[1])
    if not plain["host"]:
        raise Inconsistent("the trace holds none of the benchmark's spans")
    plain["window"] = [plain["host"][0][1],
                       max(s[2] for s in plain["host"])]
    return plain


def union(intervals):
    """Sorted, merged [start, end] intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, t0, t1):
    return [[max(a, t0), min(b, t1)] for a, b in intervals
            if min(b, t1) > max(a, t0)]


def length(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """The parts of merged ``intervals`` outside merged ``holes``."""
    out = []
    for a, b in intervals:
        at = a
        for c, d in holes:
            if d <= at or c >= b:
                continue
            if c > at:
                out.append([at, c])
            at = max(at, d)
        if at < b:
            out.append([at, b])
    return out


def reduce(plain, steps):
    """The plain trace -> the figures the readers share.  Raises
    ``Inconsistent`` where busy + idle is not the window, or where the step
    programs' own time, times the steps, is not the operations' busy time
    to within 5%."""
    t0, t1 = plain["window"]
    window = t1 - t0
    if window <= 0 or not plain["devices"]:
        raise Inconsistent("no device operations or an empty window")
    per_dev = {}
    for dev, ops in plain["devices"].items():
        busy = union(clip([[o[1], o[2]] for o in ops], t0, t1))
        gaps = subtract([[t0, t1]], busy)
        if abs(length(busy) + length(gaps) - window) > 1e-6 * window:
            raise Inconsistent("device %s: busy %.6f + idle %.6f is not the "
                               "window %.6f" % (dev, length(busy),
                                                length(gaps), window))
        per_dev[dev] = {"busy": length(busy), "gaps": gaps}
    # the step program: the module that takes most of the device's time
    mods = {}
    for dev, events in plain["modules"].items():
        inside = [m for m in events if m[1] >= t0 and m[2] <= t1]
        by_name = {}
        for m in inside:
            by_name.setdefault(m[0], []).append(m[2] - m[1])
        if by_name:
            name = max(by_name, key=lambda k: sum(by_name[k]))
            mods[dev] = (name, by_name[name])
    slow = max(per_dev, key=lambda d: per_dev[d]["busy"])
    out = {"window_s": window,
           "busy_s": sum(d["busy"] for d in per_dev.values()) / len(per_dev),
           "slowest": slow, "per_device": per_dev, "steps": steps}
    if slow in mods and mods[slow][1]:
        name, durs = mods[slow]
        out["step_program"] = name
        out["step_runs"] = len(durs)
        out["step_device_s"] = sum(durs) / steps
        busy = per_dev[slow]["busy"]
        if abs(sum(durs) - busy) > 0.05 * busy:
            raise Inconsistent(
                "the step program %s ran %d times for %.4f s, but the "
                "device's operations were busy for %.4f s: more than 5%% "
                "apart" % (name, len(durs), sum(durs), busy))
    else:
        raise Inconsistent("no whole-program events on device %s" % slow)
    return out


def top_ops(plain, dev, n=10):
    t0, t1 = plain["window"]
    total = {}
    for name, a, b, _, hlo in plain["devices"][dev]:
        a, b = max(a, t0), min(b, t1)
        if b > a:
            label = (name + " " + hlo.split("(%", 1)[0])[:64]
            total[label] = total.get(label, 0.0) + (b - a)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_span(plain, dev, gaps, n=10):
    """Each idle gap goes to the benchmark's span that was open at its
    middle (the innermost, which starts last), or to ``no_span``."""
    total = {}
    spans = plain["host"]
    for a, b in gaps:
        mid = 0.5 * (a + b)
        owner = "no_span"
        for name, s, e in spans:
            if s <= mid <= e:
                owner = name
        total[owner] = total.get(owner, 0.0) + (b - a)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]

