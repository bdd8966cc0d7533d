"""Device time by ``jax.named_scope``: the layers of the hybrid language
model (``mamba_conv``, ``mamba_ssd``, ``moe_route``, ``moe_experts``,
``moe_shared``, ``attention``), which the executor opens round each node of
a scoped part, forward and backward.

A device operation's event carries its HLO line and no scope.  The scope is
in the trace all the same: the device plane's event metadata holds, for
every operation, its framework name (``jit(mxtpu_many)/.../transpose(jvp(
mamba_ssd))/dot_general``; of a fusion, its root's).  ``ProfileData`` does
not hand that out, so the trace's file is read here as the plain protobuf it
is (``XSpace``; the field numbers are those of ``xplane.proto``).  A trace
without such names, a program without such scopes, and a context without a
trace file all read as nothing."""
import glob
import os
import re

from benchmark import trace
from benchmark.flops import moe as moe_flops
from benchmark.flops import ssd as ssd_flops
from benchmark.readers import moe as moe_counters


# ------------------------------------------------- the protobuf wire format
def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, at


def _fields(buf):
    """(field number, wire type, value) of one message's fields; a
    length-delimited value is a memoryview of its bytes."""
    buf = memoryview(buf)
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 1:
            value, at = bytes(buf[at:at + 8]), at + 8
        elif wire == 5:
            value, at = bytes(buf[at:at + 4]), at + 4
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError("wire type %d" % wire)
        yield number, wire, value


def framework_names(path):
    """{device plane's name: {operation's event name: framework name}} of
    an ``.xplane.pb``.  XSpace.planes = 1; XPlane.name = 2, .event_metadata
    = 4 and .stat_metadata = 5 (maps: key 1, value 2); XEventMetadata.name =
    2, .stats = 5; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7;
    XStatMetadata.name = 2."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for number, _, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for n, _, v in _fields(plane):
            if n == 2:
                name = bytes(v).decode("utf-8", "replace")
            elif n in (4, 5):
                entry = dict((k, val) for k, _, val in _fields(v))
                if n == 4 and 2 in entry:
                    events.append(entry[2])
                elif n == 5 and 2 in entry:
                    meta = dict((k, val) for k, _, val in _fields(entry[2]))
                    stat_names[entry.get(1, 0)] = bytes(
                        meta.get(2, b"")).decode("utf-8", "replace")
        if not name.startswith("/device:TPU:"):
            continue
        wanted = {i for i, s in stat_names.items() if s == "tf_op"}
        names = {}
        for meta in events:
            event_name, framework = None, None
            for n, _, v in _fields(meta):
                if n == 2:
                    event_name = bytes(v).decode("utf-8", "replace")
                elif n == 5:
                    stat = dict((k, val) for k, _, val in _fields(v))
                    if stat.get(1) not in wanted:
                        continue
                    if 5 in stat:
                        framework = bytes(stat[5]).decode("utf-8", "replace")
                    elif 7 in stat:
                        framework = stat_names.get(stat[7])
            if event_name and framework:
                names[event_name] = framework
        out[name] = names
    return out


def _trace_file(ctx):
    found = glob.glob(os.path.join(
        ctx.cell.root, ".bench_trace", ctx.cell.name, "plugins", "profile",
        "*", "*.xplane.pb"))
    return found[0] if found else None


def scope_seconds(profile, names, scopes, device, t0, t1):
    """(seconds, operations counted) inside [t0, t1] of the operations of
    ``device`` that belong to one of ``scopes``.  An operation the trace
    names belongs if its framework name has one of them as a scope of its
    path (bare, or inside autodiff's ``jvp(...)`` / ``transpose(...)``).
    The compiler leaves some operations unnamed (fusions that its late
    passes make: running sums, layout copies): such a one belongs if the
    named operations that ran just before and just after it both do, the
    device running one operation at a time in program order.  Operations
    that only hold others are left out, as in the plain trace."""
    pattern = re.compile(r"(?<![A-Za-z0-9_])(%s)(?![A-Za-z0-9_])"
                         % "|".join(re.escape(s) for s in scopes))
    plane_name = "/device:TPU:%s" % device
    known = names.get(plane_name, {})
    ops = []                    # (start, seconds inside, True/False/None)
    for plane in profile.planes:
        if plane.name != plane_name:
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                if trace.split_hlo(e.name)[1] in trace.CONTAINERS:
                    continue
                a = max(e.start_ns * 1e-9, t0)
                b = min((e.start_ns + e.duration_ns) * 1e-9, t1)
                if b > a:
                    framework = known.get(e.name)
                    ops.append((a, b - a, None if not framework
                                else bool(pattern.search(framework))))
    ops.sort()
    before, last = [], None
    for _, _, inside in ops:
        before.append(last)
        last = inside if inside is not None else last
    total, count, following = 0.0, 0, None
    for (_, seconds, inside), prev in zip(reversed(ops), reversed(before)):
        if inside or (inside is None and prev and following):
            total += seconds
            count += 1
        following = inside if inside is not None else following
    return total, count


def _seconds_a_step(ctx, scopes):
    profile, path = getattr(ctx, "profile", None), _trace_file(ctx)
    if profile is None or path is None:
        return None
    t0, t1 = ctx.plain["window"]
    seconds, count = scope_seconds(profile, framework_names(path), scopes,
                                   ctx.reduced["slowest"], t0, t1)
    return seconds / ctx.reduced["steps"] if count else None


# ------------------------------------------------------------- the metrics
def scope_ms(ctx, scopes):
    """Device milliseconds a step under these scopes."""
    seconds = _seconds_a_step(ctx, scopes)
    return None if seconds is None else 1e3 * seconds


def ssd_scan_roofline(ctx, scopes):
    """Least seconds of the convolution-and-scan of the step's state-space
    layers (``flops/ssd.py``, forward and backward) over their device
    seconds."""
    seconds = _seconds_a_step(ctx, scopes)
    if not seconds:
        return None
    cfg = ctx.cell.config
    tokens = int(ctx.cell.traffic["batch"]) * cfg["max_position_embeddings"]
    least, _bound = ssd_flops.least_seconds(
        cfg, tokens, ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    layers = cfg["hybrid_override_pattern"].count("M")
    return 100.0 * least * layers / seconds


def moe_experts_roofline(ctx, scopes):
    """Least seconds of the routed experts' products at the assignments the
    program COUNTED (``flops/moe.py``, each expert layer at its own count a
    step, the held experts' weights read once a pass) over the device
    seconds under ``moe_experts``."""
    seconds = _seconds_a_step(ctx, scopes)
    counted, steps = moe_counters.counted(ctx)
    if not seconds or counted is None:
        return None
    least = sum(moe_flops.least_seconds(
        ctx.cell.config, float(landed) / steps,
        ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"])[0]
        for landed in counted[:, 0])
    return 100.0 * least / seconds
