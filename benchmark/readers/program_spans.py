"""The program's own spans (``mxnet_tpu/telemetry.py`` ``span``): host events
named ``mx:<span>`` that the program writes into the profiler's trace, on the
clock of the device's operations.  The plain form keeps only the benchmark's
``bench:`` spans, so these are read from ``ctx.profile``.  A program that has
no such span (one from before the spans, or a cell that never reaches the
layer) reads as nothing, and so does a context without a profile."""


def _clipped(ctx, names):
    """Seconds of every host event named in ``names``, each cut to the
    window; an event outside the window is left out."""
    t0, t1 = ctx.plain["window"]
    out = []
    profile = getattr(ctx, "profile", None)
    for plane in profile.planes if profile is not None else ():
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name not in names:
                    continue
                a = max(e.start_ns * 1e-9, t0)
                b = min((e.start_ns + e.duration_ns) * 1e-9, t1)
                if b > a:
                    out.append(b - a)
    return out


def mean_ms(ctx, names):
    """Mean duration of the window's spans of these names."""
    spans = _clipped(ctx, names)
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)


def per_step_ms(ctx, names):
    """All the window's time under spans of these names, over its steps."""
    spans = _clipped(ctx, names)
    if not spans:
        return None
    return 1e3 * sum(spans) / ctx.reduced["steps"]
