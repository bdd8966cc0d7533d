"""Layer: the state-space mixer (``ops/ssm.py``), how much of its device
time is the scan's own Pallas kernels (``ops/pallas_kernels.py``:
``mxtpu_ssd_fwd``, ``mxtpu_ssd_states``, ``mxtpu_ssd_bwd``).  A kernel's
device operation is named after its ``pallas_call``'s ``name``; the ones
that autodiff places carry its wrappers in front
(``transpose_jvp_mxtpu_ssd_bwd__.1``), so an operation counts where its
name holds ``mxtpu_ssd_``.  Beside ``ssm.scan_ms`` (everything under the
scopes ``mamba_conv`` + ``mamba_ssd``) it says what is left to the
convolution, the norm and the layout round the kernels.  A program without
the kernels (one from before them, a shape their guard refuses) reads as
nothing."""

PREFIX = "mxtpu_ssd_"


def kernel_ms(ctx):
    """Device milliseconds a step in the scan's kernels, on the slowest
    device, inside the traced window."""
    t0, t1 = ctx.plain["window"]
    seconds = [min(end, t1) - max(start, t0)
               for name, start, end, _, _ in
               ctx.plain["devices"][ctx.reduced["slowest"]]
               if PREFIX in name and min(end, t1) > max(start, t0)]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / ctx.reduced["steps"]
