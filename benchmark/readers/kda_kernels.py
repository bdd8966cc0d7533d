"""Layer: the linear-attention mixer (``ops/kda.py``), how much of its
device time is the gated delta rule's own Pallas kernels
(``ops/pallas_kernels.py``: ``mxtpu_kda_fwd``, ``mxtpu_kda_states``,
``mxtpu_kda_bwd``).  A kernel's device operation is named after its
``pallas_call``'s ``name``; the ones that autodiff places carry its wrappers
in front (``transpose_jvp_mxtpu_kda_bwd__.1``), so an operation counts where
its name holds ``mxtpu_kda_``.  Beside ``kda.scan_ms`` (everything under the
scopes ``kda_conv`` + ``kda_scan``) it says what is left to the
convolutions, the l2 norms, the gates and the layout round the kernels.  A
program without the kernels (one from before them, a shape their guard
refuses) reads as nothing."""

PREFIX = "mxtpu_kda_"


def kernel_ms(ctx):
    """Device milliseconds a step in the rule's kernels, on the slowest
    device, inside the traced window."""
    t0, t1 = ctx.plain["window"]
    seconds = [min(end, t1) - max(start, t0)
               for name, start, end, _, _ in
               ctx.plain["devices"][ctx.reduced["slowest"]]
               if PREFIX in name and min(end, t1) > max(start, t0)]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / ctx.reduced["steps"]
