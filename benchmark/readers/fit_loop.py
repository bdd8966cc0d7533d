"""Layer: the fit loop (``module/base_module.py`` ``_fit_impl``,
``_FusedFit``).  Read from the traced window and the step program's device
time."""


def host_ms_per_batch(ctx):
    """The part of a batch's wall time in which the device was not running
    the step program: window / batches - step.device_ms."""
    per_batch = ctx.reduced["window_s"] / ctx.reduced["steps"]
    host = per_batch - ctx.reduced["step_device_s"]
    if host < 0:
        raise ctx.Inconsistent("a batch took %.3f ms of wall time but its "
                               "step %.3f ms of device time" % (
                                   1e3 * per_batch,
                                   1e3 * ctx.reduced["step_device_s"]))
    return 1e3 * host
