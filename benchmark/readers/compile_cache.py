"""Layer: the compile cache (``base.enable_compile_cache``)."""


def programs_compiled(ctx):
    """Backend compiles inside the window (compile requests that the
    persistent cache did not answer); must read 0."""
    return float(ctx.compiles_in_window)
