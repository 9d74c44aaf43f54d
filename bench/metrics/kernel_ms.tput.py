"""Device ms per window step of the Pallas kernels, from the trace."""


def read(ctx):
    """Mean kernel device time of a traced window step, or None."""
    steps = ctx.trace["steps"]
    if not steps:
        return None
    return ctx.trace["kernel_total_s"] / steps * 1e3
