"""Device ms per window step of the step's XLA ops outside the Pallas
kernels (routing top_k, state gathers and scatters, decay), from the trace."""


def read(ctx):
    """Mean non-kernel device time of a traced window step, or None."""
    steps = ctx.trace["steps"]
    if not steps:
        return None
    return ctx.trace["step_xla_s"] / steps * 1e3
