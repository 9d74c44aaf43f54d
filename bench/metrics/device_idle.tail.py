"""Share of the traced window in which no op ran on the device (1 - the
union of busy intervals over the window), mean over the cell's chips, %."""


def read(ctx):
    """The device's idle share, or None without a device plane."""
    idle = ctx.trace["idle_share"]
    if not idle:
        return None
    return 100.0 * sum(idle) / len(idle)
