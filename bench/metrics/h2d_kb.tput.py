"""Host-to-device KiB per window step: the engine's ``h2d_bytes`` (every
host array the serving path puts on a device, counted at the put) over
its ``step_calls``, between the window's counter snapshots."""


def read(ctx):
    """KiB handed to the device per window step, or None where the
    program counts no ``h2d_bytes``."""
    moved = ctx.counter("h2d_bytes")
    steps = ctx.counter("step_calls")
    if moved is None or not steps:
        return None
    return moved / steps / 1024.0
