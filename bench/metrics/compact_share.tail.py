"""Share of the window steps whose slot batch was compacted, i.e. not the
identity over every slot (engine counters ``compacted_windows /
step_calls``), %."""


def read(ctx):
    """Compacted over all window steps in the window, or None where the
    program counts no ``compacted_windows``."""
    compacted = ctx.counter("compacted_windows")
    steps = ctx.counter("step_calls")
    if compacted is None or not steps:
        return None
    return 100.0 * compacted / steps
