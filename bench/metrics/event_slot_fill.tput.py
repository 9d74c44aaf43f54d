"""Share of the padded event slots the window steps carried that held a
real event (engine counters ``launched_events / padded_event_slots``), %."""


def read(ctx):
    """Real over padded event slots in the window, or None."""
    real = ctx.counter("launched_events")
    padded = ctx.counter("padded_event_slots")
    if not padded:
        return None
    return 100.0 * real / padded
