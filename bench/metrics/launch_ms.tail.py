"""Host ms per window in the engine's launch phase (``_launch_phase``: the
idle-skip selection, slot compaction and the window step's dispatch), from
the benchmark's ``bench.launch`` span in the trace."""


def read(ctx):
    """Mean launch span, or None if no span was traced."""
    return ctx.host_span_ms("bench.launch")
