"""Host ms per window in the engine's collector (``_collect_phase``), from
the benchmark's ``bench.collect`` span in the trace."""


def read(ctx):
    """Mean collector span, or None if no span was traced."""
    return ctx.host_span_ms("bench.collect")
