"""Host ms per admitted request in the engine's ``try_admit`` (the host
sort and stack of the request's events), from the benchmark's
``bench.admit`` span in the trace."""


def read(ctx):
    """Mean admission span, or None if no admission was traced."""
    return ctx.host_span_ms("bench.admit")
