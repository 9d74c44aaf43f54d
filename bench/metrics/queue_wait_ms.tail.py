"""p95 of the admission-queue wait (``StreamRequest.queue_wait_s``) of the
requests due in the measured window, in ms."""


def read(ctx):
    """The admission queue's p95 wait, or None if nothing was admitted."""
    waits = [o["admit_s"] - o["arrival_s"] for o in ctx.due()
             if o["admit_s"] is not None]
    return ctx.quantile_ms(waits, 95.0)
