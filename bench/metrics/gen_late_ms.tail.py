"""p95 of how late the benchmark's arrival generator handed requests due
in the measured window to the runtime, against their schedule, in ms."""


def read(ctx):
    """The generator's p95 lateness, or None with no arrival due."""
    late = [ctx.late_s[o["uid"]] for o in ctx.due()
            if o["uid"] < len(ctx.late_s)]
    return ctx.quantile_ms(late, 95.0)
