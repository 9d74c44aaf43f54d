"""Synaptic operations per second over the chips' int8 peak, %.  SOPs are
counted from the plain reference's per-layer spikes times each layer's
fan-out, over the windows launched in the traced window; one SOP is one
operation."""

from bench import stats


def read(ctx):
    """The whole step's share of the chips' peak, or None."""
    sops, _ = ctx.work()
    if sops <= 0:
        return None
    peak = stats.peaks(ctx.device_kind)["int8_ops_per_s"] * ctx.chips
    return 100.0 * sops / ctx.trace["window_s"] / peak
