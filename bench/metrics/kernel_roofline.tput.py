"""Sum over the kernels of their least time over the sum of their measured
time, %.  A kernel's least time is the larger of its operations over the
chip's int8 peak and its bytes over HBM bandwidth (``bench/work.py``); the
work is that of the windows launched in the traced window."""

from bench import stats, work


def read(ctx):
    """The kernels' share of their roofline, or None with no kernel time."""
    measured = ctx.trace["kernel_total_s"]
    if measured <= 0:
        return None
    peaks = stats.peaks(ctx.device_kind)
    _, kw = ctx.work()
    least, _ = work.least_seconds(kw, peaks["int8_ops_per_s"],
                                  peaks["hbm_bytes_per_s"])
    if least.sum() <= 0:
        return None
    return 100.0 * float(least.sum()) / measured
