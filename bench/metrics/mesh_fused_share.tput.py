"""Share of the mesh engine's windows that ran as one fused step over all
chips (``mesh_global_windows`` over global plus per-shard windows), %."""


def read(ctx):
    """Fused mesh windows over all mesh windows, or None off the mesh."""
    fused = ctx.counter("mesh_global_windows")
    shard = ctx.counter("mesh_shard_windows")
    if fused is None or shard is None or fused + shard == 0:
        return None
    return 100.0 * fused / (fused + shard)
