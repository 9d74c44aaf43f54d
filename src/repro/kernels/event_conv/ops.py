"""jit'd public wrapper for the event-conv kernel.

Selects the Pallas TPU kernel on TPU backends and interpret mode elsewhere
(interpret mode executes the kernel body in Python on CPU — the validation
path mandated for this container).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.event_conv.kernel import (event_conv_batched_pallas,
                                             event_conv_pallas,
                                             event_conv_window_pallas)
from repro.kernels.event_conv.ref import (event_conv_batched_ref,
                                          event_conv_ref,
                                          event_conv_window_ref)
from repro.core.lif import supports_idle_skip
from repro.kernels.window_common import pad_empty_schedule, tile_grid


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def event_conv(v: jnp.ndarray, weights: jnp.ndarray, ev_xyc: jnp.ndarray,
               ev_gate: jnp.ndarray, co_blk: int = 128,
               use_pallas: bool | None = None, out_dtype=None) -> jnp.ndarray:
    """Accumulate a batch of UPDATE events into the membrane state.

    ``use_pallas=None`` auto-selects: Pallas (compiled) on TPU, Pallas
    interpret mode on CPU. ``use_pallas=False`` runs the pure-jnp oracle.
    ``out_dtype`` widens the accumulator (int8-native policy: int8 slab
    in, int32 accumulation out); default is ``v.dtype``.
    """
    if use_pallas is False:
        return event_conv_ref(v, weights, ev_xyc, ev_gate,
                              out_dtype=out_dtype)
    return event_conv_pallas(v, weights, ev_xyc, ev_gate, co_blk=co_blk,
                             interpret=not _on_tpu(), out_dtype=out_dtype)


def event_conv_batched(v: jnp.ndarray, weights: jnp.ndarray,
                       ev_xyc: jnp.ndarray, ev_gate: jnp.ndarray,
                       co_blk: int = 128, use_pallas: bool | None = None,
                       out_dtype=None) -> jnp.ndarray:
    """Accumulate N slots' event batches into N membrane slabs at once.

    The slot axis is a grid dimension of a single ``pallas_call`` (the TPU
    analogue of the C-XBAR broadcasting event streams across engine
    slices); weights are shared across slots. Same auto-selection rules as
    :func:`event_conv`.

    Empty batches (no slots, or a zero-length event axis after idle-skip
    compaction) return ``v`` unchanged (cast to ``out_dtype`` if given)
    without launching anything.
    """
    if v.shape[0] == 0 or ev_xyc.shape[1] == 0:
        return v if out_dtype is None else v.astype(out_dtype)
    if use_pallas is False:
        return event_conv_batched_ref(v, weights, ev_xyc, ev_gate,
                                      out_dtype=out_dtype)
    return event_conv_batched_pallas(v, weights, ev_xyc, ev_gate,
                                     co_blk=co_blk, interpret=not _on_tpu(),
                                     out_dtype=out_dtype)


def event_conv_window(v: jnp.ndarray, weights: jnp.ndarray,
                      ev_xyc: jnp.ndarray, ev_gate: jnp.ndarray,
                      alive: jnp.ndarray, *, lif, halo: int,
                      co_blk: int = 128, native: bool = False,
                      use_pallas: bool | None = None,
                      tiles: jnp.ndarray | None = None,
                      name: str | None = None):
    """Advance N slots through a whole T-timestep window in ONE launch.

    The fused window entry point (``fusion_policy="fused-window"``): the
    timestep loop runs inside the kernel with the membrane resident in
    VMEM scratch, so a window costs one launch per layer instead of T.
    Same auto-selection rules as :func:`event_conv`; ``use_pallas=False``
    runs the pure-jnp window oracle.  Returns ``(v_out, spikes)`` with
    spikes shaped ``(N, T, Ho, Wo, Co)``.

    ``tiles`` is an optional (N, nTx, nTy) interior activity bitmap
    (`window_common.tile_grid` geometry): cold tiles skip the per-timestep
    leak/clip/fire sweeps and settle with one analytic decay.  Only
    hard-reset layers (`supports_idle_skip`) may pass one — the deferred
    decay has no closed form under soft reset.  ``None`` runs dense.
    ``name`` names the Pallas launch (see `event_conv_window_pallas`).

    A zero-length event axis still runs the window (leak/fire must
    advance, unlike the scatter-only kernels) — the schedule is padded to
    one gated-off event so the launch geometry stays valid.
    """
    ev_xyc, ev_gate = pad_empty_schedule(ev_xyc, ev_gate)
    if tiles is not None and not supports_idle_skip(lif):
        raise ValueError(
            "tile sparsity requires a hard-reset layer (reset_mode='zero'):"
            " cold-tile decay has no closed form under soft reset")
    nTx, nTy, _, _ = tile_grid(v.shape[1] - 2 * halo, v.shape[2] - 2 * halo)
    if use_pallas is False or nTx * nTy == 0:
        # an empty interior (kernel wider than the padded input) has no
        # neuron to tile or fire: its halo-only scatter runs on the oracle
        return event_conv_window_ref(v, weights, ev_xyc, ev_gate, alive,
                                     lif=lif, halo=halo, native=native,
                                     tiles=tiles)
    if tiles is None:
        tiles = jnp.ones((v.shape[0], nTx, nTy), jnp.int32)
    return event_conv_window_pallas(v, weights, ev_xyc, ev_gate, alive,
                                    tiles, lif=lif, halo=halo,
                                    co_blk=co_blk, native=native,
                                    interpret=not _on_tpu(), name=name)
