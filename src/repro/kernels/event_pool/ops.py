"""jit'd public wrapper for the event-pool kernel.

Selects the Pallas TPU kernel on TPU backends and interpret mode elsewhere
(interpret mode executes the kernel body in Python on CPU — the validation
path mandated for this container), mirroring `kernels/event_conv/ops.py`.

``use_pallas=False`` is the *validation oracle*, not a production path: it
replays the kernel's per-event accumulation order sequentially so served
results are bitwise identical across both modes (pinned by
`tests/test_layer_program.py`); prefer the default on anything large.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.event_pool.kernel import (event_pool_batched_pallas,
                                             event_pool_pallas,
                                             event_pool_window_pallas)
from repro.kernels.event_pool.ref import (event_pool_batched_ref,
                                          event_pool_ref,
                                          event_pool_window_ref)
from repro.core.lif import supports_idle_skip
from repro.kernels.window_common import pad_empty_schedule, tile_grid


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def event_pool(v: jnp.ndarray, w: jnp.ndarray, ev_xyc: jnp.ndarray,
               ev_gate: jnp.ndarray, stride: int,
               use_pallas: bool | None = None, out_dtype=None) -> jnp.ndarray:
    """Accumulate a batch of pooled UPDATE events into the membrane state.

    ``use_pallas=None`` auto-selects: Pallas (compiled) on TPU, Pallas
    interpret mode on CPU. ``use_pallas=False`` runs the pure-jnp oracle.
    ``out_dtype`` widens the accumulator (int8-native policy: int8 slab
    in, int32 accumulation out); default is ``v.dtype``.
    """
    if use_pallas is False:
        return event_pool_ref(v, w, ev_xyc, ev_gate, stride,
                              out_dtype=out_dtype)
    return event_pool_pallas(v, w, ev_xyc, ev_gate, stride=stride,
                             interpret=not _on_tpu(), out_dtype=out_dtype)


def event_pool_batched(v: jnp.ndarray, w: jnp.ndarray, ev_xyc: jnp.ndarray,
                       ev_gate: jnp.ndarray, stride: int,
                       use_pallas: bool | None = None,
                       out_dtype=None) -> jnp.ndarray:
    """Accumulate N slots' pooled event batches into N slabs at once.

    Same auto-selection rules as :func:`event_pool`.  Empty batches (no
    slots, or a zero-length event axis after idle-skip compaction) return
    ``v`` unchanged (cast to ``out_dtype`` if given) without launching
    anything.
    """
    if v.shape[0] == 0 or ev_xyc.shape[1] == 0:
        return v if out_dtype is None else v.astype(out_dtype)
    if use_pallas is False:
        return event_pool_batched_ref(v, w, ev_xyc, ev_gate, stride,
                                      out_dtype=out_dtype)
    return event_pool_batched_pallas(v, w, ev_xyc, ev_gate, stride=stride,
                                     interpret=not _on_tpu(),
                                     out_dtype=out_dtype)


def event_pool_window(v: jnp.ndarray, w: jnp.ndarray, ev_xyc: jnp.ndarray,
                      ev_gate: jnp.ndarray, alive: jnp.ndarray, *, lif,
                      stride: int, native: bool = False,
                      use_pallas: bool | None = None,
                      tiles: jnp.ndarray | None = None,
                      name: str | None = None):
    """Advance N slots through a whole T-timestep pool window in ONE launch.

    The fused window entry point (``fusion_policy="fused-window"``) —
    timestep loop inside the kernel, membrane resident in VMEM scratch.
    Same auto-selection rules as :func:`event_pool`; ``use_pallas=False``
    runs the pure-jnp window oracle.  Returns ``(v_out, spikes)`` with
    spikes shaped ``(N, T, Ho, Wo, C)``.

    ``tiles`` is an optional (N, nTx, nTy) activity bitmap over (Ho, Wo)
    (`window_common.tile_grid` geometry): cold tiles skip the per-timestep
    sweeps and settle with one analytic decay.  Hard-reset layers only;
    ``None`` runs dense.  ``name`` names the Pallas launch (see
    `event_pool_window_pallas`).

    A zero-length event axis still runs the window (leak/fire must
    advance) — the schedule is padded to one gated-off event.
    """
    ev_xyc, ev_gate = pad_empty_schedule(ev_xyc, ev_gate)
    if tiles is not None and not supports_idle_skip(lif):
        raise ValueError(
            "tile sparsity requires a hard-reset layer (reset_mode='zero'):"
            " cold-tile decay has no closed form under soft reset")
    nTx, nTy, _, _ = tile_grid(v.shape[1], v.shape[2])
    if use_pallas is False or nTx * nTy == 0:
        # an empty pooled grid has no neuron to tile or fire: the oracle
        # returns its (empty) window without a zero-sized launch
        return event_pool_window_ref(v, w, ev_xyc, ev_gate, alive, lif=lif,
                                     stride=stride, native=native,
                                     tiles=tiles)
    if tiles is None:
        tiles = jnp.ones((v.shape[0], nTx, nTy), jnp.int32)
    return event_pool_window_pallas(v, w, ev_xyc, ev_gate, alive, tiles,
                                    lif=lif, stride=stride, native=native,
                                    interpret=not _on_tpu(), name=name)
