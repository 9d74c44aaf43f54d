"""jit'd public wrapper for the event-FC kernel.

Selects the Pallas TPU kernel on TPU backends and interpret mode elsewhere
(interpret mode executes the kernel body in Python on CPU — the validation
path mandated for this container), mirroring `kernels/event_conv/ops.py`.

``use_pallas=False`` is the *validation oracle*, not a production path: it
replays the kernel's per-event accumulation order sequentially so served
results are bitwise identical across both modes (pinned by
`tests/test_layer_program.py`); prefer the default on anything large.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.event_fc.kernel import (event_fc_batched_pallas,
                                           event_fc_pallas,
                                           event_fc_window_pallas)
from repro.kernels.event_fc.ref import (event_fc_batched_ref, event_fc_ref,
                                        event_fc_window_ref)
from repro.kernels.window_common import pad_empty_schedule


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def event_fc(v: jnp.ndarray, w: jnp.ndarray, ev_xyc: jnp.ndarray,
             ev_gate: jnp.ndarray, in_shape: Tuple[int, int, int],
             d_blk: int = 128, use_pallas: bool | None = None,
             out_dtype=None) -> jnp.ndarray:
    """Accumulate a batch of FC UPDATE events into the membrane state.

    ``use_pallas=None`` auto-selects: Pallas (compiled) on TPU, Pallas
    interpret mode on CPU. ``use_pallas=False`` runs the pure-jnp oracle.
    ``out_dtype`` widens the accumulator (int8-native policy: int8 stripe
    in, int32 accumulation out); default is ``v.dtype``.
    """
    if use_pallas is False:
        return event_fc_ref(v, w, ev_xyc, ev_gate, in_shape,
                            out_dtype=out_dtype)
    return event_fc_pallas(v, w, ev_xyc, ev_gate, in_shape=in_shape,
                           d_blk=d_blk, interpret=not _on_tpu(),
                           out_dtype=out_dtype)


def event_fc_batched(v: jnp.ndarray, w: jnp.ndarray, ev_xyc: jnp.ndarray,
                     ev_gate: jnp.ndarray, in_shape: Tuple[int, int, int],
                     d_blk: int = 128, use_pallas: bool | None = None,
                     out_dtype=None) -> jnp.ndarray:
    """Accumulate N slots' FC event batches into N stripes at once.

    Same auto-selection rules as :func:`event_fc`.  Empty batches (no
    slots, or a zero-length event axis after idle-skip compaction) return
    ``v`` unchanged (cast to ``out_dtype`` if given) without launching
    anything.
    """
    if v.shape[0] == 0 or ev_xyc.shape[1] == 0:
        return v if out_dtype is None else v.astype(out_dtype)
    if use_pallas is False:
        return event_fc_batched_ref(v, w, ev_xyc, ev_gate, in_shape,
                                    out_dtype=out_dtype)
    return event_fc_batched_pallas(v, w, ev_xyc, ev_gate, in_shape=in_shape,
                                   d_blk=d_blk, interpret=not _on_tpu(),
                                   out_dtype=out_dtype)


def event_fc_window(v: jnp.ndarray, w: jnp.ndarray, ev_xyc: jnp.ndarray,
                    ev_gate: jnp.ndarray, alive: jnp.ndarray, *, lif,
                    in_shape: Tuple[int, int, int], d_blk: int = 128,
                    native: bool = False, use_pallas: bool | None = None,
                    name: str | None = None):
    """Advance N slots through a whole T-timestep FC window in ONE launch.

    The fused window entry point (``fusion_policy="fused-window"``) —
    timestep loop inside the kernel, membrane stripe resident in VMEM
    scratch.  Same auto-selection rules as :func:`event_fc`;
    ``use_pallas=False`` runs the pure-jnp window oracle.  Returns
    ``(v_out, spikes)`` with spikes shaped ``(N, T, 1, 1, Dout)``.

    A zero-length event axis still runs the window (leak/fire must
    advance) — the schedule is padded to one gated-off event.  ``name``
    names the Pallas launch (see `event_fc_window_pallas`).
    """
    ev_xyc, ev_gate = pad_empty_schedule(ev_xyc, ev_gate)
    if use_pallas is False:
        return event_fc_window_ref(v, w, ev_xyc, ev_gate, alive, lif=lif,
                                   in_shape=in_shape, native=native)
    return event_fc_window_pallas(v, w, ev_xyc, ev_gate, alive, lif=lif,
                                  in_shape=in_shape, d_blk=d_blk,
                                  native=native, interpret=not _on_tpu(),
                                  name=name)
