"""Pallas TPU kernel: event-driven sum-pool scatter-accumulate.

TPU adaptation of the SNE pool datapath.  On the ASIC a pool layer runs
the same event-consume pipeline as conv, but each event updates exactly
one neuron (the paper's ``updates_per_event == 1``); on TPU the structural
mapping mirrors `kernels/event_conv/kernel.py`:

  * the **membrane slab is the cluster state memory** — one slot's whole
    ``(Ho, Wo, C)`` pool state stays resident in VMEM for the full event
    batch (pool layers are small: C <= 32 in every shipped net, so the
    slab is a few hundred kB at most);
  * the **slot axis is a grid dimension** — grid step ``n`` owns slot
    *n*'s slab and consumes slot *n*'s event batch (C-XBAR steering);
  * the per-event update is a one-row read-modify-write: the channel axis
    (lane dimension) is updated as a full vector with a one-hot channel
    select, which keeps the store lane-aligned instead of issuing a
    single-element scatter — the TPU-honest form of "one neuron update".

Events arrive packed one int32 word each and are staged in SMEM one
chunk per grid step (`window_common.pack_event_chunks`), with the slab
resident across the chunks, so the kernel's VMEM footprint does not grow
with the event bucket.  Accumulation order per slab is the event order,
exactly the reference oracle's, so results are bit-for-bit equal to
`ref.event_pool_ref`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lif import LifParams
from repro.kernels.window_common import (event_fields, for_each_event,
                                         pack_event_chunks, tile_grid,
                                         window_acc_dtype, window_grid_step)


def _pool_add(acc_ref, w_ref, stride: int):
    """The pool scatter rule, one event: ``v[x//s, y//s, c] += w[c]``.

    The channel axis (lane dimension) is updated as a full vector with a
    one-hot channel select, which keeps the store lane-aligned instead of
    issuing a single-element scatter.  Pooled coordinates past the grid
    are dropped (the VALID-window rule: the contribution is zeroed and
    the clamped read-modify-write is a no-op).
    """
    Ho, Wo, C = acc_ref.shape[1], acc_ref.shape[2], acc_ref.shape[3]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, 1, C), 2)

    def add(x, y, c):
        xo = x // stride
        yo = y // stride
        ok = ((xo < Ho) & (yo < Wo)).astype(acc_ref.dtype)
        sel = (lanes == c).astype(acc_ref.dtype)           # one-hot channel
        contrib = (sel * w_ref[...] * ok).astype(acc_ref.dtype)
        xo = jnp.minimum(xo, Ho - 1)
        yo = jnp.minimum(yo, Wo - 1)
        cur = acc_ref[0, pl.dslice(xo, 1), pl.dslice(yo, 1), :]
        acc_ref[0, pl.dslice(xo, 1), pl.dslice(yo, 1), :] = cur + contrib

    return add


def _event_pool_batched_kernel(ev_ref, w_ref, v_ref, o_ref, *, stride: int,
                               fields):
    """One grid step: one chunk of one slot's events against its slab.

    ev_ref: (1, CHUNK) int32 SMEM — packed events, input coords; -1 pads.
    w_ref:  (1, 1, C) — per-channel weights, shared by slots (float32
            carrier, or int8 codes on the native path).
    v_ref:  (1, Ho, Wo, C) — this slot's membrane slab (float32 carrier,
            or int8 storage on the native path).
    o_ref:  (1, Ho, Wo, C) — output slab in the *accumulator* dtype
            (== v dtype on the carrier path; int32 on the native path),
            resident across the slot's event chunks.
    """
    @pl.when(pl.program_id(1) == 0)
    def _load():
        o_ref[...] = v_ref[...].astype(o_ref.dtype)

    for_each_event(ev_ref, fields, _pool_add(o_ref, w_ref, stride))


def _pool_fields(Ho: int, Wo: int, C: int, stride: int):
    """Packed-event field widths for a pool layer's input coordinates
    (any input row/column lies below ``(Ho + 1) * stride``)."""
    return event_fields((Ho + 1) * stride, (Wo + 1) * stride, C)


@functools.partial(jax.jit, static_argnames=("stride", "interpret",
                                             "out_dtype"))
def event_pool_pallas(v: jnp.ndarray, w: jnp.ndarray, ev_xyc: jnp.ndarray,
                      ev_gate: jnp.ndarray, stride: int,
                      interpret: bool = False, out_dtype=None):
    """Scatter-accumulate a pooled event batch into the membrane state.

    Matches :func:`repro.kernels.event_pool.ref.event_pool_ref` bit-for-bit
    (one add per event, in event order).  Single-stream entry point — the
    N=1 special case of the batched kernel, same body.

    Args:
      v:       (Ho, Wo, C) membrane state (no halo for pool layers).
      w:       (C,) per-channel synapse weights.
      ev_xyc:  (E, 3) int32 events in input coordinates.
      ev_gate: (E,) 1/0 validity gate.
      stride:  pooling stride.
      out_dtype: accumulator/result dtype (default ``v.dtype``; the
               int8-native policy passes ``jnp.int32``).
    """
    return event_pool_batched_pallas(v[None], w, ev_xyc[None], ev_gate[None],
                                     stride=stride, interpret=interpret,
                                     out_dtype=out_dtype)[0]


def _pool_weights(w: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """(C,) weights as the kernels' (1, 1, C) block.

    Integer weight codes ride at their own width (int8) even when the
    slab is widened (int32 "subtract"-leak case) — the launch must move
    exactly the bytes `layer_program.scatter_launch_bytes` accounts for;
    float weights keep the historical cast to the slab dtype.
    """
    w3 = w if jnp.issubdtype(w.dtype, jnp.integer) else w.astype(v.dtype)
    return w3.reshape(1, 1, -1)


@functools.partial(jax.jit, static_argnames=("stride", "interpret",
                                             "out_dtype"))
def event_pool_batched_pallas(v: jnp.ndarray, w: jnp.ndarray,
                              ev_xyc: jnp.ndarray, ev_gate: jnp.ndarray,
                              stride: int, interpret: bool = False,
                              out_dtype=None):
    """Scatter N slots' pooled event batches into N slabs in one launch.

    The grid is ``(slot, event chunk)``; each slot's slab stays resident
    across its chunks.

    Args:
      v:       (N, Ho, Wo, C) membrane states, one per slot.
      w:       (C,) per-channel weights, shared across slots.
      ev_xyc:  (N, E, 3) int32 events per slot, input coordinates.
      ev_gate: (N, E) 1/0 validity gates.
      stride:  pooling stride.
      out_dtype: accumulator/result dtype (default ``v.dtype``).
    """
    N, Ho, Wo, C = v.shape
    if ev_xyc.shape[0] != N or ev_gate.shape[0] != N:
        raise ValueError(
            f"slot-axis mismatch: v has {N} slots, events "
            f"{ev_xyc.shape[0]}, gates {ev_gate.shape[0]}")
    out_dtype = v.dtype if out_dtype is None else jnp.dtype(out_dtype)
    if N == 0 or ev_xyc.shape[1] == 0:
        # degenerate batch (idle-skip compaction) — identity, skip the launch
        return v.astype(out_dtype)
    fields = _pool_fields(Ho, Wo, C, stride)
    words = pack_event_chunks(ev_xyc, ev_gate, fields)  # (N, nk, 1, CH)
    n_chunks, chunk = words.shape[1], words.shape[3]

    return pl.pallas_call(
        functools.partial(_event_pool_batched_kernel, stride=stride,
                          fields=fields),
        grid=(N, n_chunks),
        in_specs=[
            pl.BlockSpec((None, None, 1, chunk), lambda n, k: (n, k, 0, 0),
                         memory_space=pltpu.SMEM),           # event chunk
            pl.BlockSpec((1, 1, C), lambda n, k: (0, 0, 0)),  # shared weights
            pl.BlockSpec((1, Ho, Wo, C), lambda n, k: (n, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Ho, Wo, C), lambda n, k: (n, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(v.shape, out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(words, _pool_weights(w, v), v)


def _event_pool_window_kernel(alive_ref, tiles_ref, ev_ref, w_ref, v_ref,
                              v_out_ref, s_out_ref, acc_ref, *, stride: int,
                              fields, **window):
    """One grid step: one event chunk of one timestep of one slot's window.

    The fused form of `_event_pool_batched_kernel`: the grid is ``(slot,
    1, timestep, event chunk)`` (the unit axis keeps the window grid
    layout of the conv and FC kernels) with the membrane in ``acc_ref``
    VMEM scratch across the last two axes, one launch per window instead
    of T.  Pool layers have no halo, so the whole slab is the interior;
    `window_common.window_grid_step` runs the per-timestep chain around
    this kernel's scatter.

    alive_ref: (N, T) int32 SMEM (scalar prefetch) — per-timestep liveness.
    tiles_ref: (N * nTx * nTy,) int32 SMEM (scalar prefetch) — tile
               activity bitmaps over (Ho, Wo), slot-major.
    ev_ref:    (1, CHUNK) int32 SMEM — packed events, input coords.
    w_ref:     (1, 1, C) — per-channel weights, shared by slots.
    v_ref:     (1, Ho, Wo, C) — membrane slab, storage dtype.
    v_out_ref: (1, Ho, Wo, C) — final membrane, storage dtype.
    s_out_ref: (1, 1, Ho, Wo, C) — this timestep's spike frame,
               accumulator dtype.
    acc_ref:   (1, Ho, Wo, C) VMEM scratch, accumulator dtype.
    """
    def scatter():
        for_each_event(ev_ref, fields, _pool_add(acc_ref, w_ref, stride))

    window_grid_step(alive_ref, tiles_ref, v_ref, v_out_ref, s_out_ref,
                     acc_ref, scatter, halo=0, **window)


@functools.partial(jax.jit, static_argnames=("lif", "stride", "native",
                                             "interpret", "name"))
def event_pool_window_pallas(v: jnp.ndarray, w: jnp.ndarray,
                             ev_xyc: jnp.ndarray, ev_gate: jnp.ndarray,
                             alive: jnp.ndarray, tiles: jnp.ndarray, *,
                             lif: LifParams, stride: int,
                             native: bool = False, interpret: bool = False,
                             name: Optional[str] = None):
    """Advance N slots through a whole T-timestep pool window in ONE launch.

    The fused window form of :func:`event_pool_batched_pallas`; results
    are bitwise identical to iterating the per-step executor.

    Args:
      v:       (N, Ho, Wo, C) membranes, storage dtype.
      w:       (C,) per-channel weights, shared across slots.
      ev_xyc:  (N, T, E, 3) int32 packed schedule, input coordinates.
      ev_gate: (N, T, E) 1/0 validity gates.
      alive:   (N, T) per-timestep liveness.
      tiles:   (N, nTx, nTy) int32 tile activity bitmap over (Ho, Wo);
               all-ones runs the dense schedule bit-for-bit.
      lif:     the layer's LIF plan (static).
      stride:  pooling stride.
      native:  int8-native policy switch.
      name:    the launch's name in the compiled program and the
               profiler trace (``layer2_pool_window``); None keeps
               this function's name.

    Returns ``(v_out (N, Ho, Wo, C) storage dtype,
    spikes (N, T, Ho, Wo, C) accumulator dtype)``.
    """
    N, Ho, Wo, C = v.shape
    T = ev_xyc.shape[1]
    acc_dt = window_acc_dtype(v.dtype, native)
    fields = _pool_fields(Ho, Wo, C, stride)
    words = pack_event_chunks(ev_xyc, ev_gate, fields)  # (N, T, nk, 1, CH)
    n_chunks, chunk = words.shape[2], words.shape[4]
    nTx, nTy, _, _ = tile_grid(Ho, Wo)
    if tiles.shape != (N, nTx, nTy):
        raise ValueError(
            f"tiles shape {tiles.shape} != {(N, nTx, nTy)} for interior "
            f"({Ho}, {Wo})")

    return pl.pallas_call(
        functools.partial(_event_pool_window_kernel, stride=stride,
                          fields=fields, n_steps=T, n_chunks=n_chunks,
                          lif=lif, native=native),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N, 1, T, n_chunks),
            in_specs=[
                pl.BlockSpec((None, None, None, 1, chunk),
                             lambda n, b, t, k, *_: (n, t, k, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, C), lambda n, b, t, k, *_: (0, 0, 0)),
                pl.BlockSpec((1, Ho, Wo, C),
                             lambda n, b, t, k, *_: (n, 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, Ho, Wo, C),
                             lambda n, b, t, k, *_: (n, 0, 0, 0)),
                pl.BlockSpec((1, 1, Ho, Wo, C),
                             lambda n, b, t, k, *_: (n, t, 0, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((1, Ho, Wo, C), acc_dt)]),
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((N, T, Ho, Wo, C), acc_dt),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
        name=name,
    )((alive > 0).astype(jnp.int32), tiles.astype(jnp.int32).reshape(-1),
      words, _pool_weights(w, v), v)
