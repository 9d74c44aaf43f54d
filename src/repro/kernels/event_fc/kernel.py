"""Pallas TPU kernel: event-driven fully-connected row-gather accumulate.

TPU adaptation of the SNE FC datapath (the eCNN head layers run on the
same event-consume pipeline as conv; an FC "receptive field" is the whole
output vector).  Structural mapping, mirroring the conv/pool kernels:

  * the **output membrane vector is the cluster state memory** — one
    slot's ``(Dout,)`` state plus the weight block stay resident in VMEM
    for the whole event batch.  For the largest shipped layer (Din = 2048,
    Dout = 512) the weight block is 2048*512*4 = 4 MB — well inside VMEM;
  * the **grid is (slot, Dout-block)** — each grid step owns one slot's
    ``DBLK``-wide output stripe and consumes the full event batch against
    it (every "cluster" sees every event, C-XBAR broadcast);
  * the per-event update is a **gated row gather**: the event's flattened
    input coordinate selects one weight row (sublane-dynamic index), and
    the whole lane-dimension row accumulates in one VPU add — the TPU
    analogue of SNE updating a full receptive-field column per event.

Events arrive packed one int32 word each and are staged in SMEM one
chunk per grid step (`window_common.pack_event_chunks`), with the stripe
resident across the chunks, so the kernel's VMEM footprint does not grow
with the event bucket.  Accumulation order per stripe is the event order,
exactly the reference oracle's, so results are bit-for-bit equal to
`ref.event_fc_ref`.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lif import LifParams
from repro.kernels.window_common import (event_fields, for_each_event,
                                         pack_event_chunks, window_acc_dtype,
                                         window_grid_step)


def _fc_add(acc_ref, w_ref, W: int, C: int):
    """The FC scatter rule, one event: a gated weight-row gather.

    The event's flattened input coordinate selects one weight row
    (sublane-dynamic index) and the whole lane-dimension row accumulates
    in one VPU add.
    """
    def add(x, y, c):
        row = w_ref[(x * W + y) * C + c, :].astype(acc_ref.dtype)  # (DBLK,)
        acc_ref[0, 0, 0, :] = acc_ref[0, 0, 0, :] + row

    return add


def _event_fc_batched_kernel(ev_ref, w_ref, v_ref, o_ref, *, fields, W: int,
                             C: int):
    """One grid step: one chunk of one slot's events against one stripe.

    ev_ref: (1, CHUNK) int32 SMEM — packed events, input coords; -1 pads.
    w_ref:  (Din, DBLK) — weight stripe, shared by slots (float32
            carrier, or int8 codes on the native path).
    v_ref:  (1, 1, 1, DBLK) — this slot's membrane stripe (float32
            carrier, or int8 storage on the native path).
    o_ref:  (1, 1, 1, DBLK) — output stripe in the *accumulator* dtype
            (== v dtype on the carrier path; int32 on the native path),
            resident across the slot's event chunks.
    """
    @pl.when(pl.program_id(2) == 0)
    def _load():
        o_ref[...] = v_ref[...].astype(o_ref.dtype)

    for_each_event(ev_ref, fields, _fc_add(o_ref, w_ref, W, C))


@functools.partial(jax.jit, static_argnames=("in_shape", "d_blk",
                                             "interpret", "out_dtype"))
def event_fc_pallas(v: jnp.ndarray, w: jnp.ndarray, ev_xyc: jnp.ndarray,
                    ev_gate: jnp.ndarray, in_shape: Tuple[int, int, int],
                    d_blk: int = 128, interpret: bool = False,
                    out_dtype=None):
    """Accumulate an FC event batch into the output membrane state.

    Matches :func:`repro.kernels.event_fc.ref.event_fc_ref` bit-for-bit
    (one gated row add per event, in event order).  Single-stream entry
    point — the N=1 special case of the batched kernel, same body.

    Args:
      v:        (1, 1, Dout) membrane state.
      w:        (Din, Dout) weight matrix.
      ev_xyc:   (E, 3) int32 events in input coordinates.
      ev_gate:  (E,) 1/0 validity gate.
      in_shape: (H, W, C) static input geometry (flattening rule).
      d_blk:    output-block size (lane dimension of the stripe).
      out_dtype: accumulator/result dtype (default ``v.dtype``; the
                int8-native policy passes ``jnp.int32``).
    """
    return event_fc_batched_pallas(v[None], w, ev_xyc[None], ev_gate[None],
                                   in_shape=in_shape, d_blk=d_blk,
                                   interpret=interpret,
                                   out_dtype=out_dtype)[0]


def _check_fc(w: jnp.ndarray, in_shape, Dout: int, d_blk: int) -> int:
    """Validate the FC geometry; returns the clamped output block."""
    H, W, C = in_shape
    if H * W * C != w.shape[0]:
        raise ValueError(f"in_shape {in_shape} flattens to {H * W * C} "
                         f"!= weight rows {w.shape[0]}")
    d_blk = min(d_blk, Dout)
    if Dout % d_blk:
        raise ValueError(f"Dout={Dout} not divisible by d_blk={d_blk}")
    return d_blk


@functools.partial(jax.jit, static_argnames=("in_shape", "d_blk",
                                             "interpret", "out_dtype"))
def event_fc_batched_pallas(v: jnp.ndarray, w: jnp.ndarray,
                            ev_xyc: jnp.ndarray, ev_gate: jnp.ndarray,
                            in_shape: Tuple[int, int, int],
                            d_blk: int = 128, interpret: bool = False,
                            out_dtype=None):
    """Accumulate N slots' FC event batches into N stripes in one launch.

    The grid is ``(slot, output block, event chunk)``; each stripe stays
    resident across its slot's chunks.

    Args:
      v:        (N, 1, 1, Dout) membrane states, one per slot.
      w:        (Din, Dout) weight matrix, shared across slots.
      ev_xyc:   (N, E, 3) int32 events per slot, input coordinates.
      ev_gate:  (N, E) 1/0 validity gates.
      in_shape: (H, W, C) static input geometry.
      d_blk:    output-block size.
      out_dtype: accumulator/result dtype (default ``v.dtype``).
    """
    N, Dout = v.shape[0], v.shape[-1]
    Din = w.shape[0]
    H, W, C = in_shape
    if ev_xyc.shape[0] != N or ev_gate.shape[0] != N:
        raise ValueError(
            f"slot-axis mismatch: v has {N} slots, events "
            f"{ev_xyc.shape[0]}, gates {ev_gate.shape[0]}")
    d_blk = _check_fc(w, in_shape, Dout, d_blk)
    out_dtype = v.dtype if out_dtype is None else jnp.dtype(out_dtype)
    if N == 0 or ev_xyc.shape[1] == 0:
        # degenerate batch (idle-skip compaction) — identity, skip the launch
        return v.astype(out_dtype)
    fields = event_fields(H, W, C)
    words = pack_event_chunks(ev_xyc, ev_gate, fields)  # (N, nk, 1, CH)
    n_chunks, chunk = words.shape[1], words.shape[3]

    return pl.pallas_call(
        functools.partial(_event_fc_batched_kernel, fields=fields, W=W, C=C),
        grid=(N, Dout // d_blk, n_chunks),
        in_specs=[
            pl.BlockSpec((None, None, 1, chunk),
                         lambda n, d, k: (n, k, 0, 0),
                         memory_space=pltpu.SMEM),         # event chunk
            pl.BlockSpec((Din, d_blk), lambda n, d, k: (0, d)),  # w stripe
            pl.BlockSpec((1, 1, 1, d_blk), lambda n, d, k: (n, 0, 0, d)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d_blk),
                               lambda n, d, k: (n, 0, 0, d)),
        out_shape=jax.ShapeDtypeStruct(v.shape, out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(words, w, v)


def _event_fc_window_kernel(alive_ref, ev_ref, w_ref, v_ref, v_out_ref,
                            s_out_ref, acc_ref, *, fields, W: int, C: int,
                            **window):
    """One grid step: one event chunk of one timestep of one slot's window.

    The fused form of `_event_fc_batched_kernel`: the grid is ``(slot,
    output block, timestep, event chunk)`` with the membrane stripe in
    ``acc_ref`` VMEM scratch across the last two axes, one launch per
    window instead of T.  `window_common.window_grid_step` runs the
    per-timestep chain around this kernel's scatter; an FC stripe is one
    always-hot site (no halo, no tile bitmap).

    alive_ref: (N, T) int32 SMEM (scalar prefetch) — per-timestep liveness.
    ev_ref:    (1, CHUNK) int32 SMEM — packed events, input coords.
    w_ref:     (Din, DBLK) — weight stripe, shared by slots.
    v_ref:     (1, 1, 1, DBLK) — membrane stripe, storage dtype.
    v_out_ref: (1, 1, 1, DBLK) — final membrane, storage dtype.
    s_out_ref: (1, 1, 1, 1, DBLK) — this timestep's spike frame,
               accumulator dtype.
    acc_ref:   (1, 1, 1, DBLK) VMEM scratch, accumulator dtype.
    """
    def scatter():
        for_each_event(ev_ref, fields, _fc_add(acc_ref, w_ref, W, C))

    window_grid_step(alive_ref, None, v_ref, v_out_ref, s_out_ref, acc_ref,
                     scatter, halo=0, **window)


@functools.partial(jax.jit, static_argnames=("lif", "in_shape", "d_blk",
                                             "native", "interpret", "name"))
def event_fc_window_pallas(v: jnp.ndarray, w: jnp.ndarray,
                           ev_xyc: jnp.ndarray, ev_gate: jnp.ndarray,
                           alive: jnp.ndarray, *, lif: LifParams,
                           in_shape: Tuple[int, int, int], d_blk: int = 128,
                           native: bool = False, interpret: bool = False,
                           name: Optional[str] = None):
    """Advance N slots through a whole T-timestep FC window in ONE launch.

    The fused window form of :func:`event_fc_batched_pallas`; results are
    bitwise identical to iterating the per-step executor.

    Args:
      v:        (N, 1, 1, Dout) membrane stripes, storage dtype.
      w:        (Din, Dout) shared weight matrix.
      ev_xyc:   (N, T, E, 3) int32 packed schedule, input coordinates.
      ev_gate:  (N, T, E) 1/0 validity gates.
      alive:    (N, T) per-timestep liveness.
      lif:      the layer's LIF plan (static).
      in_shape: (H, W, C) static input geometry (flattening rule).
      d_blk:    output-block size (must divide Dout).
      native:   int8-native policy switch.
      name:     the launch's name in the compiled program and the
                profiler trace (``layer2_pool_window``); None keeps
                this function's name.

    Returns ``(v_out (N, 1, 1, Dout) storage dtype,
    spikes (N, T, 1, 1, Dout) accumulator dtype)``.
    """
    N, Dout = v.shape[0], v.shape[-1]
    Din = w.shape[0]
    H, W, C = in_shape
    d_blk = _check_fc(w, in_shape, Dout, d_blk)
    T = ev_xyc.shape[1]
    acc_dt = window_acc_dtype(v.dtype, native)
    fields = event_fields(H, W, C)
    words = pack_event_chunks(ev_xyc, ev_gate, fields)  # (N, T, nk, 1, CH)
    n_chunks, chunk = words.shape[2], words.shape[4]

    return pl.pallas_call(
        functools.partial(_event_fc_window_kernel, fields=fields, W=W, C=C,
                          n_steps=T, n_chunks=n_chunks, lif=lif,
                          native=native),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N, Dout // d_blk, T, n_chunks),
            in_specs=[
                pl.BlockSpec((None, None, None, 1, chunk),
                             lambda n, d, t, k, *_: (n, t, k, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((Din, d_blk), lambda n, d, t, k, *_: (0, d)),
                pl.BlockSpec((1, 1, 1, d_blk),
                             lambda n, d, t, k, *_: (n, 0, 0, d)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, d_blk),
                             lambda n, d, t, k, *_: (n, 0, 0, d)),
                pl.BlockSpec((1, 1, 1, 1, d_blk),
                             lambda n, d, t, k, *_: (n, t, 0, 0, d)),
            ],
            scratch_shapes=[pltpu.VMEM((1, 1, 1, d_blk), acc_dt)]),
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((N, T, 1, 1, Dout), acc_dt),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
        name=name,
    )((alive > 0).astype(jnp.int32), words, w, v)
