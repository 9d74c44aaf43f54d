"""Pallas TPU kernel: event-driven convolution scatter-accumulate.

TPU adaptation of the SNE cluster datapath (paper §III-D4). The ASIC streams
one event past 16 clusters and serially updates the 48-neuron receptive
field column in 48 cycles. On TPU the equivalent structure is:

  * the **membrane state tile is the cluster state memory** — it stays
    resident in VMEM for the whole event batch (the latch-based state
    memory analogue; HBM traffic happens once per phase, not per event);
  * the **grid over output-channel blocks is the cluster array** — each
    grid step owns a ``(Hp, Wp, CO_BLK)`` state slab and consumes the full
    event batch against it (all "clusters" see every event, as in the
    broadcast mode of the C-XBAR);
  * the **event batch is the dense compute phase** — sparse activity over
    a long time interval is compressed into one kernel launch, mirroring
    "long intervals of sparse input activity are compressed into dense
    computational phases".

Fast-memory budget: events are packed one int32 word each
(`window_common.pack_event_chunks`) and staged in SMEM in chunks of at
most `window_common.EVENT_CHUNK` words, one chunk per grid step along an
"arbitrary" axis, with the slab resident in VMEM across the chunks.  VMEM
holds the v-block ``Hp*Wp*CO_BLK*4`` bytes (lanes padded to 128) and the
weight block ``K*K*Ci*CO_BLK*4``, whatever the event bucket: the paper's
32768-event input rung fits as well as an 8-event one.

The per-event inner loop reads one SMEM word, unpacks ``(x, y, c)`` on
the scalar unit, and performs a dynamic-offset read-modify-write on the
VMEM slab.  This is sublane-addressed (not MXU) work — the honest mapping
of an inherently scatter-shaped algorithm; the channel axis (lane
dimension) is fully vectorised, which is the TPU analogue of SNE updating
a whole receptive-field column per event.
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


def _conv_add(acc_ref, w_ref, K: int):
    """The conv scatter rule, one event: add its flipped ``(K, K, CO_BLK)``
    weight patch at its halo coordinate (int4 codes fit int8 and promote
    exactly to the accumulator on the add)."""
    def add(x, y, c):
        patch = w_ref[:, :, c, :].astype(acc_ref.dtype)
        cur = acc_ref[0, pl.dslice(x, K), pl.dslice(y, K), :]
        acc_ref[0, pl.dslice(x, K), pl.dslice(y, K), :] = cur + patch

    return add


def _event_conv_batched_kernel(ev_ref, w_ref, v_ref, o_ref, *, K: int,
                               fields):
    """One grid step: one chunk of one slot's events against one slab.

    The slot axis only selects which event batch / membrane slab is
    resident, exactly like the C-XBAR steering one stream to one slice;
    the single-stream path is the N=1 special case of this kernel.  The
    last grid axis walks the slot's event chunks in order, with the slab
    resident in ``o_ref`` across them.

    ev_ref: (1, CHUNK) int32 SMEM — packed events (halo coords), -1 pads.
    w_ref:  (K, K, Ci, CO_BLK) — flipped weights, shared by slots
            (float32 carrier, or int8 codes on the native path).
    v_ref:  (1, Hp, Wp, CO_BLK) — this slot's membrane slab (float32
            carrier, or int8 storage on the native path).
    o_ref:  (1, Hp, Wp, CO_BLK) — output slab in the *accumulator* dtype
            (== v dtype on the carrier path; int32 on the native path,
            so per-timestep sums never saturate mid-batch).
    """
    @pl.when(pl.program_id(2) == 0)
    def _load():
        o_ref[...] = v_ref[...].astype(o_ref.dtype)

    for_each_event(ev_ref, fields, _conv_add(o_ref, w_ref, K))


@functools.partial(jax.jit, static_argnames=("co_blk", "interpret",
                                             "out_dtype"))
def event_conv_pallas(v: jnp.ndarray, weights: jnp.ndarray,
                      ev_xyc: jnp.ndarray, ev_gate: jnp.ndarray,
                      co_blk: int = 128, interpret: bool = False,
                      out_dtype=None):
    """Scatter-accumulate an event batch into the membrane state.

    Matches :func:`repro.kernels.event_conv.ref.event_conv_ref` bit-for-bit
    (float32 adds happen in the same order per channel slab). This is the
    single-stream entry point — one kernel body serves both it and the
    batched path, so the two can never drift apart.

    Args:
      v:        (Hp, Wp, Co) halo-padded membrane state.
      weights:  (K, K, Ci, Co) conv weights (unflipped; flipped here once).
      ev_xyc:   (E, 3) int32 events; coordinates already in halo coords.
      ev_gate:  (E,) 1/0 validity gate.
      co_blk:   output-channel block size (lane dimension of the slab).
      out_dtype: accumulator/result dtype (default: ``v.dtype``).  The
                int8-native policy passes int8 slabs with ``jnp.int32``
                here so the batch accumulates without saturation.
    """
    return event_conv_batched_pallas(v[None], weights, ev_xyc[None],
                                     ev_gate[None], co_blk=co_blk,
                                     interpret=interpret,
                                     out_dtype=out_dtype)[0]


@functools.partial(jax.jit, static_argnames=("co_blk", "interpret",
                                             "out_dtype"))
def event_conv_batched_pallas(v: jnp.ndarray, weights: jnp.ndarray,
                              ev_xyc: jnp.ndarray, ev_gate: jnp.ndarray,
                              co_blk: int = 128, interpret: bool = False,
                              out_dtype=None):
    """Scatter N slots' event batches into N membrane slabs in one launch.

    The grid is ``(slot, channel block, event chunk)``: grid step
    ``(n, co, k)`` applies chunk *k* of slot *n*'s packed events to slot
    *n*'s ``(Hp, Wp, CO_BLK)`` slab, which stays resident across the
    chunks.  Weights are shared across slots (one model serving many
    streams — the C-XBAR multicast of a weight set to all slices).

    Per-slab accumulation order matches the single-stream kernel exactly,
    so outputs are bit-for-bit equal to running ``event_conv_pallas`` per
    slot (and to the per-slot reference).

    Args:
      v:        (N, Hp, Wp, Co) halo-padded membrane states, one per slot.
      weights:  (K, K, Ci, Co) conv weights, shared (unflipped).
      ev_xyc:   (N, E, 3) int32 events per slot; halo coordinates.
      ev_gate:  (N, E) 1/0 validity gates (0 = padding slot).
      co_blk:   output-channel block size.
    """
    N, Hp, Wp, Co = v.shape
    K, Ci = weights.shape[0], weights.shape[2]
    if ev_xyc.shape[0] != N or ev_gate.shape[0] != N:
        raise ValueError(
            f"slot-axis mismatch: v has {N} slots, events "
            f"{ev_xyc.shape[0]}, gates {ev_gate.shape[0]}")
    out_dtype = v.dtype if out_dtype is None else jnp.dtype(out_dtype)
    if N == 0 or ev_xyc.shape[1] == 0:
        # degenerate batch (idle-skip compaction can hand us an empty slot
        # or event axis) — a scatter of nothing is the identity; skip the
        # launch instead of building a zero-sized grid
        return v.astype(out_dtype)
    co_blk = min(co_blk, Co)
    if Co % co_blk:
        raise ValueError(f"Co={Co} not divisible by co_blk={co_blk}")
    w_f = jnp.flip(jnp.flip(weights, 0), 1)
    fields = event_fields(Hp, Wp, Ci)
    words = pack_event_chunks(ev_xyc, ev_gate, fields)  # (N, nk, 1, CH)
    n_chunks, chunk = words.shape[1], words.shape[3]

    return pl.pallas_call(
        functools.partial(_event_conv_batched_kernel, K=K, fields=fields),
        grid=(N, Co // co_blk, n_chunks),
        in_specs=[
            pl.BlockSpec((None, None, 1, chunk),
                         lambda n, co, k: (n, k, 0, 0),
                         memory_space=pltpu.SMEM),      # event chunk
            pl.BlockSpec((K, K, Ci, co_blk),
                         lambda n, co, k: (0, 0, 0, co)),  # shared weights
            pl.BlockSpec((1, Hp, Wp, co_blk),
                         lambda n, co, k: (n, 0, 0, co)),  # slot v slab
        ],
        out_specs=pl.BlockSpec((1, Hp, Wp, co_blk),
                               lambda n, co, k: (n, 0, 0, co)),
        out_shape=jax.ShapeDtypeStruct(v.shape, out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(words, w_f, v)


def _event_conv_window_kernel(alive_ref, tiles_ref, ev_ref, w_ref, v_ref,
                              v_out_ref, s_out_ref, acc_ref, *, K: int,
                              fields, **window):
    """One grid step: one event chunk of one timestep of one slot's window.

    The fused form of `_event_conv_batched_kernel`.  The grid is
    ``(slot, channel block, timestep, event chunk)`` and the membrane is
    carried in the ``acc_ref`` VMEM scratch across the last two axes (the
    cluster state memory staying resident across the whole window, not
    just one dense phase), so a window costs one launch instead of T.
    `window_common.window_grid_step` runs the per-timestep chain around
    this kernel's scatter, with its tile-sparse sweeps.

    alive_ref: (N, T) int32 SMEM (scalar prefetch) — per-timestep liveness.
    tiles_ref: (N * nTx * nTy,) int32 SMEM (scalar prefetch) — interior
               tile activity bitmaps, slot-major.
    ev_ref:    (1, CHUNK) int32 SMEM — this chunk's packed events (halo
               coords), -1 pads.
    w_ref:     (K, K, Ci, CO_BLK) — flipped weights, shared by slots.
    v_ref:     (1, Hp, Wp, CO_BLK) — membrane slab in *storage* dtype
               (float32 carrier / int8 native).
    v_out_ref: (1, Hp, Wp, CO_BLK) — final membrane, storage dtype.
    s_out_ref: (1, 1, Ho, Wo, CO_BLK) — this timestep's spike frame in the
               accumulator dtype (what `frame_to_events` routes onward).
    acc_ref:   (1, Hp, Wp, CO_BLK) VMEM scratch, accumulator dtype — the
               resident membrane.
    """
    def scatter():
        for_each_event(ev_ref, fields, _conv_add(acc_ref, w_ref, K))

    window_grid_step(alive_ref, tiles_ref, v_ref, v_out_ref, s_out_ref,
                     acc_ref, scatter, **window)


@functools.partial(jax.jit, static_argnames=("lif", "halo", "co_blk",
                                             "native", "interpret", "name"))
def event_conv_window_pallas(v: jnp.ndarray, weights: jnp.ndarray,
                             ev_xyc: jnp.ndarray, ev_gate: jnp.ndarray,
                             alive: jnp.ndarray, tiles: jnp.ndarray, *,
                             lif: LifParams, halo: int, co_blk: int = 128,
                             native: bool = False, interpret: bool = False,
                             name: Optional[str] = None):
    """Advance N slots through a whole T-timestep window in ONE launch.

    The fused window form of :func:`event_conv_batched_pallas`: instead of
    one scatter launch per timestep (with leak/fire between launches in
    XLA), the timestep loop moves into the launch's grid and the membrane
    slab stays resident in VMEM scratch for the full window.  Results —
    membrane AND every timestep's spike frame — are bitwise identical to
    iterating the per-step executor (`tests/test_fused_window.py`).

    Args:
      v:       (N, Hp, Wp, Co) halo-padded membranes in storage dtype
               (float32 carrier, int8 native).
      weights: (K, K, Ci, Co) conv weights (unflipped; flipped here once).
      ev_xyc:  (N, T, E, 3) int32 packed schedule, halo coordinates.
      ev_gate: (N, T, E) 1/0 validity gates.
      alive:   (N, T) 1.0 where the slot has a real timestep (frozen
               timesteps hold state and emit no spikes).
      tiles:   (N, nTx, nTy) int32 interior tile activity bitmap
               (`window_common.tile_grid` over (Ho, Wo)); all-ones runs
               the dense schedule bit-for-bit.
      lif:     the layer's LIF plan (static — baked into the kernel).
      halo:    conv halo width (K - 1 headroom; the interior crop rule).
      co_blk:  output-channel block size (must divide Co).
      native:  int8-native policy — int32 accumulator, int8 saturation at
               every boundary, int8 storage out.
      name:    the launch's name in the compiled program and the
               profiler trace (``layer2_pool_window``); None keeps
               this function's name.

    Returns ``(v_out (N, Hp, Wp, Co) storage dtype,
    spikes (N, T, Ho, Wo, Co) accumulator dtype)``.
    """
    N, Hp, Wp, Co = v.shape
    K, Ci = weights.shape[0], weights.shape[2]
    T = ev_xyc.shape[1]
    Ho, Wo = Hp - 2 * halo, Wp - 2 * halo
    acc_dt = window_acc_dtype(v.dtype, native)
    co_blk = min(co_blk, Co)
    if Co % co_blk:
        raise ValueError(f"Co={Co} not divisible by co_blk={co_blk}")
    w_f = jnp.flip(jnp.flip(weights, 0), 1)
    fields = event_fields(Hp, Wp, Ci)
    words = pack_event_chunks(ev_xyc, ev_gate, fields)  # (N, T, nk, 1, CH)
    n_chunks, chunk = words.shape[2], words.shape[4]
    nTx, nTy, _, _ = tile_grid(Ho, Wo)
    if tiles.shape != (N, nTx, nTy):
        raise ValueError(
            f"tiles shape {tiles.shape} != {(N, nTx, nTy)} for interior "
            f"({Ho}, {Wo})")

    return pl.pallas_call(
        functools.partial(_event_conv_window_kernel, K=K, fields=fields,
                          halo=halo, n_steps=T, n_chunks=n_chunks, lif=lif,
                          native=native),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N, Co // co_blk, T, n_chunks),
            in_specs=[
                pl.BlockSpec((None, None, None, 1, chunk),
                             lambda n, co, t, k, *_: (n, t, k, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((K, K, Ci, co_blk),
                             lambda n, co, t, k, *_: (0, 0, 0, co)),
                pl.BlockSpec((1, Hp, Wp, co_blk),
                             lambda n, co, t, k, *_: (n, 0, 0, co)),
            ],
            out_specs=[
                pl.BlockSpec((1, Hp, Wp, co_blk),
                             lambda n, co, t, k, *_: (n, 0, 0, co)),
                pl.BlockSpec((1, 1, Ho, Wo, co_blk),
                             lambda n, co, t, k, *_: (n, t, 0, 0, co)),
            ],
            scratch_shapes=[pltpu.VMEM((1, Hp, Wp, co_blk), acc_dt)]),
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((N, T, Ho, Wo, Co), acc_dt),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
        name=name,
    )((alive > 0).astype(jnp.int32), tiles.astype(jnp.int32).reshape(-1),
      words, w_f, v)
