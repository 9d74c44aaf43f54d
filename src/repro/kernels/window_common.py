"""Shared LIF boundary sequence for the fused multi-timestep window kernels.

The fused ``*_window`` kernels (`kernels/event_conv`, `kernels/event_pool`,
`kernels/event_fc`) run the whole ``leak -> scatter -> clip -> fire ->
reset`` chain for every timestep of a serving window inside ONE Pallas
launch, with the membrane carried in VMEM scratch between iterations.  The
per-timestep boundary arithmetic must stay *bitwise identical* to the
per-step executor (`core.layer_program.layer_timestep`), which is the
fused path's exactness oracle — so the boundary ops are not re-derived
here: :func:`leak_boundary` and :func:`clip_fire_reset` call straight into
`core.lif` (`apply_leak`, `fire_and_reset`), the single source both
executors share.  :func:`window_grid_step` is the one per-grid-step body
all three window kernels run around their own scatter.

Every scatter kernel, per-step and window, also takes its events through
here: :func:`pack_event_chunks` packs them one int32 word each into
SMEM-sized chunks and :func:`for_each_event` is the in-kernel loop that
unpacks them.

This module is a *leaf* on the kernel side of the layering: it may import
`core.lif` / `core.quant` (which import no kernels), and every kernel
package's ``kernel.py`` / ``ref.py`` may import it, but it must never
import `core.layer_program` (which imports the kernel packages — the one
cycle the layering forbids).  The two halo-crop helpers are therefore
restated here rather than imported from the executor.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lif import (LifParams, apply_leak, fire_and_reset,
                            idle_decay, supports_idle_skip)
from repro.core.quant import INT8_MAX, INT8_MIN

__all__ = ["EVENT_CHUNK", "INT8_MAX", "INT8_MIN", "clip_fire_reset",
           "cold_tile_decay", "crop_interior", "dilate_conv", "dilate_pool",
           "event_fields", "for_each_event", "fused_window_ref",
           "leak_boundary", "pack_event_chunks", "pad_empty_schedule",
           "route_frame", "saturate_int8", "seed_site_map", "sites_to_tiles",
           "tile_grid", "tile_spans", "tiles_to_sites", "window_acc_dtype",
           "window_grid_step", "write_cropped"]

# Tiles per spatial axis of one membrane interior.  4x4 matches the
# window kernels' launch geometry (whole-interior blocks): a tile is the
# smallest slab region the in-kernel `@pl.when` can predicate without
# breaking the lane (channel) axis, and 16 tiles keeps the per-timestep
# predicate overhead negligible against the elementwise sweep it skips.
TILE_GRID_MAX = 4

# Most packed events one grid step stages in SMEM (4 KiB of int32 words).
# The scatter kernels chunk the event axis over an "arbitrary" grid axis
# with the membrane resident across chunks, so no VMEM block grows with
# the event bucket: the paper network's 32768-event input rung costs the
# same fast memory as an 8-event one.
EVENT_CHUNK = 1024


def _field_bits(n: int) -> int:
    """Bits that hold every value in ``[0, n)`` (at least one)."""
    return max(1, (int(n) - 1).bit_length())


def event_fields(x_end: int, y_end: int, c_end: int) -> Tuple[int, int]:
    """Field widths ``(y_bits, c_bits)`` of one kernel's packed events.

    An event ``(x, y, c)`` with ``x < x_end``, ``y < y_end``, ``c <
    c_end`` packs into one non-negative int32 word
    ``(x << (y_bits + c_bits)) | (y << c_bits) | c`` — the paper's
    one-word event format (`core.events.pack_events`) cut down to the
    three fields a scatter reads.  The widths are static per kernel
    geometry; a geometry whose fields need more than 31 bits is refused.
    """
    xb, yb, cb = _field_bits(x_end), _field_bits(y_end), _field_bits(c_end)
    if xb + yb + cb > 31:
        raise ValueError(
            f"event fields ({x_end}, {y_end}, {c_end}) need "
            f"{xb + yb + cb} bits; a packed event holds 31")
    return yb, cb


def pack_event_chunks(ev_xyc: jnp.ndarray, ev_gate: jnp.ndarray,
                      fields: Tuple[int, int]) -> jnp.ndarray:
    """Pack an event schedule into SMEM-sized chunks of int32 words.

    ``(..., E, 3)`` events with ``(..., E)`` gates become
    ``(..., n_chunks, 1, chunk)`` words: gated-on events in their order,
    gated-off (padding) events as ``-1``, which the kernels skip.  The
    chunk is at most :data:`EVENT_CHUNK` words and the event axis is
    padded with ``-1`` to ``n_chunks * chunk`` (at most one word per
    chunk of padding; an empty axis becomes one padding word).
    """
    yb, cb = fields
    x, y, c = ev_xyc[..., 0], ev_xyc[..., 1], ev_xyc[..., 2]
    word = (x << (yb + cb)) | (y << cb) | c
    word = jnp.where(ev_gate > 0, word, -1).astype(jnp.int32)
    E = word.shape[-1]
    n_chunks = max(1, -(-E // EVENT_CHUNK))
    chunk = max(1, -(-E // n_chunks))
    pad = n_chunks * chunk - E
    if pad:
        word = jnp.pad(word, [(0, 0)] * (word.ndim - 1) + [(0, pad)],
                       constant_values=-1)
    return word.reshape(word.shape[:-1] + (n_chunks, 1, chunk))


def for_each_event(ev_ref, fields: Tuple[int, int], update) -> None:
    """Run ``update(x, y, c)`` for each real event of the staged chunk.

    ``ev_ref`` is the ``(1, chunk)`` SMEM block of packed words
    (:func:`pack_event_chunks`); events are applied in order, padding
    words (``-1``) are skipped.  Shared by every scatter kernel so the
    unpacking rule has one home.
    """
    yb, cb = fields

    def body(i, carry):
        word = ev_ref[0, i]

        @pl.when(word >= 0)
        def _apply():
            update(word >> (yb + cb), (word >> cb) & ((1 << yb) - 1),
                   word & ((1 << cb) - 1))
        return carry

    jax.lax.fori_loop(0, ev_ref.shape[-1], body, 0)


def pad_empty_schedule(ev_xyc: jnp.ndarray, ev_gate: jnp.ndarray):
    """Pad a zero-length event axis to one gated-off event.

    A fused window must still run its leak/fire boundaries even with no
    events (unlike the scatter-only kernels, where an empty batch is the
    identity), so the ``(N, T, 0, 3)`` schedule is widened to one padding
    event per timestep with ``gate = 0`` to keep the launch geometry
    valid.  Shared by every ``*_window`` ops wrapper.
    """
    if ev_xyc.shape[2] == 0:
        ev_xyc = jnp.pad(ev_xyc, [(0, 0), (0, 0), (0, 1), (0, 0)])
        ev_gate = jnp.pad(ev_gate, [(0, 0), (0, 0), (0, 1)])
    return ev_xyc, ev_gate


def window_acc_dtype(storage_dtype, native: bool):
    """Accumulator dtype a fused window computes in.

    The native integer path widens its int8 storage slab to int32 for the
    whole in-kernel window (the resident-phase analogue of the per-step
    executor's per-timestep widening); the carrier path accumulates in the
    storage dtype itself.
    """
    return jnp.int32 if native else jnp.dtype(storage_dtype)


def leak_boundary(v: jnp.ndarray, lif: LifParams) -> jnp.ndarray:
    """One timestep boundary's leak on the interior values (dt == 1).

    Delegates to `core.lif.apply_leak` so the arithmetic is the per-step
    executor's, bit for bit.
    """
    return apply_leak(v, lif.leak, 1, lif.leak_mode)


def clip_fire_reset(v: jnp.ndarray, lif: LifParams):
    """Finish a timestep on the interior: clip, threshold, emit, reset.

    Returns ``(v_next, spikes)`` in ``v.dtype``.  The clip is the 8-bit
    state saturation (`layer_program.clip_state` semantics: a no-op when
    the layer has no ``state_clip``); fire/reset delegate to
    `core.lif.fire_and_reset`.
    """
    if lif.state_clip is not None:
        c = jnp.asarray(lif.state_clip, v.dtype)
        v = jnp.clip(v, -c, c)
    return fire_and_reset(v, lif)


def saturate_int8(v: jnp.ndarray) -> jnp.ndarray:
    """Apply int8 storage saturation in the accumulator dtype.

    The per-step native executor downcasts the whole slab (halo included)
    to int8 at every timestep boundary; inside a fused window the state
    stays in the int32 accumulator, so the saturation is expressed as a
    clip to the int8 rails — the values are exactly the downcast-upcast
    round trip's.
    """
    return jnp.clip(v, INT8_MIN, INT8_MAX)


def route_frame(s: jnp.ndarray, cap: int):
    """One dense spike frame -> a padded event list (in-kernel routing).

    The single-frame port of `core.layer_program.frame_to_events`, used by
    the fused-network megakernel (`kernels/network_window`) to route one
    timestep's FIRE frame into the next layer's event ring buffer without
    leaving the kernel — and restated here (not imported) because of the
    kernels-never-import-the-executor layering rule.  The arithmetic is
    kept line-for-line identical (iota sort keys, ``top_k`` of the negated
    keys, sentinel clamp, row-major decomposition), so the event order,
    gates and drop counts are bitwise the executor's.

    Args:
      s:   (H, W, C) one spike frame (accumulator dtype, exact 0/1).
      cap: the consumer layer's per-timestep event capacity.

    Returns ``(xyc (cap', 3) int32, gate (cap',) s.dtype,
    n_drop () int32)`` with ``cap' = min(cap, H*W*C)``.
    """
    H, W, C = s.shape
    S = H * W * C
    cap = min(cap, S)
    flat = s.reshape(1, S)
    nz = flat != 0
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    key = jnp.where(nz, idx, S)
    order = -jax.lax.top_k(-key, cap)[0]                     # (1, cap)
    gate = (order < S).astype(s.dtype)[0]
    order = jnp.minimum(order, S - 1)[0]                     # clamp pads
    x = order // (W * C)
    y = (order // C) % W
    c = order % C
    xyc = jnp.stack([x, y, c], axis=-1)
    n = jnp.sum(nz.astype(jnp.int32))
    n_drop = jnp.maximum(n - cap, 0)
    return xyc, gate, n_drop


def crop_interior(vp: jnp.ndarray, h: int) -> jnp.ndarray:
    """Crop the halo off ``(..., Hp, Wp, C)`` — the logical layer geometry.

    Restates `core.layer_program.interior` (see module doc for why it is
    not imported).
    """
    if h == 0:
        return vp
    return vp[..., h:vp.shape[-3] - h, h:vp.shape[-2] - h, :]


def write_cropped(vp: jnp.ndarray, x: jnp.ndarray, h: int) -> jnp.ndarray:
    """Write the logical interior back into the halo-padded buffer.

    Restates `core.layer_program.write_interior`.
    """
    if h == 0:
        return x
    return vp.at[..., h:vp.shape[-3] - h, h:vp.shape[-2] - h, :].set(x)


# ---------------------------------------------------------------------------
# Tile activity bitmaps (spatial sparsity inside the window kernels).
#
# One (N, nTx, nTy) int32 bitmap per layer marks which tiles of each slot's
# membrane *interior* can possibly be touched this window.  Seeded from the
# collector's event coordinates (`seed_site_map`), propagated layer to
# layer through the receptive-field footprint (`dilate_conv` /
# `dilate_pool`; FC layers are always-hot), and reduced to tile granularity
# (`sites_to_tiles`).  The contract the kernels rely on: the bitmap is a
# SUPERSET of the interior sites the window's scatters can write, and —
# because hard-reset membranes sit strictly below threshold at every
# boundary (`core.lif.supports_idle_skip`) — a cold tile can neither
# receive input nor fire, so its whole leak→clip→fire→reset sweep
# collapses to one analytic `idle_decay` at the end of the window.
# ---------------------------------------------------------------------------

def tile_grid(H: int, W: int, max_tiles: int = TILE_GRID_MAX):
    """Static tile grid for an (H, W) interior: ``(nTx, nTy, th, tw)``.

    At most ``max_tiles`` tiles per axis; edge tiles may be smaller (prime
    geometries stay exact — the kernels slice tiles with static bounds
    clamped to the interior).  Every tile is non-empty by construction:
    ``nT = ceil(dim / ceil(dim / min(dim, max_tiles)))``.  An empty axis
    (a conv whose kernel overhangs its padded input) has no tiles.
    """
    def axis(d):
        if d == 0:
            return 0, 1
        t = -(-d // min(d, max_tiles))
        return -(-d // t), t

    (nTx, th), (nTy, tw) = axis(H), axis(W)
    return (nTx, nTy, th, tw)


def tile_spans(H: int, W: int):
    """Static ``(ti, tj, x0, x1, y0, y1)`` bounds of every interior tile."""
    nTx, nTy, th, tw = tile_grid(H, W)
    return [(ti, tj, ti * th, min((ti + 1) * th, H),
             tj * tw, min((tj + 1) * tw, W))
            for ti in range(nTx) for tj in range(nTy)]


def seed_site_map(ev_xyc: jnp.ndarray, ev_gate: jnp.ndarray,
                  shape) -> jnp.ndarray:
    """Collector events -> (N, H, W) site-activity map (input coords).

    Marks every site a gated event names, any channel (the bitmaps track
    spatial activity only — the channel axis is the lane dimension the
    kernels never split).  Out-of-range coordinates are ignored rather
    than clamped onto a real site.

    Args:
      ev_xyc:  (T, N, E, 3) int32 window schedule in *layer* coordinates
               (pre halo shift).
      ev_gate: (T, N, E) validity gates.
      shape:   the layer's (H, W) input geometry.
    """
    H, W = shape
    T, N, E = ev_gate.shape
    x, y = ev_xyc[..., 0], ev_xyc[..., 1]
    ok = (ev_gate > 0) & (x >= 0) & (x < H) & (y >= 0) & (y < W)
    flat = jnp.clip(x, 0, H - 1) * W + jnp.clip(y, 0, W - 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (T, N, E), 1)
    m = jnp.zeros((N, H * W), jnp.float32)
    m = m.at[slot.reshape(-1), flat.reshape(-1)].max(
        ok.reshape(-1).astype(jnp.float32))
    return m.reshape(N, H, W)


def dilate_conv(site_map: jnp.ndarray, kernel: int,
                padding: int) -> jnp.ndarray:
    """Propagate an input site map through a conv's scatter footprint.

    The scatter writes an event's K-wide patch *starting at* its halo
    coordinate (``dynamic_slice`` at ``x + P`` into the ``halo == K - 1``
    slab — econv's halo rule), so input site ``x`` touches interior rows
    ``[x + P - K + 1, x + P]``.  Output site ``r`` can therefore be
    touched iff some active input lies in ``[r - P, r - P + K - 1]``:
    a max-pool with window K, stride 1 and padding P on both sides,
    which yields the layer's output geometry directly.
    (N, H, W) -> (N, H + 2P - K + 1, W + 2P - K + 1).
    """
    return jax.lax.reduce_window(
        site_map, 0.0, jax.lax.max, (1, kernel, kernel), (1, 1, 1),
        ((0, 0), (padding, padding), (padding, padding)))


def dilate_pool(site_map: jnp.ndarray, stride: int, out_shape) -> jnp.ndarray:
    """Propagate an input site map through a pool's scatter footprint.

    Input site ``(x, y)`` lands on output ``(x // s, y // s)``; events
    whose pooled coordinate falls past the output grid are dropped (the
    kernels' VALID-window rule), hence the crop before the reduction.
    (N, H, W) -> (N, Ho, Wo).
    """
    Ho, Wo = out_shape
    m = site_map[:, :Ho * stride, :Wo * stride]
    return jax.lax.reduce_window(m, 0.0, jax.lax.max, (1, stride, stride),
                                 (1, stride, stride), "VALID")


def sites_to_tiles(site_map: jnp.ndarray, grid) -> jnp.ndarray:
    """Reduce an (N, H, W) site map to its (N, nTx, nTy) tile bitmap."""
    nTx, nTy, th, tw = grid
    N, H, W = site_map.shape
    m = jnp.pad(site_map, ((0, 0), (0, nTx * th - H), (0, nTy * tw - W)))
    t = jax.lax.reduce_window(m, 0.0, jax.lax.max, (1, th, tw),
                              (1, th, tw), "VALID")
    return (t > 0).astype(jnp.int32)


def tiles_to_sites(tiles: jnp.ndarray, grid, shape) -> jnp.ndarray:
    """Upsample a tile bitmap back to site granularity (the ref's mask)."""
    _, _, th, tw = grid
    H, W = shape
    m = jnp.repeat(jnp.repeat(tiles, th, axis=-2), tw, axis=-1)
    return m[..., :H, :W]


def cold_tile_decay(v: jnp.ndarray, lif: LifParams, dt) -> jnp.ndarray:
    """Collapse a cold tile's whole window into one analytic decay.

    Delegates to `core.lif.idle_decay` — the exact contract the serving
    engine's window-level idle skip already relies on (``dt`` leak steps
    plus one clip, bitwise the iterated per-timestep sweep for the
    dyadic/integral leaks every shipped net uses).  ``dt`` is the number
    of *alive* timesteps in the window (frozen timesteps hold state in
    the dense path too); ``dt == 0`` is a bitwise no-op.
    """
    return idle_decay(v, lif, dt)


def window_grid_step(alive_ref, tiles_ref, v_ref, v_out_ref, s_out_ref,
                     acc_ref, scatter: Callable[[], None], *, halo: int,
                     n_steps: int, n_chunks: int, lif: LifParams,
                     native: bool) -> None:
    """One grid step ``(slot, block, timestep, event chunk)`` of a fused
    window kernel — the shared body of every ``*_window`` kernel.

    The membrane lives in ``acc_ref`` (VMEM scratch) across the timestep
    and chunk axes.  Per timestep the executor chain runs — ``leak`` on
    the first chunk, ``scatter()`` (the layer kind's event loop over the
    staged chunk) on every chunk, ``clip -> fire -> reset`` on the last —
    with the arithmetic of :func:`leak_boundary` / :func:`clip_fire_reset`
    (bitwise the per-step executor's).  A frozen timestep (``alive == 0``)
    runs nothing: it holds state and its spike frame stays zero.

    The leak/clip/fire sweeps are predicated per interior tile
    (:func:`tile_grid`) on the bitmap in ``tiles_ref``; a cold tile skips
    them and settles with one :func:`cold_tile_decay` after the last
    timestep (hard-reset layers only; an all-ones bitmap is the dense
    schedule).  FC layers pass ``tiles_ref=None``: their one site is
    always hot.  The scatter and the whole-slab native saturation stay
    unconditional, so halo cells behave exactly as in the dense path.

    alive_ref: (N, T) int32 SMEM (scalar prefetch) — per-timestep liveness.
    tiles_ref: (N * nTx * nTy,) int32 SMEM (scalar prefetch) slot-major
               tile bitmaps, or None.
    v_ref:     (1, Hp, Wp, BLK) — membrane block in storage dtype.
    v_out_ref: (1, Hp, Wp, BLK) — final membrane, storage dtype.
    s_out_ref: (1, 1, Ho, Wo, BLK) — this timestep's spike frame,
               accumulator dtype.
    acc_ref:   (1, Hp, Wp, BLK) VMEM scratch, accumulator dtype.
    """
    n, t, k = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    h = halo
    Ho, Wo = acc_ref.shape[1] - 2 * h, acc_ref.shape[2] - 2 * h
    nTx, nTy, _, _ = tile_grid(Ho, Wo)
    spans = tile_spans(Ho, Wo)
    alive = alive_ref[n, t] > 0
    first = k == 0
    last = k == n_chunks - 1

    def tile_on(ti, tj):
        if tiles_ref is None:
            return True
        return tiles_ref[(n * nTx + ti) * nTy + tj] > 0

    def region(x0, x1, y0, y1):
        return (0, slice(h + x0, h + x1), slice(h + y0, h + y1), slice(None))

    @pl.when((t == 0) & first)
    def _load():
        acc_ref[...] = v_ref[...].astype(acc_ref.dtype)

    @pl.when(first)
    def _clear():
        s_out_ref[...] = jnp.zeros_like(s_out_ref)   # cold tiles never fire

    for ti, tj, x0, x1, y0, y1 in spans:
        @pl.when(first & alive & tile_on(ti, tj))
        def _leak(r=region(x0, x1, y0, y1)):
            acc_ref[r] = leak_boundary(acc_ref[r], lif)

    pl.when(alive)(scatter)

    for ti, tj, x0, x1, y0, y1 in spans:
        @pl.when(last & alive & tile_on(ti, tj))
        def _fire(r=region(x0, x1, y0, y1), x0=x0, x1=x1, y0=y0, y1=y1):
            v_new, s = clip_fire_reset(acc_ref[r], lif)
            acc_ref[r] = v_new
            s_out_ref[0, 0, x0:x1, y0:y1, :] = s

    if native:
        @pl.when(last & alive)
        def _saturate():
            # int8 storage saturation at every boundary, halo included —
            # exactly the per-step executor's whole-slab downcast
            acc_ref[...] = saturate_int8(acc_ref[...])

    @pl.when((t == n_steps - 1) & last)
    def _store():
        if tiles_ref is not None and supports_idle_skip(lif):
            # settle cold tiles: dt alive boundaries of pure leak in one
            # step (soft-reset layers never get a real bitmap — the ops
            # wrappers refuse one — and all-ones has no cold tile)
            dtv = sum((alive_ref[n, u] > 0).astype(jnp.int32)
                      for u in range(n_steps))
            for ti, tj, x0, x1, y0, y1 in spans:
                @pl.when(jnp.logical_not(tile_on(ti, tj)))
                def _cold(r=region(x0, x1, y0, y1)):
                    acc_ref[r] = cold_tile_decay(acc_ref[r], lif, dtv)
        v_out_ref[...] = acc_ref[...].astype(v_out_ref.dtype)


def fused_window_ref(v: jnp.ndarray, ev_xyc: jnp.ndarray,
                     ev_gate: jnp.ndarray, alive: jnp.ndarray,
                     scatter: Callable, *, lif: LifParams, halo: int,
                     native: bool, tiles: jnp.ndarray | None = None):
    """Pure-jnp oracle driver shared by every ``*_window_ref``.

    Runs the fused window sequence — per timestep ``leak -> scatter ->
    clip -> fire -> reset`` with frozen-timestep fallback and (native) int8
    boundary saturation — per slot, in exactly the order the Pallas window
    kernels execute it.  ``scatter(acc, xyc_t, gate_t)`` is the layer
    kind's single-slot batch-scatter oracle (`event_conv_ref` and
    friends), already bit-for-bit the kernels' inner event loop.

    With ``tiles`` given, the dense result is patched to the tile-sparse
    kernels' semantics: cold interior sites are frozen through the window
    and settled with one :func:`cold_tile_decay`, and their spike frames
    are forced to zero.  This is bitwise the dense path wherever the tile
    bitmap honours its superset contract (no scatter write and no
    above-threshold initial state on a cold tile) — the condition the
    propagation rules guarantee for hard-reset layers.  Halo cells belong
    to no tile and keep their dense values, exactly as in the kernels
    (scatter and the whole-slab native saturation stay unconditional).

    Args:
      v:       (N, Hp, Wp, C) membranes in storage dtype.
      ev_xyc:  (N, T, E, 3) int32 packed window schedule.
      ev_gate: (N, T, E) validity gates.
      alive:   (N, T) per-timestep liveness.
      scatter: per-slot scatter oracle closing over weights/geometry.
      lif:     the layer's LIF plan.
      halo:    halo width (0 for pool/fc).
      native:  int8-native policy switch.
      tiles:   optional (N, nTx, nTy) activity bitmap over the interior
               (`tile_grid` geometry); None keeps the dense semantics.

    Returns ``(v_out (N, ...) storage dtype, spikes (N, T, ...)
    accumulator dtype)``.
    """
    acc_dt = window_acc_dtype(v.dtype, native)
    T = ev_xyc.shape[1]

    def one(vp, xyc, gate, al):
        acc = vp.astype(acc_dt)
        frames = []
        for t in range(T):
            prev = acc
            acc = write_cropped(acc, leak_boundary(crop_interior(acc, halo),
                                                   lif), halo)
            acc = scatter(acc, xyc[t], gate[t].astype(acc_dt))
            v_new, s = clip_fire_reset(crop_interior(acc, halo), lif)
            acc = write_cropped(acc, v_new, halo)
            if native:
                acc = saturate_int8(acc)
            a = al[t] > 0
            acc = jnp.where(a, acc, prev)
            frames.append(jnp.where(a, s, jnp.zeros_like(s)))
        return acc.astype(vp.dtype), jnp.stack(frames)

    v_out, frames = jax.vmap(one)(v, ev_xyc, ev_gate, alive)
    if tiles is None:
        return v_out, frames

    H = v.shape[1] - 2 * halo
    W = v.shape[2] - 2 * halo
    grid = tile_grid(H, W)
    mask = tiles_to_sites(tiles.astype(jnp.float32), grid, (H, W))
    cold = (mask == 0)[:, :, :, None]                        # (N, H, W, 1)
    dt = jnp.sum((alive > 0).astype(jnp.int32), axis=1).reshape(-1, 1, 1, 1)
    dec = cold_tile_decay(crop_interior(v, halo).astype(acc_dt), lif, dt)
    interior = crop_interior(v_out, halo)
    v_out = write_cropped(v_out, jnp.where(cold, dec.astype(v.dtype),
                                           interior), halo)
    frames = jnp.where(cold[:, None], jnp.zeros((), frames.dtype), frames)
    return v_out, frames
