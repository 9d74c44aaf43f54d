"""The unified layer-program executor: one event-domain network step.

The paper's SNE pipelines a whole eCNN through homogeneous engine slices —
every layer kind (conv, pool, FC) runs the *same* event-consume/fire
datapath (§III-C/D); only the scatter rule a consumed UPDATE event applies
to the membrane state differs.  This module is that design point in JAX:

  * :func:`compile_program` lowers ``SNNSpec`` into a :class:`LayerProgram`
    — a typed sequence of :class:`LayerOp` (scatter kind, halo,
    per-timestep event capacity, LIF plan);
  * one executor runs ``leak -> scatter -> clip -> fire -> reset`` for
    every layer kind, in two equivalent drivers over the same primitives:

      - :func:`layer_event_forward` / :func:`run_stream` — the
        single-stream scan (explicit time-sorted events, lazy TLU leak,
        RST support).  `core.econv.event_forward` and
        `core.sne_net.event_apply` are thin wrappers over these;
      - :func:`window_step` — the slot-batched serving step
        (`serve.event_engine.EventServeEngine` jits exactly this), where
        every layer's scatter is a slot-batched Pallas launch
        (`kernels/event_conv`, `kernels/event_pool`, `kernels/event_fc`)
        and inter-layer event routing (:func:`frame_to_events`) stays on
        device — the only dense materialisation between layers is the
        spike frame at FIRE.  Its **fusion policy** (compiled, like the
        dtype policy) picks the lowering: ``"per-step"`` (one scatter
        launch per layer per timestep — the bit-exactness oracle) or
        ``"fused-window"`` (the whole window per layer in ONE fused
        launch via :func:`layer_window`, time loop inside the kernel,
        membrane in VMEM scratch — L launches per window instead of
        L×T).

  * the per-layer capacity heuristics (:func:`layer_step_capacity` for
    serving-time per-timestep buckets, :func:`layer_stream_capacity` for
    whole-inference buffers) live here and nowhere else, so
    `sne_net.default_capacities` and `event_engine.default_step_capacities`
    cannot drift apart.

Having exactly one executor is what made the int4/int8 lowering a single
switch: every compiled program carries a **dtype policy** and every entry
point executes whichever datapath it names —

  * ``"f32-carrier"`` (default) — integer-domain values held in float32
    carriers, exact for |x| < 2^24.  Works for float nets too; for
    quantised nets it is the bit-exactness *oracle*.
  * ``"int8-native"`` (paper §III-D4) — int4-range weight codes stored as
    int8, int8 saturating membrane storage between timesteps, int32
    scatter accumulation inside a timestep.  Requires an integer-domain
    spec (`core.quant.quantize_net`); results are bitwise identical to
    the carrier oracle after a plain dtype cast, because both paths run
    the same exact integer arithmetic.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import TYPE_CHECKING, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import events as ev
from repro.core.econv import (EConvParams, EConvSpec, EConvStats, _halo,
                              dense_forward)
from repro.core.lif import (LifParams, apply_leak, fire_and_reset,
                            idle_decay, supports_idle_skip)
# the policy names live in the leaf module `core.policies` (see its
# docstring); re-exported here for every executor caller
from repro.core.policies import (DTYPE_POLICIES, F32_CARRIER, FUSED_NETWORK,
                                 FUSED_WINDOW, FUSION_POLICIES, INT8_NATIVE,
                                 PER_STEP, ExecutionPolicy, resolve_policy)
from repro.core.policies import all_policies as all_policies  # noqa: F401
from repro.core.quant import INT8_MAX, INT8_MIN, fake_quant_weights
from repro.kernels.event_conv.ops import (event_conv_batched,
                                          event_conv_window)
from repro.kernels.event_fc.ops import event_fc_batched, event_fc_window
from repro.kernels.event_pool.ops import (event_pool_batched,
                                          event_pool_window)
from repro.kernels.network_window import NetLayer, network_window
from repro.kernels.window_common import (dilate_conv, dilate_pool,
                                         seed_site_map, sites_to_tiles,
                                         tile_grid, tiles_to_sites)

if TYPE_CHECKING:  # pragma: no cover - annotation only (avoids an import cycle)
    from repro.core.sne_net import SNNSpec


# ---------------------------------------------------------------------------
# Capacity heuristics — THE single source for core and serving.
# ---------------------------------------------------------------------------

def layer_step_capacity(lspec: EConvSpec, activity: float = 0.25,
                        slack: float = 4.0, align: int = 8) -> int:
    """Per-timestep *input*-event bucket for one layer (collector + FIFOs).

    Sizes one timestep's bucket on the layer's input geometry; ``activity``
    is the expected per-step fraction of active input sites and ``slack``
    over-provisions like the ASIC FIFO sizing.
    """
    return ev.capacity_for((1,) + lspec.in_shape, activity, slack,
                           align=align)


def layer_stream_capacity(lspec: EConvSpec, n_timesteps: int,
                          activity: float = 0.05, slack: float = 4.0) -> int:
    """Whole-inference *output*-event buffer for one layer (FIFO/DMA).

    Sizes the full event stream a layer may emit over ``n_timesteps`` on
    its output geometry — the `event_apply` buffer analogue.
    """
    return ev.capacity_for((n_timesteps,) + lspec.out_shape, activity,
                           slack)


# ---------------------------------------------------------------------------
# The program: SNNSpec + params metadata -> typed ops.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerOp:
    """One layer lowered onto the homogeneous event datapath.

    Everything the executor needs, resolved at compile time: the scatter
    kind (which Pallas kernel family consumes this layer's events), the
    halo width (conv scatters need address headroom; pool/FC do not), the
    per-timestep input-event capacity (the serving-side FIFO), the LIF
    plan (shared leak/fire/reset dynamics), and the dtype policy (which
    datapath — float carrier or native integer — executes it).
    """

    index: int
    spec: EConvSpec
    halo: int
    step_capacity: int
    dtype_policy: str = F32_CARRIER

    @property
    def kind(self) -> str:
        """Scatter kind ("conv" | "pool" | "fc")."""
        return self.spec.kind

    @property
    def lif(self) -> LifParams:
        """The layer's LIF plan (shared boundary dynamics)."""
        return self.spec.lif


@dataclasses.dataclass(frozen=True)
class LayerProgram:
    """A compiled eCNN: the typed op sequence every entry point executes.

    ``dtype_policy`` names the dtype domain the datapath computes in;
    ``fusion_policy`` names how :func:`window_step` lowers a window onto
    Pallas launches — ``"per-step"`` (one scatter launch per layer per
    timestep; the bit-exactness oracle) or ``"fused-window"`` (one fused
    launch per layer for the whole window).  Both are compiled in, so the
    jitted serving step closes over one fully-resolved execution plan.
    """

    spec: "SNNSpec"
    ops: Tuple[LayerOp, ...]
    dtype_policy: str = F32_CARRIER
    fusion_policy: str = PER_STEP
    tile_sparsity: bool = True

    def __len__(self) -> int:
        return len(self.ops)

    @property
    def step_capacities(self) -> Tuple[int, ...]:
        """Per-layer per-timestep event buckets the program baked in."""
        return tuple(op.step_capacity for op in self.ops)


def state_dtype(op: LayerOp):
    """Membrane *storage* dtype between timesteps (the resident slabs)."""
    return jnp.int8 if op.dtype_policy == INT8_NATIVE else jnp.float32


def acc_dtype(op: LayerOp):
    """Accumulator dtype a timestep computes in (leak/scatter/fire)."""
    return jnp.int32 if op.dtype_policy == INT8_NATIVE else jnp.float32


def scatter_dtypes(op: LayerOp):
    """Dtypes of one scatter launch: ``(v_in, v_out, weights, gate)``.

    The native path feeds the kernel its int8 storage slab directly when
    the post-leak state provably stays in int8 range ("toward_zero" leak
    only shrinks |v|); a "subtract" leak can transiently leave the range,
    so the slab is widened to the accumulator before the launch.  Gates
    ride at the slab dtype (the kernels cast them to ``v.dtype``).
    """
    if op.dtype_policy == INT8_NATIVE:
        v_in = (jnp.int8 if op.lif.leak_mode == "toward_zero"
                else jnp.int32)
        return v_in, jnp.int32, jnp.int8, v_in
    f = jnp.float32
    return f, f, f, f


def validate_policy_layer(lspec: EConvSpec, index: int,
                          dtype_policy: str) -> None:
    """Reject a layer spec the named datapath cannot execute exactly.

    int8-native needs a genuinely integer-domain layer: integral threshold /
    leak (they become int32 scalars) and an int8-representable state clip
    (the storage saturation).  `core.quant.quantize_net` produces exactly
    such specs; float nets must go through it first.
    """
    if dtype_policy not in DTYPE_POLICIES:
        raise ValueError(f"unknown dtype policy {dtype_policy!r} "
                         f"(expected one of {DTYPE_POLICIES})")
    if dtype_policy == F32_CARRIER:
        return
    p = lspec.lif
    if p.state_clip is None or not (0 < p.state_clip <= INT8_MAX):
        raise ValueError(
            f"layer {index}: int8-native requires state_clip in (0, "
            f"{INT8_MAX}], got {p.state_clip} — lower the net with "
            f"core.quant.quantize_net first")
    for name, val in (("threshold", p.threshold), ("leak", p.leak),
                      ("state_clip", p.state_clip)):
        if not float(val).is_integer():
            raise ValueError(
                f"layer {index}: int8-native requires integral {name}, got "
                f"{val} — lower the net with core.quant.quantize_net")


def validate_policy_spec(spec: "SNNSpec", dtype_policy: str) -> None:
    """Whole-network face of :func:`validate_policy_layer`."""
    if dtype_policy not in DTYPE_POLICIES:
        raise ValueError(f"unknown dtype policy {dtype_policy!r} "
                         f"(expected one of {DTYPE_POLICIES})")
    for i, l in enumerate(spec.layers):
        validate_policy_layer(l, i, dtype_policy)


def layer_op(spec: EConvSpec, index: int = 0,
             step_capacity: Optional[int] = None,
             dtype_policy: str = F32_CARRIER) -> LayerOp:
    """Lower a single layer spec (the one-layer program used by econv).

    Validates the spec against the policy here — every op construction
    path (`compile_program`, `econv.event_forward`, direct use) gets the
    same loud rejection instead of silently truncating float dynamics.
    """
    validate_policy_layer(spec, index, dtype_policy)
    return LayerOp(index=index, spec=spec, halo=_halo(spec),
                   step_capacity=(step_capacity if step_capacity is not None
                                  else layer_step_capacity(spec)),
                   dtype_policy=dtype_policy)


def compile_program(spec: "SNNSpec",
                    step_capacities: Optional[Tuple[int, ...]] = None,
                    step_activity: float = 0.25, step_slack: float = 4.0,
                    step_align: int = 8,
                    dtype_policy: Optional[str] = None,
                    fusion_policy: Optional[str] = None,
                    policy: Optional[ExecutionPolicy] = None) -> LayerProgram:
    """Compile ``SNNSpec`` into the typed op sequence the executors run.

    ``step_capacities`` overrides the per-layer per-timestep event buckets
    (one per layer); by default :func:`layer_step_capacity` sizes them.
    ``policy`` (an `ExecutionPolicy`) selects the datapath dtype domain
    and the window lowering in one value; the program records only the
    two compile-time axes (``idle_skip`` and ``backend`` are serving-time
    concerns).  The legacy ``dtype_policy=`` / ``fusion_policy=`` kwargs
    keep working through the deprecation shim, with their historical
    defaults (f32 carrier, per-step).  Results are cached (LRU) on the
    resolved policy, so equal calls share one program object — static and
    hashable, safe to close over in ``jax.jit``.
    """
    pol = resolve_policy(
        "core.layer_program.compile_program", policy,
        default=ExecutionPolicy(fusion_policy=PER_STEP),
        dtype_policy=dtype_policy, fusion_policy=fusion_policy)
    return _compile_program_cached(spec, step_capacities, step_activity,
                                   step_slack, step_align,
                                   pol.dtype_policy, pol.fusion_policy,
                                   pol.tile_sparsity)


@functools.lru_cache(maxsize=64)
def _compile_program_cached(spec: "SNNSpec",
                            step_capacities: Optional[Tuple[int, ...]],
                            step_activity: float, step_slack: float,
                            step_align: int, dtype_policy: str,
                            fusion_policy: str,
                            tile_sparsity: bool = True) -> LayerProgram:
    """Cached compile body keyed on the resolved policy axes."""
    if step_capacities is not None and len(step_capacities) != len(spec.layers):
        raise ValueError("need one per-timestep capacity per layer")
    if dtype_policy not in DTYPE_POLICIES:   # layer_op re-checks per layer,
        raise ValueError(                    # but an empty spec must not slip
            f"unknown dtype policy {dtype_policy!r} "
            f"(expected one of {DTYPE_POLICIES})")
    if fusion_policy not in FUSION_POLICIES:
        raise ValueError(f"unknown fusion policy {fusion_policy!r} "
                         f"(expected one of {FUSION_POLICIES})")
    ops = []
    for i, l in enumerate(spec.layers):
        cap = (step_capacities[i] if step_capacities is not None
               else layer_step_capacity(l, step_activity, step_slack,
                                        step_align))
        ops.append(layer_op(l, index=i, step_capacity=cap,
                            dtype_policy=dtype_policy))
    return LayerProgram(spec=spec, ops=tuple(ops), dtype_policy=dtype_policy,
                        fusion_policy=fusion_policy,
                        tile_sparsity=tile_sparsity)


def default_stream_capacities(spec: "SNNSpec", activity: float = 0.05,
                              slack: float = 4.0) -> List[int]:
    """Whole-inference output buffers, one per layer (`event_apply`)."""
    return [layer_stream_capacity(l, spec.n_timesteps, activity, slack)
            for l in spec.layers]


def default_step_capacities(spec: "SNNSpec", activity: float = 0.25,
                            slack: float = 4.0, align: int = 8) -> List[int]:
    """Per-timestep input buckets, one per layer (the serving collector)."""
    return [layer_step_capacity(l, activity, slack, align)
            for l in spec.layers]


# ---------------------------------------------------------------------------
# Shared state-geometry primitives (3D single-stream and 4D slot-batched).
# ---------------------------------------------------------------------------

def padded_state(op: LayerOp, dtype=None, n_slots: Optional[int] = None
                 ) -> jnp.ndarray:
    """Zero halo-padded membrane state; batched when ``n_slots`` is given.

    ``dtype=None`` picks the op's policy storage dtype (:func:`state_dtype`).
    """
    if dtype is None:
        dtype = state_dtype(op)
    Ho, Wo, Co = op.spec.out_shape
    h = op.halo
    shape = (Ho + 2 * h, Wo + 2 * h, Co)
    if n_slots is not None:
        shape = (n_slots,) + shape
    return jnp.zeros(shape, dtype)


def interior(vp: jnp.ndarray, h: int) -> jnp.ndarray:
    """Crop the halo off ``(..., Hp, Wp, C)`` — logical layer geometry."""
    if h == 0:
        return vp
    return vp[..., h:vp.shape[-3] - h, h:vp.shape[-2] - h, :]


def write_interior(vp: jnp.ndarray, x: jnp.ndarray, h: int) -> jnp.ndarray:
    """Write the logical interior back into the halo-padded buffer."""
    if h == 0:
        return x
    return vp.at[..., h:vp.shape[-3] - h, h:vp.shape[-2] - h, :].set(x)


def clip_state(v: jnp.ndarray, p: LifParams) -> jnp.ndarray:
    """8-bit-state saturation (no-op when the layer has no clip).

    dtype-generic: the bound rides at ``v.dtype`` (float carrier or the
    int32 accumulator — integral by the int8-native validation).
    """
    if p.state_clip is None:
        return v
    c = jnp.asarray(p.state_clip, v.dtype)
    return jnp.clip(v, -c, c)


# ---------------------------------------------------------------------------
# The scatter primitive — every layer kind, single-event and slot-batched.
# ---------------------------------------------------------------------------

def scatter_event(op: LayerOp, params: EConvParams, vp: jnp.ndarray,
                  e_x, e_y, e_c, gate) -> jnp.ndarray:
    """Accumulate ONE event's synaptic contribution (UPDATE_OP datapath).

    The per-event form the single-stream scan consumes; the slot-batched
    kernels implement exactly this rule over whole event batches.
    """
    spec = op.spec
    if spec.kind == "conv":
        K = spec.kernel
        # out[i, j, :] += W[i', j', c, :] with i' = e_x + P - i  => flipped W.
        w_f = jnp.flip(jnp.flip(params.w, 0), 1)          # (K, K, Ci, Co)
        patch = jnp.take(w_f, e_c, axis=2) * gate          # (K, K, Co)
        ox = e_x + spec.padding   # origin in halo coords (always in bounds)
        oy = e_y + spec.padding
        cur = jax.lax.dynamic_slice(vp, (ox, oy, 0), (K, K, vp.shape[2]))
        return jax.lax.dynamic_update_slice(vp, cur + patch, (ox, oy, 0))
    if spec.kind == "pool":
        s = spec.stride
        val = jnp.take(params.w, e_c) * gate
        return vp.at[e_x // s, e_y // s, e_c].add(val)
    # fc: flatten (x, y, c) -> row of the weight matrix
    H, W, C = spec.in_shape
    flat = (e_x * W + e_y) * C + e_c
    row = jnp.take(params.w, flat, axis=0) * gate          # (Dout,)
    return vp.at[0, 0, :].add(row)


def _channel_block(n_channels: int, want: int) -> int:
    """Largest channel-block size <= ``want`` that divides ``n_channels``.

    The kernels tile their lane dimension in equal blocks, so the block
    must divide the channel count; any width (192, 11, ...) stays
    servable, it just gets a smaller-than-requested block.
    """
    b = min(want, n_channels)
    while n_channels % b:
        b -= 1
    return b


def check_native_weights(op: LayerOp, params: EConvParams) -> None:
    """int8-native requires integer weight codes, loudly (dtype is static,
    so this check is jit-safe — it fires at trace time, not per step)."""
    if (op.dtype_policy == INT8_NATIVE
            and not jnp.issubdtype(params.w.dtype, jnp.integer)):
        raise ValueError(
            f"layer {op.index} ({op.kind}): int8-native execution needs "
            f"integer weight codes, got {params.w.dtype} — lower the net "
            f"with core.quant.quantize_net and use params_for('int8-native')")


def scatter_events_batched(op: LayerOp, params: EConvParams, vp: jnp.ndarray,
                           xyc: jnp.ndarray, gate: jnp.ndarray,
                           co_blk: int = 128,
                           use_pallas: Optional[bool] = None) -> jnp.ndarray:
    """Accumulate all slots' event batches into all slots' membranes.

    One slot-batched Pallas launch per layer, whatever the kind — the
    parametrized scatter primitive of the composable dataflow:

      conv: per-event ``K x K x Co`` weight-patch accumulate (halo coords);
      pool: strided per-event one-site add (``kernels/event_pool``);
      fc:   gated weight-row gather accumulate (``kernels/event_fc``).

    Under the int8-native policy the launch consumes the int8 slab (or the
    int32-widened one for "subtract" leak — see :func:`scatter_dtypes`)
    and returns the int32 accumulator slab; the carrier policy is
    unchanged (dtype in == dtype out).
    """
    spec = op.spec
    check_native_weights(op, params)
    out_dtype = acc_dtype(op) if op.dtype_policy == INT8_NATIVE else None
    if spec.kind == "conv":
        # shift into halo coordinates (same arithmetic as scatter_event)
        off = jnp.asarray([spec.padding, spec.padding, 0], jnp.int32)
        return event_conv_batched(vp, params.w, xyc + off, gate,
                                  co_blk=_channel_block(spec.out_channels,
                                                        co_blk),
                                  use_pallas=use_pallas, out_dtype=out_dtype)
    if spec.kind == "pool":
        return event_pool_batched(vp, params.w, xyc, gate,
                                  stride=spec.stride, use_pallas=use_pallas,
                                  out_dtype=out_dtype)
    return event_fc_batched(vp, params.w, xyc, gate, in_shape=spec.in_shape,
                            d_blk=_channel_block(spec.out_channels, co_blk),
                            use_pallas=use_pallas, out_dtype=out_dtype)


def scatter_launch_bytes(op: LayerOp, n_slots: int, n_events: int) -> int:
    """Bytes one slot-batched scatter launch moves (operands + result).

    The dtype rules come from :func:`scatter_dtypes` — the same single
    source the executor uses — so this accounting cannot drift from what
    the kernels actually consume.  Events are int32 triples under every
    policy; weights, gates and the membrane slabs carry the policy dtypes.
    This is the figure `benchmarks/layer_program.py` pins: the int8-native
    launch must move strictly fewer bytes than the float carrier's.
    """
    v_in_dt, v_out_dt, w_dt, gate_dt = scatter_dtypes(op)
    spec = op.spec
    Ho, Wo, Co = spec.out_shape
    h = op.halo
    slab = n_slots * (Ho + 2 * h) * (Wo + 2 * h) * Co
    if spec.kind == "conv":
        H, W, Ci = spec.in_shape
        w_elems = spec.kernel * spec.kernel * Ci * spec.out_channels
    elif spec.kind == "pool":
        w_elems = spec.in_shape[2]
    else:
        H, W, Ci = spec.in_shape
        w_elems = H * W * Ci * spec.out_channels
    isz = (lambda dt: jnp.dtype(dt).itemsize)
    return (n_slots * n_events * 3 * 4            # event triples, int32
            + n_slots * n_events * isz(gate_dt)   # validity gates
            + w_elems * isz(w_dt)                 # shared weights
            + slab * isz(v_in_dt)                 # membrane slab in
            + slab * isz(v_out_dt))               # accumulator slab out


# ---------------------------------------------------------------------------
# The executor step: leak -> scatter -> clip -> fire -> reset, any kind.
# ---------------------------------------------------------------------------

def layer_timestep(op: LayerOp, params: EConvParams, vp: jnp.ndarray,
                   xyc: jnp.ndarray, gate: jnp.ndarray,
                   alive_t: jnp.ndarray, co_blk: int = 128,
                   use_pallas: Optional[bool] = None):
    """One layer x one timestep for every slot: the uniform datapath.

    ``alive_t`` (N,) freezes slots whose request has no timestep here (the
    tail of a window past a short request) — their state and spikes are
    held/zeroed so a frozen slot is bit-identical to not stepping it.

    Carrier policy: everything stays float32.  int8-native policy: ``vp``
    is the int8 storage slab; leak runs in the int32 accumulator, the
    scatter consumes the narrowest exact slab (:func:`scatter_dtypes`) and
    accumulates in int32, clip/fire/reset run in int32, and the result is
    saturated back to int8 storage.  The interior is exact by construction
    (post-clip values fit int8); halo cells are write-only scratch — they
    never feed an output — so saturating them is harmless.
    """
    lp = op.lif
    h = op.halo
    if op.dtype_policy == INT8_NATIVE:
        acc = acc_dtype(op)
        v_in_dt = scatter_dtypes(op)[0]
        v_l = apply_leak(interior(vp, h).astype(acc), lp.leak, 1,
                         lp.leak_mode)
        vp_l = write_interior(vp.astype(v_in_dt), v_l.astype(v_in_dt), h)
        vp_s = scatter_events_batched(op, params, vp_l, xyc, gate, co_blk,
                                      use_pallas)                 # int32
        v = clip_state(interior(vp_s, h), lp)
        v, s = fire_and_reset(v, lp)
        vp_new = write_interior(vp_s, v, h)
        vp_new = jnp.clip(vp_new, INT8_MIN, INT8_MAX).astype(jnp.int8)
    else:
        vp_l = write_interior(vp, apply_leak(interior(vp, h), lp.leak, 1,
                                             lp.leak_mode), h)
        vp_s = scatter_events_batched(op, params, vp_l, xyc, gate, co_blk,
                                      use_pallas)
        v = clip_state(interior(vp_s, h), lp)
        v, s = fire_and_reset(v, lp)
        vp_new = write_interior(vp_s, v, h)
    m = alive_t.reshape(-1, 1, 1, 1)
    # where (not s * m): keeps the spike dtype policy-native (int32 spikes
    # would promote to f32 against the f32 alive mask); bitwise identical
    # for the carrier since spikes are exactly 0/1
    s = jnp.where(m > 0, s, jnp.zeros_like(s))
    return jnp.where(m > 0, vp_new, vp), s


def frame_to_events(s: jnp.ndarray, cap: int):
    """Slot-batched dense spike frames -> padded event lists (routing).

    s: (N, H, W, C) binary spike frames. Returns ``(xyc (N,cap,3),
    gate (N,cap), n_drop (N,))``. Event order is row-major (the same order
    ``dense_to_events`` emits within a timestep); overflow beyond ``cap``
    is dropped and counted — the inter-layer FIFO back-pressure.
    """
    N, H, W, C = s.shape
    S = H * W * C
    cap = min(cap, S)
    flat = s.reshape(N, S)
    nz = flat != 0
    # first `cap` nonzero sites in row-major order: nonzero sites keep
    # their flat index as sort key, zeros get the sentinel S; top_k of the
    # negated keys is O(S log cap) vs a full argsort's O(S log S).
    idx = jax.lax.broadcasted_iota(jnp.int32, (N, S), 1)
    key = jnp.where(nz, idx, S)
    order = -jax.lax.top_k(-key, cap)[0]                          # (N, cap)
    gate = (order < S).astype(s.dtype)
    order = jnp.minimum(order, S - 1)                             # clamp pads
    x = order // (W * C)
    y = (order // C) % W
    c = order % C
    xyc = jnp.stack([x, y, c], axis=-1)
    n = jnp.sum(nz.astype(jnp.int32), axis=1)
    n_drop = jnp.maximum(n - cap, 0)
    return xyc, gate, n_drop


def apply_idle_decay(states, dt, *, program: LayerProgram):
    """Apply each slot's deferred idle decay to every layer's interior.

    ``dt`` (N,) counts the input-free timesteps accumulated while the slot
    was being skipped; `core.lif.idle_decay` collapses them analytically
    (leak + clip) in one elementwise pass.  Slots with ``dt == 0`` come
    back bit-identical.  Traced inside :func:`window_step`, so the flush
    costs no separate dispatch.
    """
    dt4 = dt.reshape(-1, 1, 1, 1)
    out = []
    for vp, op in zip(states, program.ops):
        if not supports_idle_skip(op.lif):
            # soft-reset networks run with idle_skip force-disabled, so
            # their deferred dt is always zero — pass the slab through
            out.append(vp)
            continue
        v_in = interior(vp, op.halo)
        if op.dtype_policy == INT8_NATIVE:
            # decay in the wide accumulator (leak * dt can overflow int8);
            # idle_decay ends clipped, so the downcast back is exact
            dec = idle_decay(v_in.astype(acc_dtype(op)), op.lif,
                             dt4).astype(jnp.int8)
        else:
            dec = idle_decay(v_in, op.lif, dt4.astype(v_in.dtype))
        out.append(write_interior(vp, dec, op.halo))
    return tuple(out)


def effective_tile_sparsity(program: LayerProgram) -> bool:
    """Whether the fused drivers will thread tile activity bitmaps.

    Tile sparsity needs every layer hard-reset (``reset_mode == "zero"``):
    a cold tile settles with ONE analytic decay (`core.lif.idle_decay`),
    which has no closed form under soft reset.  Soft-reset programs run
    dense — silently, like ``idle_skip`` — so the policy default (on)
    never rejects a network the optimisation cannot serve exactly.  The
    per-step driver is the bit-exactness oracle and never consults this.
    """
    return (program.tile_sparsity
            and all(supports_idle_skip(op.lif) for op in program.ops))


def window_tile_maps(program: LayerProgram, ev_xyc: jnp.ndarray,
                     ev_gate: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
    """Per-layer (N, nTx, nTy) tile activity bitmaps for one window.

    Seeds a layer-0 site map from the collector's event coordinates
    (``ev_xyc`` (T, N, E0, 3) / ``ev_gate`` (T, N, E0), layer coords —
    the driver layout BEFORE the slot-major transpose), then walks the
    program: each layer dilates the incoming map through its receptive
    field (conv: K×K halo; pool: stride window; fc: always-hot — one
    output site fed by everything) and coarsens it to the layer's
    `kernels.window_common.tile_grid`.

    Propagation is tile-granular ON PURPOSE: the window kernels run the
    leak/fire sweep on every site of a hot tile, so any such site may
    spike (e.g. carried-in membrane at threshold) — the next layer must
    see the *upsampled tile footprint* (``tiles_to_sites``), not the raw
    site map, or the bitmap would undercount downstream activity and
    break the superset contract the kernels rely on.
    """
    op0 = program.ops[0].spec
    in_map = seed_site_map(ev_xyc, ev_gate, op0.in_shape[:2])
    tiles = []
    for op in program.ops:
        spec = op.spec
        Ho, Wo, _ = spec.out_shape
        if spec.kind == "conv":
            out_map = dilate_conv(in_map, spec.kernel, spec.padding)
        elif spec.kind == "pool":
            out_map = dilate_pool(in_map, spec.stride, (Ho, Wo))
        else:
            out_map = jnp.ones((in_map.shape[0], Ho, Wo), jnp.float32)
        grid = tile_grid(Ho, Wo)
        t = sites_to_tiles(out_map, grid)
        tiles.append(t)
        in_map = tiles_to_sites(t.astype(jnp.float32), grid, (Ho, Wo))
    return tuple(tiles)


def window_kernel_name(op: LayerOp) -> str:
    """The name of ``op``'s fused-window launch: its layer index and kind
    (``layer2_pool_window``), stable across slot and event buckets."""
    return f"layer{op.index}_{op.spec.kind}_window"


def layer_window(op: LayerOp, params: EConvParams, vp: jnp.ndarray,
                 xyc: jnp.ndarray, gate: jnp.ndarray, alive: jnp.ndarray,
                 co_blk: int = 128, use_pallas: Optional[bool] = None,
                 tiles: Optional[jnp.ndarray] = None):
    """One layer × one WHOLE window for every slot: one fused launch.

    The fused-window counterpart of :func:`layer_timestep`: the full
    ``leak -> scatter -> clip -> fire -> reset`` chain over all T
    timesteps runs inside a single Pallas launch per layer
    (``kernels/event_conv|event_pool|event_fc`` ``*_window`` kernels),
    with the membrane carried in VMEM scratch between iterations and the
    per-timestep event buckets passed as a packed schedule.  Results —
    final membranes and every timestep's spike frame — are bitwise
    identical to iterating :func:`layer_timestep` (the per-step oracle),
    under both dtype policies.

    Args:
      vp:    (N, Hp, Wp, C) membrane slab in the op's storage dtype.
      xyc:   (T, N, E, 3) int32 events binned by timestep (layer coords;
             conv shifts into halo coords here, like the per-step path).
      gate:  (T, N, E) validity gates.
      alive: (T, N) 1.0 where the slot has a real timestep (frozen
             timesteps hold state and emit no spikes, exactly the
             per-step ``alive_t`` semantics).
      tiles: optional (N, nTx, nTy) tile activity bitmap
             (:func:`window_tile_maps` geometry) — cold tiles skip the
             per-timestep sweep inside the kernel and settle with one
             analytic decay.  Ignored for fc layers (a single always-hot
             output site).

    Returns ``(vp_new, spikes (T, N, Ho, Wo, C))`` with spikes in the
    op's accumulator dtype (what :func:`frame_to_events` routes onward).
    The launch is named ``layer<index>_<kind>_window`` in the compiled
    program and the profiler trace (:func:`window_kernel_name`).
    """
    spec = op.spec
    check_native_weights(op, params)
    native = op.dtype_policy == INT8_NATIVE
    name = window_kernel_name(op)
    x = jnp.transpose(xyc, (1, 0, 2, 3))     # slot-major for the kernels
    g = jnp.transpose(gate, (1, 0, 2))
    a = jnp.transpose(alive, (1, 0))
    if spec.kind == "conv":
        off = jnp.asarray([spec.padding, spec.padding, 0], jnp.int32)
        vp_new, s = event_conv_window(
            vp, params.w, x + off, g, a, lif=op.lif, halo=op.halo,
            co_blk=_channel_block(spec.out_channels, co_blk), native=native,
            use_pallas=use_pallas, tiles=tiles, name=name)
    elif spec.kind == "pool":
        vp_new, s = event_pool_window(vp, params.w, x, g, a, lif=op.lif,
                                      stride=spec.stride, native=native,
                                      use_pallas=use_pallas, tiles=tiles,
                                      name=name)
    else:
        vp_new, s = event_fc_window(
            vp, params.w, x, g, a, lif=op.lif, in_shape=spec.in_shape,
            d_blk=_channel_block(spec.out_channels, co_blk), native=native,
            use_pallas=use_pallas, name=name)
    return vp_new, jnp.transpose(s, (1, 0, 2, 3, 4))


def _window_step_fused(params: Sequence[EConvParams], states, class_counts,
                       ev_xyc, ev_gate, alive, pre_dt, *,
                       program: LayerProgram, co_blk: int = 128,
                       use_pallas: Optional[bool] = None):
    """The fused-window driver behind :func:`window_step` (L launches).

    Layer-major instead of timestep-major: layer *l* at timestep *t*
    depends only on layer *l-1*'s frames at the same timestep and its own
    state, so the whole window can run layer by layer — each layer ONE
    fused launch (:func:`layer_window`) — with :func:`frame_to_events`
    routing every timestep's FIRE frame at once (vmapped over the window,
    still on device, still zero extra launches).  Outputs are bitwise
    equal to the per-step driver's.
    """
    L = len(program.ops)
    N = class_counts.shape[0]
    states = list(apply_idle_decay(states, pre_dt, program=program))
    tiles = (window_tile_maps(program, ev_xyc, ev_gate)
             if effective_tile_sparsity(program) else None)
    counts = jnp.zeros((L, N), jnp.float32)
    drops = jnp.zeros((L, N), jnp.int32)
    xyc, gate = ev_xyc, ev_gate
    s_frames = None
    for op, p in zip(program.ops, params):
        if op.index > 0:
            xyc, gate, n_drop = jax.vmap(
                lambda s, cap=op.step_capacity: frame_to_events(s, cap)
            )(s_frames)
            drops = drops.at[op.index].add(jnp.sum(n_drop, axis=0))
        counts = counts.at[op.index].add(
            jnp.sum(gate, axis=(0, 2)).astype(counts.dtype))
        states[op.index], s_frames = layer_window(
            op, p, states[op.index], xyc, gate, alive, co_blk, use_pallas,
            tiles=None if tiles is None else tiles[op.index])
    class_counts = class_counts + jnp.sum(
        s_frames, axis=(0, 2, 3)).astype(class_counts.dtype)
    return tuple(states), class_counts, counts, drops


# ---------------------------------------------------------------------------
# The fused-network driver: the whole program in ONE launch per window.
# ---------------------------------------------------------------------------

# Per-core VMEM on current TPUs is ~16 MiB; the megakernel must fit every
# layer's accumulator slab + the boundary ring buffers + its I/O blocks in
# one grid step's budget, or the driver falls back to fused-window.
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024


def _slab_elems(op: LayerOp) -> int:
    """Elements of one slot's halo-padded membrane slab."""
    Ho, Wo, Co = op.spec.out_shape
    h = op.halo
    return (Ho + 2 * h) * (Wo + 2 * h) * Co


def _ring_capacity(program: LayerProgram, index: int) -> int:
    """Ring-buffer width of the boundary feeding layer ``index`` (>= 1).

    The consumer's compiled per-timestep capacity, clamped to the
    producer's frame size — the same clamp :func:`frame_to_events`
    applies, so the in-kernel buffers are sized exactly like the
    off-kernel event lists they replace.
    """
    h, w, c = program.ops[index - 1].spec.out_shape
    return min(program.ops[index].step_capacity, h * w * c)


@dataclasses.dataclass(frozen=True)
class NetworkWindowPlan:
    """VMEM accounting of one fused-network grid step (one slot).

    ``membrane_bytes`` is the resident accumulator scratch (every layer's
    slab at once), ``ring_bytes`` the inter-layer event ring buffers,
    ``io_bytes`` the input/output blocks pallas stages for the step
    (schedule, weights, storage slabs in and out, last-layer spike
    frames, counters).  ``total_bytes`` is what must fit the scratch
    budget for the megakernel to launch.
    """

    membrane_bytes: int
    ring_bytes: int
    io_bytes: int

    @property
    def total_bytes(self) -> int:
        """Whole per-grid-step VMEM footprint (scratch + staged blocks)."""
        return self.membrane_bytes + self.ring_bytes + self.io_bytes


def network_window_plan(program: LayerProgram,
                        n_timesteps: int) -> NetworkWindowPlan:
    """Size the fused-network megakernel's per-grid-step VMEM footprint.

    Deterministic per ``(program, n_timesteps)``: the layer-0 event width
    is the program's compiled collector capacity (``step_capacities[0]``,
    the worst case the engine can launch), NOT the traced axis — so the
    serving engine's launch accounting and the driver's budget decision
    can never diverge across idle-skip compaction buckets.
    """
    acc_isz = 4                                   # int32 / float32
    sto_isz = 1 if program.dtype_policy == INT8_NATIVE else 4
    ops = program.ops
    membrane = sum(_slab_elems(op) for op in ops) * acc_isz
    ring = sum(_ring_capacity(program, i) * (3 * 4 + acc_isz)
               for i in range(1, len(ops)))
    # per-boundary spike-frame scratch: tile-granular fire writes cannot
    # produce a routing *value*, so every non-last layer stages its
    # current frame in VMEM before route_frame reads it
    ring += sum(op.spec.out_shape[0] * op.spec.out_shape[1]
                * op.spec.out_shape[2] for op in ops[:-1]) * acc_isz
    e0 = ops[0].step_capacity
    Ho, Wo, Co = ops[-1].spec.out_shape
    io = (n_timesteps * e0 * 3 * 4                # layer-0 schedule
          + n_timesteps * e0 * acc_isz            # layer-0 gates
          + n_timesteps * 4)                      # alive row
    for op in ops:
        w_isz = jnp.dtype(scatter_dtypes(op)[2]).itemsize
        spec = op.spec
        if spec.kind == "conv":
            w_elems = spec.kernel ** 2 * spec.in_shape[2] * spec.out_channels
        elif spec.kind == "pool":
            w_elems = spec.in_shape[2]
        else:
            h, w, c = spec.in_shape
            w_elems = h * w * c * spec.out_channels
        io += w_elems * w_isz                     # shared weight block
        io += 2 * _slab_elems(op) * sto_isz       # storage slab in + out
    io += n_timesteps * Ho * Wo * Co * acc_isz    # last layer's frames
    io += 2 * len(ops) * 4                        # counts + drops rows
    for op in ops:                                # per-layer tile bitmaps
        nTx, nTy, _, _ = tile_grid(op.spec.out_shape[0],
                                   op.spec.out_shape[1])
        io += nTx * nTy * 4
    return NetworkWindowPlan(membrane_bytes=membrane, ring_bytes=ring,
                             io_bytes=io)


def effective_fusion(program: LayerProgram, n_timesteps: int,
                     vmem_budget: Optional[int] = None) -> str:
    """The fusion the window step will actually execute.

    ``"fused-network"`` downgrades to ``"fused-window"`` when the
    megakernel's :func:`network_window_plan` exceeds the VMEM scratch
    budget — the single source both :func:`window_step` and the serving
    engines' launch accounting consult, so the counted launches always
    match the executed lowering.
    """
    if program.fusion_policy != FUSED_NETWORK:
        return program.fusion_policy
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    plan = network_window_plan(program, n_timesteps)
    return FUSED_NETWORK if plan.total_bytes <= budget else FUSED_WINDOW


def state_bytes(program: LayerProgram, n_slots: int) -> int:
    """Total membrane storage the serving engine holds resident (bytes)."""
    sto_isz = 1 if program.dtype_policy == INT8_NATIVE else 4
    return sum(_slab_elems(op) for op in program.ops) * n_slots * sto_isz


def window_scratch_bytes(program: LayerProgram, n_timesteps: int,
                         co_blk: int = 128) -> int:
    """Peak per-launch VMEM *scratch* bytes of one window step.

    Per-step kernels carry no scratch (the slab rides as an I/O block);
    a fused-window launch holds one layer's accumulator slab (channel-
    blocked for conv/fc); the fused-network megakernel holds every
    layer's slab plus the boundary ring buffers at once.  This is the
    figure `benchmarks/layer_program.py` reports per policy — the VMEM
    residency each lowering buys.
    """
    fusion = effective_fusion(program, n_timesteps)
    if fusion == PER_STEP:
        return 0
    if fusion == FUSED_NETWORK:
        plan = network_window_plan(program, n_timesteps)
        return plan.membrane_bytes + plan.ring_bytes
    peak = 0
    for op in program.ops:
        Ho, Wo, Co = op.spec.out_shape
        h = op.halo
        cb = Co if op.kind == "pool" else _channel_block(Co, co_blk)
        peak = max(peak, (Ho + 2 * h) * (Wo + 2 * h) * cb * 4)
    return peak


@functools.lru_cache(maxsize=64)
def _net_layers(program: LayerProgram) -> Tuple[NetLayer, ...]:
    """Lower the program's ops into the megakernel's static layer plans."""
    out = []
    for op in program.ops:
        spec = op.spec
        out.append(NetLayer(
            kind=spec.kind, lif=op.lif, halo=op.halo,
            cap=(op.step_capacity if op.index == 0
                 else _ring_capacity(program, op.index)),
            padding=spec.padding if spec.kind == "conv" else 0,
            stride=spec.stride if spec.kind == "pool" else 1,
            in_shape=spec.in_shape))
    return tuple(out)


def _window_step_network(params: Sequence[EConvParams], states, class_counts,
                         ev_xyc, ev_gate, alive, pre_dt, *,
                         program: LayerProgram, co_blk: int = 128,
                         use_pallas: Optional[bool] = None,
                         vmem_budget: Optional[int] = None):
    """The fused-network driver behind :func:`window_step` (ONE launch).

    The whole compiled program — every layer, all T timesteps — runs
    inside a single Pallas launch (`kernels/network_window`): all
    membrane slabs resident in VMEM scratch, inter-layer spikes routed
    through in-kernel event ring buffers, only the last layer's frames
    (the rate-decode input) and the per-layer counters leaving the
    kernel.  Outputs are bitwise equal to the fused-window driver's (the
    retained oracle): the in-kernel routing is `window_common.route_frame`
    — line-for-line :func:`frame_to_events` — and the per-layer chains
    are the per-layer window kernels' exact sequences.

    When :func:`network_window_plan` exceeds the VMEM scratch budget the
    driver warns with the sizing diagnostic and executes the fused-window
    lowering instead (L launches) — same bitwise results, the engines'
    launch accounting follows via :func:`effective_fusion`.
    """
    T = ev_xyc.shape[0]
    if effective_fusion(program, T, vmem_budget) != FUSED_NETWORK:
        plan = network_window_plan(program, T)
        budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
        warnings.warn(
            f"fused-network window needs {plan.total_bytes} bytes of VMEM "
            f"per grid step (membrane {plan.membrane_bytes} + rings "
            f"{plan.ring_bytes} + I/O {plan.io_bytes}) > budget {budget}; "
            f"falling back to the fused-window lowering "
            f"({len(program.ops)} launches per window)")
        return _window_step_fused(params, states, class_counts, ev_xyc,
                                  ev_gate, alive, pre_dt, program=program,
                                  co_blk=co_blk, use_pallas=use_pallas)
    for op, p in zip(program.ops, params):
        check_native_weights(op, p)
    N = class_counts.shape[0]
    states = list(apply_idle_decay(states, pre_dt, program=program))
    # bitmaps come from the timestep-major collector layout (layer coords,
    # pre-transpose, pre-halo-shift) — exactly what seed_site_map expects
    tiles = (window_tile_maps(program, ev_xyc, ev_gate)
             if effective_tile_sparsity(program) else None)
    xyc = jnp.transpose(ev_xyc, (1, 0, 2, 3))    # slot-major for the kernel
    gate = jnp.transpose(ev_gate, (1, 0, 2))
    al = jnp.transpose(alive, (1, 0))
    op0 = program.ops[0]
    if op0.kind == "conv":
        xyc = xyc + jnp.asarray([op0.spec.padding, op0.spec.padding, 0],
                                jnp.int32)
    native = program.dtype_policy == INT8_NATIVE
    v_out, s_last, counts_nl, drops_nl = network_window(
        tuple(states), tuple(p.w for p in params), xyc, gate, al,
        layers=_net_layers(program), native=native, use_pallas=use_pallas,
        tiles=tiles)
    # counters leave the kernel as exact int32; the (L, N) float32 counts
    # contract is an exact cast (values < 2^24), bitwise the fused path's
    counts = counts_nl.astype(jnp.float32).T
    drops = drops_nl.T
    class_counts = class_counts + jnp.sum(
        s_last, axis=(1, 2, 3)).astype(class_counts.dtype)
    return tuple(v_out), class_counts, counts, drops


def window_step(params: Sequence[EConvParams], states, class_counts,
                ev_xyc, ev_gate, alive, pre_dt, *, program: LayerProgram,
                co_blk: int = 128, use_pallas: Optional[bool] = None,
                vmem_budget: Optional[int] = None):
    """Advance every slot through one window of timesteps (jit this).

    The whole-network step the serving engine executes.  The program's
    compiled ``fusion_policy`` picks the lowering (same pattern as
    ``dtype_policy`` — one switch, every entry point honours it):

      * ``"per-step"`` — per timestep the program chain runs layer by
        layer, each layer one slot-batched scatter launch (L×T launches
        per window), with :func:`frame_to_events` routing the FIRE frame
        into the next layer's event bucket on device.  This is the
        bit-exactness oracle for the fused path.
      * ``"fused-window"`` — each layer's full window runs in ONE fused
        Pallas launch (:func:`layer_window`; L launches per window), the
        time loop inside the kernel and the membrane resident in VMEM
        scratch.  Bitwise identical outputs.
      * ``"fused-network"`` — the WHOLE program runs in ONE Pallas launch
        per window (:func:`_window_step_network`): every layer's membrane
        in VMEM scratch at once, inter-layer spikes through in-kernel
        event ring buffers.  Bitwise identical outputs; falls back to
        fused-window (with a warning) when the geometry exceeds
        ``vmem_budget`` (default :data:`DEFAULT_VMEM_BUDGET`) — see
        :func:`effective_fusion`.

    Args:
      states:       tuple of per-layer membrane slabs, each (N, Hp, Wp, C).
      class_counts: (N, n_classes) running rate-decode accumulator.
      ev_xyc:       (W, N, E0, 3) collector output — layer-0 events binned
                    by timestep-within-window, per slot.
      ev_gate:      (W, N, E0) validity gates.
      alive:        (W, N) 1.0 where the slot has a real timestep there.
      pre_dt:       (N,) deferred idle timesteps per slot, applied as one
                    analytic decay before stepping (fused here so a slot
                    re-entering after skipped windows costs no extra
                    dispatch; all-zero for slots with nothing pending).

    Returns new states, class_counts, per-layer per-slot consumed-event
    counts (L, N) and inter-layer overflow drops (L, N) for this window.
    """
    if program.fusion_policy == FUSED_NETWORK:
        return _window_step_network(params, states, class_counts, ev_xyc,
                                    ev_gate, alive, pre_dt, program=program,
                                    co_blk=co_blk, use_pallas=use_pallas,
                                    vmem_budget=vmem_budget)
    if program.fusion_policy == FUSED_WINDOW:
        return _window_step_fused(params, states, class_counts, ev_xyc,
                                  ev_gate, alive, pre_dt, program=program,
                                  co_blk=co_blk, use_pallas=use_pallas)
    L = len(program.ops)
    N = class_counts.shape[0]
    states = apply_idle_decay(states, pre_dt, program=program)

    def one_t(carry, xs_t):
        states, class_counts, counts, drops = carry
        xyc, gate, alive_t = xs_t
        states = list(states)
        s = None
        for op, p in zip(program.ops, params):
            if op.index > 0:
                xyc, gate, n_drop = frame_to_events(s, op.step_capacity)
                drops = drops.at[op.index].add(n_drop)
            counts = counts.at[op.index].add(
                jnp.sum(gate, axis=1).astype(counts.dtype))
            states[op.index], s = layer_timestep(op, p, states[op.index],
                                                 xyc, gate, alive_t, co_blk,
                                                 use_pallas)
        # class counts stay float32 under every policy (integer spikes
        # sum exactly; rate decoding is policy-independent)
        class_counts = class_counts + jnp.sum(
            s, axis=(1, 2)).astype(class_counts.dtype)
        return (tuple(states), class_counts, counts, drops), None

    counts0 = jnp.zeros((L, N), jnp.float32)
    drops0 = jnp.zeros((L, N), jnp.int32)
    (states, class_counts, counts, drops), _ = jax.lax.scan(
        one_t, (tuple(states), class_counts, counts0, drops0),
        (ev_xyc, ev_gate, alive))
    return states, class_counts, counts, drops


# ---------------------------------------------------------------------------
# The single-stream scan driver (explicit events, lazy TLU leak, RST).
# ---------------------------------------------------------------------------

def layer_event_forward(op: LayerOp, params: EConvParams,
                        stream: ev.EventStream, out_capacity: int,
                        n_timesteps: int):
    """Consume an event stream through one LayerOp; emit the output stream.

    Equivalent to `core.econv.dense_forward` on the densified input
    (tested), but performs work proportional to the number of events + the
    number of *active* timestep boundaries — the paper's
    energy-proportionality property, with idle timesteps skipped by the
    lazy TLU leak.

    The lazy timestep skip is exact only for hard resets (a reset neuron
    cannot re-cross the threshold without new input); SNE's datapath resets
    the membrane on fire, so this matches the hardware.

    Under the int8-native policy the scan carries the membrane in the
    int32 accumulator (the whole inference is one resident phase — the
    VMEM-held analogue of the serving path's per-timestep int8 storage);
    the emitted event stream is bitwise identical to the carrier oracle's
    and the returned membrane holds the same integers in int32.
    """
    spec = op.spec
    Ho, Wo, Co = spec.out_shape
    p = op.lif
    if p.reset_mode != "zero":
        raise ValueError("event path requires reset_mode='zero' (hardware "
                         "semantics; lazy TLU skip is exact only then)")
    check_native_weights(op, params)
    n_flat = Ho * Wo * Co
    # Flat coordinate tables for FIRE emission.
    ii = jnp.arange(n_flat, dtype=jnp.int32)
    fx = ii // (Wo * Co)
    fy = (ii // Co) % Wo
    fc = ii % Co

    out0 = ev.EventStream(
        t=jnp.full((out_capacity,), n_timesteps, jnp.int32),
        x=jnp.zeros((out_capacity,), jnp.int32),
        y=jnp.zeros((out_capacity,), jnp.int32),
        c=jnp.zeros((out_capacity,), jnp.int32),
        op=jnp.full((out_capacity,), ev.OP_UPDATE, jnp.int32),
        valid=jnp.zeros((out_capacity,), bool),
    )

    def fire_emit(vp, t_fire, out, cursor, emitted):
        """Finish timestep ``t_fire``: clip, threshold, emit, reset."""
        v_int = clip_state(interior(vp, op.halo), p)
        v_new, s = fire_and_reset(v_int, p)
        vp = write_interior(vp, v_new, op.halo)
        mask = s.reshape(-1) > 0
        k = jnp.cumsum(mask.astype(jnp.int32)) - 1 + cursor
        ok = mask & (k < out_capacity)
        kk = jnp.where(ok, k, out_capacity)  # out-of-range => dropped scatter
        out = ev.EventStream(
            t=out.t.at[kk].set(t_fire, mode="drop"),
            x=out.x.at[kk].set(fx, mode="drop"),
            y=out.y.at[kk].set(fy, mode="drop"),
            c=out.c.at[kk].set(fc, mode="drop"),
            op=out.op,
            valid=out.valid.at[kk].set(True, mode="drop"),
        )
        n = jnp.sum(mask.astype(jnp.int32))
        return vp, out, cursor + n, emitted + n

    def step(carry, e):
        vp, t_cur, out, cursor, emitted, n_upd, n_bnd = carry
        e_t, e_x, e_y, e_c, e_op, e_valid = e
        # Padding slots sort to the tail; clamping their timestep to the
        # last real step (T-1) makes them trigger the final boundary flush
        # while keeping the leak count exactly equal to the dense path's.
        t_evt = jnp.minimum(jnp.where(e_valid, e_t, jnp.int32(n_timesteps)),
                            jnp.int32(n_timesteps - 1))
        crossing = t_evt > t_cur

        def do_boundary(args):
            vp, out, cursor, emitted = args
            vp, out, cursor, emitted = fire_emit(vp, t_cur, out, cursor,
                                                 emitted)
            dt = t_evt - t_cur
            v_int = clip_state(apply_leak(interior(vp, op.halo), p.leak, dt,
                                          p.leak_mode), p)
            vp = write_interior(vp, v_int, op.halo)
            return vp, out, cursor, emitted

        vp, out, cursor, emitted = jax.lax.cond(
            crossing, do_boundary, lambda a: a, (vp, out, cursor, emitted))
        t_cur = jnp.maximum(t_cur, t_evt)
        n_bnd = n_bnd + crossing.astype(jnp.int32)

        # RST_OP: clear every membrane (paper: all clusters activated).
        is_rst = e_valid & (e_op == ev.OP_RST)
        vp = jnp.where(is_rst, jnp.zeros_like(vp), vp)

        # UPDATE_OP: scatter the weight patch (gate zeroes everything else).
        is_upd = e_valid & (e_op == ev.OP_UPDATE)
        gate = is_upd.astype(vp.dtype)
        vp = scatter_event(op, params, vp, e_x, e_y, e_c, gate)
        n_upd = n_upd + is_upd.astype(jnp.int32)
        return (vp, t_cur, out, cursor, emitted, n_upd, n_bnd), None

    vp0 = padded_state(op, (acc_dtype(op) if op.dtype_policy == INT8_NATIVE
                            else params.w.dtype))
    carry0 = (vp0, jnp.int32(0), out0, jnp.int32(0), jnp.int32(0),
              jnp.int32(0), jnp.int32(0))
    xs = (stream.t, stream.x, stream.y, stream.c, stream.op, stream.valid)
    (vp, t_cur, out, cursor, emitted, n_upd, n_bnd), _ = jax.lax.scan(
        step, carry0, xs)
    # Final flush: fire the last accumulated timestep (idempotent if the
    # padding slots already advanced t_cur past the last real event).
    fire_t = jnp.minimum(t_cur, jnp.int32(n_timesteps - 1))
    vp, out, cursor, emitted = fire_emit(vp, fire_t, out, cursor, emitted)
    stats = EConvStats(
        n_update_events=n_upd,
        n_sops=n_upd * spec.updates_per_event(),
        n_out_events=emitted,
        n_dropped=jnp.maximum(emitted - out_capacity, 0),
        n_boundaries=n_bnd,
    )
    return out, interior(vp, op.halo), stats


def run_stream(program: LayerProgram, params: Sequence[EConvParams],
               stream: ev.EventStream, capacities: Sequence[int],
               n_timesteps: int):
    """Chain :func:`layer_event_forward` through the whole program.

    ``capacities[i]`` sizes layer *i*'s output event buffer (the FIFO/DMA
    capacity analogue).  Returns the final output stream plus the per-layer
    stats tuple; `sne_net.event_apply` wraps these into NetworkEventStats.
    """
    if len(capacities) != len(program.ops):
        raise ValueError("need one output capacity per layer")
    stats_all = []
    s = stream
    for op, p, cap in zip(program.ops, params, capacities):
        s, _, st = layer_event_forward(op, p, s, cap, n_timesteps)
        stats_all.append(st)
    return s, tuple(stats_all)


# ---------------------------------------------------------------------------
# Dense differentiable driver — the training twin of the event executors.
# ---------------------------------------------------------------------------

def dense_program_forward(program: LayerProgram,
                          params: Sequence[EConvParams],
                          spikes: jnp.ndarray, train: bool = False,
                          qat: bool = False):
    """Differentiable dense-frame forward over the compiled op chain.

    Runs the layer chain exactly as compiled — ``program.ops`` in order,
    each op's spec and LIF plan — on dense ``(T, H, W, C)`` spike frames:
    one `lax.scan` of `core.lif.lif_step` per op (via
    `core.econv.dense_forward`).  That is the same ``leak -> integrate ->
    clip -> fire -> reset`` boundary arithmetic the event drivers execute
    (:func:`layer_timestep`, :func:`layer_event_forward`), sharing
    `core.lif.apply_leak` / ``state_clip`` / the reset rule verbatim:

      * ``train=False`` — the hard threshold.  On binary spike inputs this
        computes the function the serving :func:`window_step` serves
        (bitwise for integer-domain nets, where both paths do exact
        integer arithmetic in their carriers).
      * ``train=True`` — the fire routes through `core.lif.spike_fn`'s
        custom-VJP fast-sigmoid surrogate so ``jax.grad`` flows (BPTT
        through the scan).  The forward values are identical to
        ``train=False``; only the backward rule differs — the executor's
        forward IS the function the gradients flow through.

    ``qat=True`` fake-quantizes conv/fc weights onto the *layer-shared*
    int4 deployment grid (`core.quant.fake_quant_weights` with
    ``per_channel=False`` — exactly the execution grid
    `core.quant.quantize_net` lowers onto, so the QAT forward equals the
    deployed ``codes * shared_scale`` model bitwise) with straight-through
    gradients; pool layers keep their unit synapses.

    Only the float-carrier policy trains (int8-native storage carries no
    gradients); quantized serving parity is proven by the serving tests.
    Returns ``(out_spikes (T, 1, 1, n_classes), acts)`` like
    `core.sne_net.dense_apply`.
    """
    if program.dtype_policy != F32_CARRIER:
        raise ValueError(
            f"dense_program_forward trains the {F32_CARRIER!r} datapath; "
            f"got a {program.dtype_policy!r} program — train in the "
            f"carrier domain and lower with core.quant.quantize_net")
    if len(params) != len(program.ops):
        raise ValueError("need one params entry per compiled op")
    x = spikes
    acts = []
    for op, p in zip(program.ops, params):
        if qat and op.kind != "pool":
            p = EConvParams(w=fake_quant_weights(p.w, per_channel=False))
        x, _ = dense_forward(p, op.spec, x, train=train)
        acts.append(x)
    return x, acts
