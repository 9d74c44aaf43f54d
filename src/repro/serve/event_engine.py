"""Slot-batched continuous serving of concurrent DVS event streams.

The LM serving engine (`repro.serve.engine`) batches token decode over
fixed slots; this module is its event-domain twin — the missing subsystem
between "one DVS recording at a time" (`core/sne_net.event_apply` over
`core/econv.event_forward`) and a production event-serving system. It
mirrors the SNE macro-architecture (paper §III-D):

  * **slots == engine slices** — a fixed-capacity set of concurrent
    inferences, each owning one batched row of every layer's membrane
    state (static shapes are the XLA constraint, exactly the constraint
    that sized the ASIC's per-slice state memories);
  * **collector** — the host-side stage that merges per-slot event streams
    into padded per-window event batches, reusing the
    ``EventStream`` capacity/overflow semantics from `core/events.py` as
    back-pressure: a (slot, timestep) bucket that exceeds its static
    capacity drops the excess and *counts* it (FIFO overflow), and
    admission blocks when no slot is free (queue back-pressure);
  * **batched step == C-XBAR broadcast** — all active slots advance
    together through one jitted per-window step; *every* layer kind
    scatters all slots' event batches into all slots' membrane slabs in a
    single ``pallas_call`` with a batch grid dimension
    (`kernels.event_conv` / `kernels.event_pool` / `kernels.event_fc`),
    the TPU analogue of the C-XBAR multicasting an event stream across
    parallel engine slices.

Work in the synaptic path is proportional to measured events (the paper's
energy-proportionality), and every completed request carries a telemetry
record mapping its measured event counts through the analytic hardware
model (`serve/telemetry.py`).

Execution semantics: the engine owns no datapath of its own.  At
construction the network is compiled to a layer program
(`core.layer_program.compile_program`) and the jitted per-window step IS
`core.layer_program.window_step` — the same unified
``leak -> scatter(events) -> clip -> fire -> reset`` executor the core
event path (`econv.event_forward`, `sne_net.event_apply`) runs, here over
slot-batched state.  ``dtype_policy`` selects the program's dtype domain:
the default float32 carrier, or ``"int8-native"`` (paper §III-D4) where
the resident membrane slabs are int8, the weights are int8 codes from
`core.quant.quantize_net`, and scatters accumulate in int32 — bitwise
identical results, 4x less resident state and strictly smaller launches.
``fusion_policy`` selects the window lowering: the default
``"fused-window"`` runs each layer's WHOLE window — leak, scatter, clip,
fire, reset for every timestep — in ONE fused Pallas launch
(`kernels/*/..._window` kernels, membrane resident in VMEM scratch), so a
window costs L launches instead of L×window; ``"per-step"`` is the
bitwise-identical oracle lowering with one slot-batched scatter launch
per layer per timestep.  Either way inter-layer event routing
(`layer_program.frame_to_events`) stays on device — so engine outputs
match the dense path (`sne_net.dense_apply`) up to float summation order,
and each scatter is bit-for-bit its single-stream kernel per slab.

**Window-level idle skip (the TLU trick at serving scale, §III-D4.iii).**
With ``idle_skip=True`` (default, requires hard resets) the collector also
reports a per-slot activity mask for the window.  A (slot, window) pair
with zero input events provably does zero work anywhere in the network —
post-reset membranes sit below threshold and ``leak >= 0`` only shrinks
them, so layer 0 emits nothing, hence layer 1 sees nothing, and so on.
Such slots bypass the batched step entirely: their leak is *deferred* as a
per-slot idle-step counter and applied analytically (`core.lif.idle_decay`)
in one shot right before the slot next participates, exactly the paper's
time-of-last-update bookkeeping.  Active slots are *compacted* — gathered
into a dense batch (slot axis bucketed to powers of two, event axis
trimmed to the window's occupancy) — before the single Pallas launch, and
results are scattered back.  Active-slot results are bit-for-bit those of
the dense full-batch path; an all-idle window launches no kernels at all.
"""
from __future__ import annotations

import dataclasses
import time
from functools import lru_cache, partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events as ev
from repro.core.econv import EConvParams
from repro.core.engine import SneConfig
from repro.core.layer_program import (FUSED_NETWORK, FUSED_WINDOW, LayerOp,
                                      check_native_weights, compile_program,
                                      effective_fusion, state_dtype,
                                      window_step)
from repro.core.layer_program import \
    default_step_capacities as _program_step_capacities
from repro.core.lif import supports_idle_skip
from repro.core.policies import (BACKEND_LOCAL, BACKEND_MESH,
                                 ExecutionPolicy, resolve_policy)
from repro.core.sne_net import SNNSpec
from repro.serve.spans import Span
from repro.serve.telemetry import RequestTelemetry, request_telemetry


@dataclasses.dataclass
class EventRequest:
    """One inference over an event recording (the serving unit of work)."""

    uid: int
    stream: ev.EventStream          # time-sorted UPDATE events
    n_timesteps: int
    dropped_at_ingest: int = 0      # overflow counted when the stream was built
    # filled on completion:
    class_counts: Optional[np.ndarray] = None
    prediction: Optional[int] = None
    telemetry: Optional[RequestTelemetry] = None
    done: bool = False
    # memo so run()'s up-front pass and try_admit don't scan the stream twice
    _validated: bool = dataclasses.field(default=False, repr=False)

    @staticmethod
    def from_dense(uid: int, spikes: jnp.ndarray,
                   capacity: Optional[int] = None) -> "EventRequest":
        """Build a request from a dense ``(T, H, W, C)`` spike tensor."""
        if capacity is None:
            n = int(jnp.sum((spikes != 0).astype(jnp.int32)))
            capacity = max(8, ((n + 7) // 8) * 8)
        stream = ev.dense_to_events(spikes, capacity)
        dropped = int(ev.overflow_count(spikes, capacity))
        return EventRequest(uid=uid, stream=stream,
                            n_timesteps=int(spikes.shape[0]),
                            dropped_at_ingest=dropped)


@dataclasses.dataclass
class CollectedWindow:
    """One window's host-side collector output, pre-launch.

    The unit the streaming runtime pipelines: collecting window N+1 (pure
    host work — numpy binning, no device sync) can overlap the device
    computing window N, because everything here comes from host state.
    ``part_idx`` is the participating slot set (active slots that still
    have timesteps to serve; under the synchronous ``step()`` this equals
    the active set, but the streaming runtime keeps finished slots
    resident until their last window retires).
    """

    xyc: np.ndarray        # (W, N, E0, 3) int32 collector bins
    gate: np.ndarray       # (W, N, E0) f32 validity gates
    alive: np.ndarray      # (W, N) f32 real-timestep mask
    n_win_ev: np.ndarray   # (N,) int64 raw events per slot this window
    max_bucket: int        # largest (slot, timestep) bucket fill
    part_idx: np.ndarray   # participating slot indices
    seq: int               # the engine's window number (``n_collected``)


@dataclasses.dataclass
class InflightWindow:
    """A dispatched-but-not-retired window step (device work in flight).

    ``counts``/``drops`` are device futures (JAX async dispatch); the
    numpy conversion that forces the device sync is deferred to
    :meth:`EventServeEngine._retire_phase`, which is what lets the
    streaming runtime collect the next window while this one computes.
    """

    idx: np.ndarray        # dense (launched) slot indices; batch row i
                           # holds slot idx[i], the rest are the tail
    counts: jnp.ndarray    # (L, batch) per-layer consumed events — future
    drops: jnp.ndarray     # (L, batch) inter-layer overflow — future
    seq: int               # the collected window's number


def default_step_capacities(spec: SNNSpec, activity: float = 0.25,
                            slack: float = 4.0,
                            align: int = 8) -> List[int]:
    """Per-layer *per-timestep* input-event capacities (collector + FIFOs).

    Unlike `sne_net.default_capacities` (whole-inference buffers), these
    size one timestep's bucket.  Delegates to the single-sourced heuristic
    in `core.layer_program` (`layer_step_capacity`) — the same rule
    `compile_program` bakes into each LayerOp — so core and serving
    capacity sizing cannot drift.
    """
    return _program_step_capacities(spec, activity, slack, align)


@lru_cache(maxsize=32)
def event_bucket_ladder(cap: int) -> Tuple[int, ...]:
    """The event-axis capacity ladder: {8, 12, 16, 24, 32, 48, ...} ≤ cap.

    Power-of-two buckets waste up to 2x padding right below each rung;
    interleaving the 1.5x midpoints halves the worst case (≤ 1.33x) while
    keeping the rung count O(log cap) — the bounded jit-retrace set the
    fixed buckets were chosen for.  ``cap`` itself always terminates the
    ladder, so no occupancy is ever rounded past the collector capacity.
    """
    vals = []
    v = 8
    while v < cap:
        vals.append(v)
        if v + (v >> 1) < cap:
            vals.append(v + (v >> 1))
        v <<= 1
    vals.append(cap)
    return tuple(vals)


def event_bucket(n: int, cap: int) -> int:
    """Smallest ladder rung >= ``n`` (the adaptive per-window ``Eb``).

    The SINGLE source for event-axis trimming — both the local engine's
    `_launch_window` and the mesh engine's `_launch_global` call this, so
    their launch geometries (and jit caches) cannot drift apart.
    """
    for v in event_bucket_ladder(cap):
        if v >= n:
            return v
    return cap


def batched_window_step(params, states, class_counts, sel, ev_xyc, ev_gate,
                        alive, pre_dt, **kw):
    """One window over a slot batch gathered from the resident slabs.

    ``sel`` is ``(2, Ab)`` int32: row 0 the slot each batch position
    reads (``gidx``), row 1 the slot it writes back (``sidx``).  Dummy
    positions read a real slot and write to the out-of-range index ``N``,
    which ``mode="drop"`` discards, so no slot is ever scattered twice.
    Returns the full ``(N, ...)`` slabs and class counts with the batch's
    rows written back, plus ``window_step``'s per-layer counts and drops
    in batch order.  ``kw`` goes to
    `core.layer_program.window_step` (``program`` and its lowering
    options).
    """
    gidx, sidx = sel[0], sel[1]
    out, cc, counts, drops = window_step(
        params, tuple(v[gidx] for v in states), class_counts[gidx],
        ev_xyc, ev_gate, alive, pre_dt, **kw)
    states = tuple(v.at[sidx].set(o, mode="drop")
                   for v, o in zip(states, out))
    class_counts = class_counts.at[sidx].set(cc, mode="drop")
    return states, class_counts, counts, drops


class EventServeEngine:
    """Continuous slot-batched inference over concurrent event streams."""

    def __new__(cls, *args, **kwargs):
        """Dispatch construction on ``policy.backend``.

        The Ludwig-style zero-code-change knob: constructing an
        `EventServeEngine` with ``policy=ExecutionPolicy(backend="mesh")``
        (or the legacy ``backend="mesh"`` kwarg) returns a
        `repro.serve.mesh_engine.MeshEventServeEngine` — same constructor
        args, same serving surface, slot axis sharded across the device
        mesh.  ``"local"`` (the default) stays this class, the bitwise
        parity oracle.
        """
        if cls is EventServeEngine:
            pol = kwargs.get("policy")
            backend = (pol.backend if isinstance(pol, ExecutionPolicy)
                       else kwargs.get("backend"))
            if backend == BACKEND_MESH:
                from repro.serve.mesh_engine import MeshEventServeEngine
                return super().__new__(MeshEventServeEngine)
        return super().__new__(cls)

    def __init__(self, spec: SNNSpec, params: Sequence[EConvParams],
                 n_slots: int, window: int = 4,
                 step_capacities: Optional[Sequence[int]] = None,
                 sne_cfg: Optional[SneConfig] = None,
                 n_parallel_slices: Optional[int] = None,
                 co_blk: int = 128, use_pallas: Optional[bool] = None,
                 idle_skip: Optional[bool] = None,
                 dtype_policy: Optional[str] = None,
                 fusion_policy: Optional[str] = None,
                 donate_buffers: bool = False,
                 policy: Optional[ExecutionPolicy] = None,
                 backend: Optional[str] = None,
                 device: Optional[jax.Device] = None):
        """Compile the network into the engine's jitted per-window step.

        ``policy`` (an `repro.core.policies.ExecutionPolicy`) selects the
        execution configuration in one value: the datapath dtype domain,
        the window lowering (the default ``"fused-window"`` runs each
        layer's whole window in one Pallas launch, L per window;
        ``"per-step"`` is the bitwise-identical oracle, L×window), the
        window-level idle skip, and the backend (``"local"`` here;
        ``"mesh"`` dispatches to `serve.mesh_engine.MeshEventServeEngine`
        via ``__new__``).  The legacy ``dtype_policy=`` /
        ``fusion_policy=`` / ``idle_skip=`` / ``backend=`` kwargs keep
        working through the deprecation shim (warns once per process).
        ``donate_buffers`` donates the membrane slabs and class-count
        accumulator to each window step (``jax.jit`` ``donate_argnums``)
        so XLA reuses their device buffers in place — the resident slot
        state never round-trips or reallocates between windows.  Results
        are bitwise unchanged; the streaming runtime turns this on.
        ``device`` commits the engine's weights, slot state and every
        per-window input to one device (the mesh backend's shards); by
        default they live on JAX's default device.
        """
        if n_slots < 1 or window < 1:
            raise ValueError("need n_slots >= 1 and window >= 1")
        # fail fast — not inside _finish after a request was fully served
        if n_parallel_slices is not None and n_parallel_slices < 1:
            raise ValueError(f"n_parallel_slices={n_parallel_slices} < 1")
        pol = resolve_policy(
            "serve.event_engine.EventServeEngine", policy,
            default=ExecutionPolicy(), dtype_policy=dtype_policy,
            fusion_policy=fusion_policy, idle_skip=idle_skip,
            backend=backend)
        if pol.backend != BACKEND_LOCAL:
            # unreachable through EventServeEngine(...) — __new__ routes
            # mesh policies to the subclass — but loud for direct callers
            raise ValueError(f"EventServeEngine is the {BACKEND_LOCAL!r} "
                             f"backend; policy selects {pol.backend!r}")
        self.policy = pol
        self.spec = spec
        self.device = device
        self.params = jax.device_put(list(params), device)
        self.N = n_slots
        self.W = window
        self.dtype_policy = pol.dtype_policy
        self.fusion_policy = pol.fusion_policy
        # compile the network once; the program is the engine's datapath
        # (compile also validates the spec against both policies)
        self.program = compile_program(
            spec, step_capacities=(tuple(step_capacities)
                                   if step_capacities is not None else None),
            policy=dataclasses.replace(pol, backend=BACKEND_LOCAL))
        # fail at construction, not at first trace: the native datapath
        # executes integer codes (same single-sourced check the executor
        # applies per scatter — see layer_program.check_native_weights)
        for op, p in zip(self.program.ops, self.params):
            check_native_weights(op, p)
        self.caps = self.program.step_capacities
        self.cfg = sne_cfg or SneConfig()
        self.n_parallel_slices = n_parallel_slices
        # the lazy skip is only exact for hard resets (see core.lif);
        # soft-reset networks silently fall back to dense stepping
        self.idle_skip = pol.idle_skip and all(
            supports_idle_skip(l.lif) for l in spec.layers)
        L = len(spec.layers)

        # host-side slot bookkeeping (the collector's view)
        self.slot_req: List[Optional[EventRequest]] = [None] * n_slots
        self.active = np.zeros((n_slots,), bool)
        self.tau = np.zeros((n_slots,), np.int64)        # local time cursor
        self.ptr = np.zeros((n_slots,), np.int64)        # event array cursor
        self._ev: List[Optional[np.ndarray]] = [None] * n_slots  # (M,4) t,x,y,c
        self.acc_counts = np.zeros((L, n_slots), np.float64)
        self.acc_drops = np.zeros((L, n_slots), np.float64)
        # engine-lifetime inter-layer drop totals per layer boundary (row l
        # = events dropped routing INTO layer l; row 0 is always 0 — the
        # collector counts input drops).  Unlike ``acc_drops`` this is
        # never reset on slot reuse, so it feeds engine-level telemetry.
        self.total_drops = np.zeros((L,), np.float64)
        self.collector_drops = np.zeros((n_slots,), np.int64)  # capacity
        self.oor_drops = np.zeros((n_slots,), np.int64)        # out-of-range
        self.windows = np.zeros((n_slots,), np.int64)
        self.admit_time = np.zeros((n_slots,), np.float64)
        # idle-skip bookkeeping: deferred leak steps + per-slot accounting
        self.pending_dt = np.zeros((n_slots,), np.int64)
        self.dense_ts = np.zeros((n_slots,), np.int64)
        self.skipped_windows = np.zeros((n_slots,), np.int64)
        self.stats = {"windows": 0, "admitted": 0, "completed": 0,
                      "evicted": 0,
                      "collector_dropped": 0, "out_of_range_dropped": 0,
                      "step_calls": 0, "kernel_launches": 0,
                      # window steps whose slot batch is not the identity
                      # (fewer slots than N): slot compaction engaged
                      "compacted_windows": 0,
                      "dense_slot_windows": 0, "skipped_slot_windows": 0,
                      "leak_flushes": 0,
                      # padding-waste accounting: real events collected vs
                      # the padded event-slot footprint the launches moved
                      # (ladder Eb), the pow2 counterfactual the ladder
                      # replaced, and the measured schedule bytes shipped
                      "collected_events": 0, "launched_events": 0,
                      "padded_event_slots": 0, "padded_event_slots_pow2": 0,
                      "launch_bytes": 0,
                      # every host byte handed to the device, counted at
                      # the put itself (`_put`)
                      "h2d_bytes": 0}
        # host seconds per span name (`repro.serve.spans`); a streaming
        # runtime reports this dict as its ``phase_s``
        self.phase_s: Dict[str, float] = {}
        self.n_collected = 0     # windows collected; the next one's number

        # zeroed slot state on the device (its bytes count in h2d_bytes)
        self.states = tuple(self._zero_state(op) for op in self.program.ops)
        self.class_counts = self._put(
            np.zeros((n_slots, spec.n_classes), np.float32))

        # histogram of per-(slot, timestep) bucket occupancy: bin 0 holds
        # empty buckets, bin b>0 holds fills whose power-of-two ceiling is
        # 2^(b-1) — the measured baseline for adaptive event-capacity
        # bucketing (every bucket is padded to the window's Eb).  Sized
        # from the collector capacity: the largest possible fill is
        # caps[0], whose bin is (caps[0]-1).bit_length()+1 < bit_length+2.
        self.bucket_fill_hist = np.zeros(
            (int(self.caps[0]).bit_length() + 2,), np.int64)

        # the jitted per-window program: the slot gather, the unified
        # program executor and the scatter-back in ONE dispatch, compacted
        # or not (a full in-order batch takes the resident identity index)
        step_fn = partial(batched_window_step, program=self.program,
                          co_blk=co_blk, use_pallas=use_pallas)
        self._step = jax.jit(step_fn, donate_argnums=(1, 2)
                             if donate_buffers else ())
        ident = np.arange(n_slots, dtype=np.int32)
        self._identity_sel = self._put(np.stack([ident, ident]))

        # slot teardown fused into one dispatch: zeroing every membrane
        # slab row plus the class-count row and reading the finished
        # counts back costs one launch here, vs one eager scatter per
        # state tensor per finish (which dominates host time at high
        # request turnover)
        def _reset_fn(states, cc, slot):
            row = cc[slot]
            states = tuple(v.at[slot].set(jnp.zeros((), v.dtype))
                           for v in states)
            return states, cc.at[slot].set(0.0), row
        self._reset = jax.jit(_reset_fn)

    # --- helpers -----------------------------------------------------------

    def _put(self, x: np.ndarray) -> jnp.ndarray:
        """Host array -> the engine's device (JAX's default if unset),
        counted in ``stats["h2d_bytes"]``."""
        self.stats["h2d_bytes"] += x.nbytes
        return jax.device_put(x, self.device)

    def _zero_state(self, op: LayerOp) -> jnp.ndarray:
        Ho, Wo, Co = op.spec.out_shape
        h = op.halo
        # storage dtype follows the program's dtype policy: float32
        # carrier, or int8 resident membranes on the native path (4x less
        # slot state held between windows)
        return self._put(np.zeros((self.N, Ho + 2 * h, Wo + 2 * h, Co),
                                  state_dtype(op)))

    def _reset_slot_state(self, slot: int) -> jnp.ndarray:
        self.states, self.class_counts, row = self._reset(
            self.states, self.class_counts, self._put(np.int32(slot)))
        return row

    @property
    def n_active(self) -> int:
        """Number of slots currently holding an admitted request."""
        return int(self.active.sum())

    @property
    def n_free(self) -> int:
        """Number of slots available for admission."""
        return self.N - self.n_active

    # --- admission (queue back-pressure) -----------------------------------

    def validate_request(self, req: EventRequest) -> None:
        """Raise if a request can never be served (checked pre-admission)."""
        if req._validated:
            return
        if req.n_timesteps < 1:
            raise ValueError(f"request {req.uid}: n_timesteps < 1")
        s = req.stream
        n_other_op = int(np.sum(np.asarray(s.valid)
                                & (np.asarray(s.op) != ev.OP_UPDATE)))
        if n_other_op:
            # the batched window step has no RST/FIRE datapath; refusing is
            # the loud alternative to silently diverging from event_forward
            raise ValueError(
                f"request {req.uid}: stream contains {n_other_op} valid "
                f"non-UPDATE events (OP_RST/OP_FIRE); the serving engine "
                f"supports UPDATE-only streams — run such streams through "
                f"core.sne_net.event_apply instead")
        req._validated = True

    def try_admit(self, req: EventRequest,
                  slot: Optional[int] = None) -> bool:
        """Admit into a free slot; False when the engine is full.

        The free-slot check runs first so a full engine answers False
        without rescanning the head-of-queue stream every window.
        ``slot`` pins the admission to a specific free slot (the
        streaming runtime's slot-policy hook); by default the lowest
        free slot is taken.
        """
        free = np.nonzero(~self.active)[0]
        if len(free) == 0:
            return False
        if slot is None:
            slot = int(free[0])
        elif self.active[slot]:
            raise ValueError(f"slot {slot} is occupied")
        self.validate_request(req)
        slot = int(slot)
        s = req.stream
        keep = np.asarray(s.valid) & (np.asarray(s.op) == ev.OP_UPDATE)
        arr = np.stack([np.asarray(s.t)[keep], np.asarray(s.x)[keep],
                        np.asarray(s.y)[keep], np.asarray(s.c)[keep]],
                       axis=1).astype(np.int64)
        arr = arr[np.argsort(arr[:, 0], kind="stable")]  # collector sort
        H, W, C = self.spec.in_shape
        in_range = ((arr[:, 1] >= 0) & (arr[:, 1] < H)
                    & (arr[:, 2] >= 0) & (arr[:, 2] < W)
                    & (arr[:, 3] >= 0) & (arr[:, 3] < C)
                    & (arr[:, 0] >= 0) & (arr[:, 0] < req.n_timesteps))
        self._ev[slot] = arr[in_range]
        self.slot_req[slot] = req
        self.active[slot] = True
        self.tau[slot] = 0
        self.ptr[slot] = 0
        self.acc_counts[:, slot] = 0.0
        self.acc_drops[:, slot] = 0.0
        # out-of-range events are a data-quality loss, not back-pressure —
        # kept distinct from collector capacity drops so operators tuning
        # step_capacities see only what capacity can actually fix
        n_oor = int(np.sum(~in_range))
        self.collector_drops[slot] = 0
        self.oor_drops[slot] = n_oor
        self.stats["out_of_range_dropped"] += n_oor
        self.windows[slot] = 0
        self.pending_dt[slot] = 0
        self.dense_ts[slot] = 0
        self.skipped_windows[slot] = 0
        self.admit_time[slot] = time.time()
        # slot state is already zero: engines start zeroed and _finish
        # re-zeroes on release, so admission needs no device writes
        self.stats["admitted"] += 1
        return True

    # --- the collector ------------------------------------------------------

    def _participating(self) -> np.ndarray:
        """Active slots that still have timesteps to serve.

        Under the synchronous :meth:`step` this is exactly the active
        set (finished slots are released within the same step); the
        streaming runtime keeps a finished slot resident — active but
        no longer participating — until the window that completed it
        retires.
        """
        return np.asarray(
            [s for s in np.nonzero(self.active)[0]
             if self.tau[s] < self.slot_req[s].n_timesteps], np.int64)

    def _collect_phase(self) -> Optional[CollectedWindow]:
        """Collect one window of host-side work, or None if nothing to do.

        Pure host work on host state — safe to run while a previously
        launched window is still computing on device (the streaming
        runtime's overlap point).
        """
        part_idx = self._participating()
        if len(part_idx) == 0:
            return None
        xyc, gate, alive, n_win_ev, max_bucket = \
            self._collect_window(part_idx)
        self.n_collected += 1
        return CollectedWindow(xyc=xyc, gate=gate, alive=alive,
                               n_win_ev=n_win_ev, max_bucket=max_bucket,
                               part_idx=part_idx, seq=self.n_collected - 1)

    def _collect_window(self, part_idx: np.ndarray):
        """Bin each participating slot's next ``W`` timesteps of events.

        Returns numpy ``(ev_xyc (W,N,E0,3) int32, gate (W,N,E0) f32,
        alive (W,N) f32, n_win_ev (N,) int64, max_bucket int)`` —
        ``n_win_ev`` is each slot's raw event count in this window (the
        idle-skip activity mask: 0 means the slot provably does no work),
        ``max_bucket`` the largest single (slot, timestep) bucket fill
        (the event-axis compaction bound). A bucket holds at most
        ``caps[0]`` events; the excess is dropped and counted (EventStream
        overflow semantics — the serving-side FIFO back-pressure).
        """
        W, N, E0 = self.W, self.N, self.caps[0]
        xyc = np.zeros((W, N, E0, 3), np.int32)
        gate = np.zeros((W, N, E0), np.float32)
        alive = np.zeros((W, N), np.float32)
        n_win_ev = np.zeros((N,), np.int64)
        max_bucket = 0
        for slot in part_idx:
            req = self.slot_req[slot]
            arr = self._ev[slot]
            t0 = self.tau[slot]
            n_alive = min(self.W, req.n_timesteps - t0)
            alive[:n_alive, slot] = 1.0
            p = self.ptr[slot]
            # arr is time-sorted (try_admit), so window and per-timestep
            # boundaries are binary searches, not Python scans.
            end = p + int(np.searchsorted(arr[p:, 0], t0 + n_alive, "left"))
            win = arr[p:end]
            self.ptr[slot] = end
            n_win_ev[slot] = end - p
            bounds = np.searchsorted(win[:, 0],
                                     np.arange(t0, t0 + n_alive + 1))
            Hi, Wi, Ci = self.spec.in_shape
            for dt in range(n_alive):
                rows = win[bounds[dt]:bounds[dt + 1]]
                if len(rows) > E0:
                    dropped = len(rows) - E0
                    self.collector_drops[slot] += dropped
                    self.stats["collector_dropped"] += dropped
                    # drop by the same deterministic priority the on-device
                    # router applies (frame_to_events / route_frame keep the
                    # lowest row-major flat site indices), NOT by arrival
                    # order — so which events survive an overfull timestep
                    # does not depend on ingest ordering.  Survivors stay
                    # in arrival order (stable sort + re-sort of positions)
                    # so the in-bucket accumulation order is untouched.
                    key = (rows[:, 1] * Wi + rows[:, 2]) * Ci + rows[:, 3]
                    keep = np.argsort(key, kind="stable")[:E0]
                    keep.sort()
                    rows = rows[keep]
                k = len(rows)
                max_bucket = max(max_bucket, k)
                # padding-waste baseline: bin 0 = empty bucket, bin b>0 =
                # occupancy whose power-of-two ceiling is 2^(b-1) (clamped
                # into the caps[0]-derived histogram)
                b = 0 if k == 0 else (k - 1).bit_length() + 1
                self.bucket_fill_hist[
                    min(b, len(self.bucket_fill_hist) - 1)] += 1
                if k:
                    xyc[dt, slot, :k, 0] = rows[:, 1]
                    xyc[dt, slot, :k, 1] = rows[:, 2]
                    xyc[dt, slot, :k, 2] = rows[:, 3]
                    gate[dt, slot, :k] = 1.0
            self.stats["collected_events"] += int(n_win_ev[slot])
        return xyc, gate, alive, n_win_ev, max_bucket

    # --- stepping -----------------------------------------------------------

    def step(self) -> int:
        """Advance all active slots one window; returns #active before.

        With ``idle_skip`` on, slots whose window carries zero input events
        never reach the batched step: their leak is deferred (TLU) and the
        remaining slots are compacted before the kernel launch. A window
        in which *every* resident slot is idle launches nothing at all.

        This is the synchronous composition of the pipeline phases the
        streaming runtime overlaps: collect -> launch -> retire -> finish,
        back to back.  It is the parity oracle for the streaming path.
        """
        n_active = self.n_active
        if n_active == 0:
            return 0
        col = self._collect_phase()
        if col is None:          # cannot happen under pure-sync stepping
            return n_active
        inflight, finished = self._launch_phase(col)
        if inflight is not None:
            self._retire_phase(inflight)
        for slot in finished:
            self._finish(slot)
        return n_active

    def _launch_phase(self, col: CollectedWindow
                      ) -> Tuple[Optional[InflightWindow], List[int]]:
        """Dispatch one collected window; advance host time bookkeeping.

        Idle-skip selection, compaction, and the async device dispatch —
        everything except the blocking numpy accounting, which
        :meth:`_retire_phase` applies.  Returns the in-flight record
        (None when every participating slot was idle-skipped) and the
        slots whose request completed with this window; callers must
        :meth:`_finish` those only after the window is retired.
        """
        dense_idx = self._select_dense(col)
        inflight = None
        if len(dense_idx):
            inflight = self._launch_window(dense_idx, col.xyc, col.gate,
                                           col.alive, col.max_bucket,
                                           col.seq)
        return inflight, self._account_window(col, dense_idx)

    def _select_dense(self, col: CollectedWindow) -> np.ndarray:
        """Participating slots that must actually launch this window.

        With ``idle_skip`` on, a slot whose window carries zero input
        events provably does no work and is deferred instead of launched;
        the mesh backend applies this selection per shard, so one shard's
        dense window never forces a launch for another's idle slots.
        """
        act_idx = col.part_idx
        if self.idle_skip:
            return act_idx[col.n_win_ev[act_idx] > 0]
        return act_idx

    def _account_window(self, col: CollectedWindow,
                        dense_idx: np.ndarray) -> List[int]:
        """Post-dispatch host bookkeeping for one collected window.

        Defers idle slots' leak analytically, advances every
        participating slot's time cursor, and returns the slots whose
        request completed with this window (shared verbatim by the mesh
        backend, so local and mesh time/skip accounting cannot drift).
        """
        act_idx = col.part_idx
        for slot in act_idx:
            if slot not in dense_idx:
                # provably-idle window: defer its leak steps analytically
                self.pending_dt[slot] += int(col.alive[:, slot].sum())
                self.skipped_windows[slot] += 1
        self.stats["dense_slot_windows"] += len(dense_idx)
        self.stats["skipped_slot_windows"] += len(act_idx) - len(dense_idx)
        self.stats["windows"] += 1
        finished = []
        for slot in act_idx:
            self.tau[slot] += min(self.W,
                                  self.slot_req[slot].n_timesteps
                                  - self.tau[slot])
            self.windows[slot] += 1
            if self.tau[slot] >= self.slot_req[slot].n_timesteps:
                finished.append(int(slot))
        return finished

    @staticmethod
    def _bucket(n: int, cap: int) -> int:
        """Round up to a power of two (capped) — bounds jit retraces."""
        return min(1 << max(n - 1, 0).bit_length(), cap)

    def _launch_window(self, idx: np.ndarray, xyc: np.ndarray,
                       gate: np.ndarray, alive: np.ndarray,
                       max_bucket: int, seq: int) -> InflightWindow:
        """Compact the active slots and dispatch the batched window step.

        Batch row ``i < len(idx)`` carries slot ``idx[i]``; the tail rows
        are gated off, frozen and dropped at scatter-back.  Without
        ``idle_skip`` every slot rides along (the non-participating ones
        fill the tail) on the full event axis — the dense reference path
        the skip path is tested bit-for-bit against.

        The only device work here is the input puts and ONE call of the
        jitted ``_step``, which gathers the batch from the resident slabs
        and scatters it back itself; a full in-order batch passes the
        resident identity index, so it is the same program with no index
        put.  The dispatch is asynchronous: the returned record carries
        the per-window count/drop futures, and the membrane slabs /
        class-count accumulators are replaced by their post-window
        futures immediately (with ``donate_buffers`` the old buffers are
        donated to the step, so slab state never round-trips).  Nothing
        here blocks on the device; :meth:`_retire_phase` does.
        """
        A = len(idx)
        if self.idle_skip:
            # slot-axis compaction: power-of-two bucket, dummies mirror
            # slot 0
            Ab = self._bucket(A, self.N)
            tail = np.zeros((Ab - A,), np.int64)
            # event-axis compaction: trim to this window's occupancy on
            # the adaptive ladder (pow2 kept as the waste counterfactual)
            Eb = event_bucket(max_bucket, self.caps[0])
            Eb_pow2 = self._bucket(max(max_bucket, 8), self.caps[0])
        else:
            # dense: the slots not in this window (zero gate and alive
            # from the collector) fill the tail
            Ab = self.N
            tail = np.setdiff1d(np.arange(self.N), idx)
            Eb = Eb_pow2 = self.caps[0]
        gidx = np.concatenate([idx, tail])
        # deferred decay for slots (re)entering the dense path, fused into
        # the window step
        pre = np.zeros((Ab,), np.int64)
        if self.idle_skip and self.pending_dt[idx].any():
            pre[:A] = self.pending_dt[idx]
            self.pending_dt[idx] = 0
            self.stats["leak_flushes"] += 1
        # fancy indexing copies, so masking the tail leaves the
        # collector's arrays alone
        xyc_w = xyc[:, gidx, :Eb]
        gate_w = gate[:, gidx, :Eb]
        alive_w = alive[:, gidx]
        gate_w[:, A:] = 0.0
        alive_w[:, A:] = 0.0
        if np.array_equal(idx, np.arange(self.N)):
            sel = self._identity_sel
        else:
            sidx = np.concatenate([idx, np.full((Ab - A,), self.N)])
            sel = self._put(np.stack([gidx, sidx]).astype(np.int32))
            self.stats["compacted_windows"] += 1
        self.states, self.class_counts, counts, drops = self._step(
            self.params, self.states, self.class_counts, sel,
            self._put(xyc_w), self._put(gate_w), self._put(alive_w),
            self._put(pre))
        self.dense_ts[idx] += alive[:, idx].sum(axis=0).astype(np.int64)
        self.stats["step_calls"] += 1
        self.stats["launched_events"] += int(np.sum(gate_w))
        self.stats["padded_event_slots"] += self.W * Ab * Eb
        self.stats["padded_event_slots_pow2"] += self.W * Ab * Eb_pow2
        self.stats["launch_bytes"] += (xyc_w.nbytes + gate_w.nbytes
                                       + alive_w.nbytes)
        # fused-network: ONE launch for the whole window (or per-layer
        # fused-window launches when the VMEM budget forced a fallback —
        # effective_fusion is the same predicate the driver uses);
        # fused-window: ONE launch per layer per window; per-step: one
        # slot-batched scatter launch per layer per timestep
        fusion = effective_fusion(self.program, self.W)
        if fusion == FUSED_NETWORK:
            self.stats["kernel_launches"] += 1
        elif fusion == FUSED_WINDOW:
            self.stats["kernel_launches"] += len(self.program.ops)
        else:
            self.stats["kernel_launches"] += self.W * len(self.program.ops)
        return InflightWindow(idx=idx, counts=counts, drops=drops, seq=seq)

    def _retire_phase(self, w: InflightWindow) -> None:
        """Block on one in-flight window and apply its numpy accounting.

        The only phase that synchronises with the device.  Per-request
        event/drop accumulators become valid for ``w.idx`` slots here —
        which is why a finished slot may only be released
        (:meth:`_finish`) after its last window retires.
        """
        with Span(self.phase_s, "serve.retire.wait", win=w.seq):
            counts_np = np.asarray(w.counts, np.float64)
            drops_np = np.asarray(w.drops, np.float64)
        A = len(w.idx)
        self.acc_counts[:, w.idx] += counts_np[:, :A]
        self.acc_drops[:, w.idx] += drops_np[:, :A]
        self.total_drops += drops_np[:, :A].sum(axis=1)

    def inter_layer_drops(self) -> dict:
        """Engine-lifetime ring/capacity drop totals per layer boundary.

        Row ``l`` counts events dropped while routing INTO layer ``l``
        across every retired window of every request (unlike the
        per-request ``inter_layer_dropped`` telemetry, this survives slot
        reuse).  Row 0 is always 0 — input-side drops are counted by the
        collector (``collector_dropped`` / ``out_of_range_dropped``).
        """
        return {
            "inter_layer_dropped": [float(d) for d in self.total_drops],
            "inter_layer_dropped_total": float(self.total_drops.sum()),
            "collector_dropped": self.stats["collector_dropped"],
            "out_of_range_dropped": self.stats["out_of_range_dropped"],
        }

    def padding_waste(self) -> dict:
        """Padded-vs-real event accounting for the capacity buckets.

        ``padded_event_slots`` is the event-axis footprint the launches
        actually moved (every (slot, timestep) bucket padded to the
        window's adaptive ladder ``Eb`` — `event_bucket`),
        ``padded_event_slots_pow2`` the counterfactual footprint under
        the old power-of-two-only sizing, ``launched_events`` the gated
        real events inside it, ``launch_bytes`` the measured collector
        schedule bytes shipped to the device, and ``bucket_fill_hist``
        the occupancy histogram (bin 0 = empty bucket; bin b>0 = fills
        with power-of-two ceiling ``2**(b-1)``).
        ``padding_waste_improvement`` is pow2-waste / ladder-waste
        (>= 1.0 whenever the ladder helped; 1.0 when every window
        happened to land on a power-of-two rung).
        """
        padded = self.stats["padded_event_slots"]
        pow2 = self.stats["padded_event_slots_pow2"]
        real = self.stats["launched_events"]
        hist = self.bucket_fill_hist
        last = int(np.nonzero(hist)[0].max()) + 1 if hist.any() else 0
        return {
            "collected_events": self.stats["collected_events"],
            "launched_events": real,
            "padded_event_slots": padded,
            "padded_event_slots_pow2": pow2,
            "padding_waste_ratio": padded / real if real else float("inf"),
            "padding_waste_ratio_pow2": pow2 / real if real else float("inf"),
            "padding_waste_improvement": pow2 / padded if padded else 1.0,
            "launch_bytes": self.stats["launch_bytes"],
            "bucket_fill_hist": [int(h) for h in hist[:last]],
        }

    def evict_slot(self, slot: int) -> Optional[EventRequest]:
        """Release a slot without completing its request (SLO eviction).

        The deadline-miss path of the streaming runtime's admission
        layer: the slot's request is abandoned mid-stream, the slot state
        is re-zeroed (a chained device op — safe while a window that
        included this slot is still in flight, because the reset orders
        after that window's writes), and the slot immediately becomes
        admissible again.  Returns the evicted request, or None if the
        slot was free.
        """
        req = self.slot_req[slot]
        if req is None:
            return None
        self.slot_req[slot] = None
        self.active[slot] = False
        self._ev[slot] = None
        self._reset_slot_state(slot)
        self.stats["evicted"] += 1
        return req

    def _finish(self, slot: int) -> None:
        req = self.slot_req[slot]
        cc = np.asarray(self._reset_slot_state(slot))
        req.class_counts = cc
        req.prediction = int(np.argmax(cc))
        per_layer = self.acc_counts[:, slot]
        sops = [n * l.updates_per_event()
                for n, l in zip(per_layer, self.spec.layers)]
        sites = sum(l.in_shape[0] * l.in_shape[1] * l.in_shape[2]
                    for l in self.spec.layers)
        req.telemetry = request_telemetry(
            self.cfg, uid=req.uid, n_timesteps=req.n_timesteps,
            n_windows=int(self.windows[slot]),
            per_layer_events=list(per_layer), per_layer_sops=sops,
            input_sites=sites,
            input_dropped=req.dropped_at_ingest
            + int(self.collector_drops[slot]) + int(self.oor_drops[slot]),
            inter_layer_dropped=list(self.acc_drops[:, slot]),
            wall_time_s=time.time() - self.admit_time[slot],
            n_parallel_slices=self.n_parallel_slices,
            n_dense_timesteps=int(self.dense_ts[slot]),
            n_skipped_windows=int(self.skipped_windows[slot]))
        req.done = True
        self.slot_req[slot] = None
        self.active[slot] = False
        self._ev[slot] = None
        self.stats["completed"] += 1

    def run(self, requests: Sequence[EventRequest],
            max_windows: int = 100_000) -> None:
        """Continuous batching: admit as slots free, step until drained.

        The whole queue is validated before any work starts, so one
        malformed request rejects the batch up front instead of stranding
        already-admitted requests mid-flight.
        """
        for r in requests:
            self.validate_request(r)
        pending = list(requests)
        for _ in range(max_windows):
            while pending and self.try_admit(pending[0]):
                pending.pop(0)
            if self.step() == 0 and not pending:
                break
        else:
            raise RuntimeError("max_windows exceeded before drain")
