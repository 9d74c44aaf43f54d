"""Slot-sharded multi-device serving: the ``backend="mesh"`` engine.

`EventServeEngine` tops out at one device; this engine shards the serving
**slot axis** across a 1-D JAX device mesh (`distributed.sharding`'s
slot-axis helpers).  The paper's energy story scales the same way — SNE
replicates independent engine slices and multicasts events to them — and
the slot axis is exactly such a lane: every slot's computation is
independent of batch composition (the property the streaming-vs-sync
parity tests pin), so distributing slots over devices preserves each
request's bitwise results.

Construction is the Ludwig-style zero-code-change knob: callers build
``EventServeEngine(..., policy=ExecutionPolicy(backend="mesh"))`` and
``EventServeEngine.__new__`` returns this subclass — same constructor
args, same phase surface (`_collect_phase` / `_launch_phase` /
`_retire_phase` / `_finish`), so `EventServeEngine.run`, the
`StreamingRuntime`, and every test harness drive it unchanged.

Layout:

* **per-shard membrane slabs** — each of the D shards is a full local
  `EventServeEngine` owning ``n_slots / D`` slots, its states committed
  to its own device (`jax.device_put`); host bookkeeping (collector,
  admission, telemetry) stays shard-local.
* **replicated weights** — one mesh-replicated copy feeds the fused
  step; each shard also keeps a device-local copy for its fallback path.
* **host-side router** — :meth:`MeshEventServeEngine.try_admit` admits
  each request to the least-loaded shard (fewest active slots, lowest
  shard index on ties); explicit-slot admission (the streaming runtime's
  placement hook) maps global slot ids onto (shard, local-slot).

Dispatch picks between two paths per window:

* **fused mesh step** — when *every* shard has dense (non-idle) work,
  ONE ``shard_map``-ped `core.layer_program.window_step` runs over the
  whole slot axis: states stay sharded in place
  (`jax.make_array_from_single_device_arrays` assembles the global view
  of the per-device slabs zero-copy, and the outputs hand each shard its
  device-local block back), weights replicated, and idle slots ride
  along *frozen* — gates and liveness zeroed, leak deferred exactly as
  the local engine defers it — which is bitwise identical to skipping
  them (the dense branch of the local engine already holds frozen rows
  bit-for-bit).
* **per-shard dispatch** — when any shard's window is entirely idle,
  each dense shard launches its own compacted window on its own device
  (the shard engine's unmodified idle-skip compaction) and idle shards
  launch **nothing**: one device's dense window never forces launches
  on another.

``backend="local"`` remains the parity oracle: mesh outputs must match
it request-for-request across the full `core.policies.all_policies()`
matrix (`tests/test_mesh_serving.py`).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.econv import EConvParams
from repro.core.engine import SneConfig
from repro.core.layer_program import (FUSED_NETWORK, FUSED_WINDOW,
                                      effective_fusion, window_step)
from repro.core.policies import (BACKEND_LOCAL, BACKEND_MESH,
                                 ExecutionPolicy, resolve_policy)
from repro.core.sne_net import SNNSpec
from repro.distributed.sharding import (replicated, slot_mesh,
                                        slot_sharding, slot_spec)
from repro.serve.event_engine import (CollectedWindow, EventRequest,
                                      EventServeEngine, InflightWindow,
                                      event_bucket)
from repro.serve.spans import Span


@dataclasses.dataclass
class MeshCollectedWindow:
    """Per-shard collector outputs for one mesh window (pre-launch).

    ``part_idx`` is the *global* participating slot set (the streaming
    runtime snapshots launch-time slot->request maps from it); ``cols``
    holds each shard's local `CollectedWindow` (None where a shard has
    nothing to serve).
    """

    cols: List[Optional[CollectedWindow]]
    part_idx: np.ndarray
    seq: int                      # the mesh's window number


@dataclasses.dataclass
class MeshInflightWindow:
    """One dispatched-but-not-retired mesh window.

    Either a fused mesh step (``counts``/``drops`` are (L, N) global
    futures and ``dense`` the per-shard local dense slots) or a set of
    per-shard in-flight windows (``per_shard``).  ``idx`` is always the
    global launched slot ids — the field the streaming runtime's
    reserved-slot and latency-attribution logic reads.
    """

    idx: np.ndarray
    seq: int
    per_shard: Optional[List[Tuple[int, InflightWindow]]] = None
    dense: Optional[List[np.ndarray]] = None
    counts: Optional[jnp.ndarray] = None
    drops: Optional[jnp.ndarray] = None


class MeshEventServeEngine(EventServeEngine):
    """Slot-sharded `EventServeEngine` over a JAX device mesh."""

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
                 devices=None):
        """Shard ``n_slots`` over the mesh and build the fused mesh step.

        Same surface as `EventServeEngine` plus ``devices``: a device
        sequence, a device count, or None for the largest usable prefix
        of ``jax.devices()``.  ``n_slots`` must divide evenly over the
        shards (the ``shard_map`` uniformity constraint); with
        ``devices=None`` the largest divisor wins, an explicit request
        that does not divide raises.
        """
        pol = resolve_policy(
            "serve.event_engine.EventServeEngine", policy,
            default=ExecutionPolicy(backend=BACKEND_MESH),
            dtype_policy=dtype_policy, fusion_policy=fusion_policy,
            idle_skip=idle_skip, backend=backend)
        if pol.backend != BACKEND_MESH:
            # constructing the subclass directly is itself the choice
            pol = dataclasses.replace(pol, backend=BACKEND_MESH)
        if n_slots < 1 or window < 1:
            raise ValueError("need n_slots >= 1 and window >= 1")
        if devices is None:
            d = min(len(jax.devices()), n_slots)
            while n_slots % d:
                d -= 1
            self.mesh = slot_mesh(d)
        else:
            self.mesh = slot_mesh(devices)
            if n_slots % self.mesh.size:
                raise ValueError(
                    f"n_slots={n_slots} does not divide over "
                    f"{self.mesh.size} devices (equal slot shards are the "
                    f"shard_map uniformity constraint)")
        self._devs = list(self.mesh.devices.flat)
        self.D = len(self._devs)
        self.spd = n_slots // self.D          # slots per device (shard)
        self.policy = pol
        self.N = n_slots
        self.W = window
        self.spec = spec
        self.params = list(params)
        self.dtype_policy = pol.dtype_policy
        self.fusion_policy = pol.fusion_policy
        self.cfg = sne_cfg or SneConfig()
        self.n_parallel_slices = n_parallel_slices

        # D full local engines, one per device: shard-local membrane
        # slabs, collectors, admission and telemetry bookkeeping.  Their
        # state/params are committed to their device so the per-shard
        # fallback dispatch runs exactly where the slab lives.
        local_pol = dataclasses.replace(pol, backend=BACKEND_LOCAL)
        self.shards = []
        for dev in self._devs:
            sh = EventServeEngine(
                spec, params, n_slots=self.spd, window=window,
                step_capacities=step_capacities, sne_cfg=sne_cfg,
                n_parallel_slices=n_parallel_slices, co_blk=co_blk,
                use_pallas=use_pallas, donate_buffers=donate_buffers,
                policy=local_pol, device=dev)
            self.shards.append(sh)
        # one span record for the mesh and its shards (their retire waits
        # run inside the mesh's retire)
        self.phase_s: Dict[str, float] = {}
        for sh in self.shards:
            sh.phase_s = self.phase_s
        self.n_collected = 0
        self.program = self.shards[0].program
        self.caps = self.shards[0].caps
        self.idle_skip = self.shards[0].idle_skip

        # the fused mesh step: ONE shard_map'd window_step over the whole
        # slot axis — weights replicated, states/collector tensors
        # slot-sharded, each device computing its own block
        self._mesh_params = jax.device_put(self.params,
                                           replicated(self.mesh))
        P1, Pw = slot_spec(1, 0), slot_spec(2, 1)   # (N,...) / (W, N, ...)
        step_fn = partial(window_step, program=self.program, co_blk=co_blk,
                          use_pallas=use_pallas)
        # check_vma=False: every output is slot-sharded (nothing claimed
        # replicated); the flag only disables an assertion layer, not
        # numerics
        self._mesh_step = jax.jit(jax.shard_map(
            step_fn, mesh=self.mesh,
            in_specs=(jax.sharding.PartitionSpec(), P1, P1, Pw, Pw, Pw, P1),
            out_specs=(P1, P1, Pw, Pw), check_vma=False))

        # mesh-level launch accounting on top of the shards' own stats
        # (the aggregate `stats` property folds both together)
        self._extra = {"windows": 0, "step_calls": 0, "kernel_launches": 0,
                       "launched_events": 0, "padded_event_slots": 0,
                       "padded_event_slots_pow2": 0, "launch_bytes": 0,
                       "h2d_bytes": 0, "mesh_global_windows": 0,
                       "mesh_shard_windows": 0}

        # one-time sanity probe: the zero-copy assembly of per-device
        # blocks must map shard s to global rows [s*spd, (s+1)*spd)
        probe = self._assemble(
            [jax.device_put(
                jnp.arange(s * self.spd, (s + 1) * self.spd, dtype=jnp.int32),
                dev) for s, dev in enumerate(self._devs)], ndim=1)
        np.testing.assert_array_equal(np.asarray(probe),
                                      np.arange(self.N, dtype=np.int32))

    # --- sharded-state plumbing --------------------------------------------

    def _assemble(self, pieces: List[jnp.ndarray], ndim: int) -> jnp.ndarray:
        """Zero-copy global view of per-device blocks (slot axis 0)."""
        shape = (self.N,) + tuple(pieces[0].shape[1:])
        return jax.make_array_from_single_device_arrays(
            shape, slot_sharding(self.mesh, ndim, 0), pieces)

    def _put_slots(self, a: np.ndarray, axis: int) -> jnp.ndarray:
        """Host array -> its slot blocks on their devices (slot axis
        ``axis``), counted in ``h2d_bytes``."""
        self._extra["h2d_bytes"] += a.nbytes
        return jax.device_put(a, slot_sharding(self.mesh, a.ndim, axis))

    def _split(self, garr: jnp.ndarray) -> List[jnp.ndarray]:
        """Per-shard device-local blocks of a slot-sharded global array."""
        by_dev = {s.device: s.data for s in garr.addressable_shards}
        return [by_dev[d] for d in self._devs]

    # --- global views (the EventServeEngine surface) ------------------------

    @property
    def active(self) -> np.ndarray:
        """Global active mask — shard masks concatenated in slot order."""
        return np.concatenate([sh.active for sh in self.shards])

    @property
    def slot_req(self) -> List[Optional[EventRequest]]:
        """Global slot -> request view (read-only snapshot)."""
        return [r for sh in self.shards for r in sh.slot_req]

    @property
    def windows(self) -> np.ndarray:
        """Per-slot served-window counts, concatenated in slot order."""
        return np.concatenate([sh.windows for sh in self.shards])

    @property
    def tau(self) -> np.ndarray:
        """Per-slot time cursors, concatenated in slot order."""
        return np.concatenate([sh.tau for sh in self.shards])

    @property
    def bucket_fill_hist(self) -> np.ndarray:
        """Summed per-shard collector bucket-occupancy histogram."""
        return np.sum([sh.bucket_fill_hist for sh in self.shards], axis=0)

    @property
    def stats(self) -> dict:
        """Aggregate counters: shard sums + mesh-level launch accounting.

        ``windows`` counts *mesh* windows (one per engine tick, however
        many shards participated); ``mesh_global_windows`` /
        ``mesh_shard_windows`` split them by dispatch path.  Launch
        counters (``step_calls``, ``kernel_launches``, ...) sum the
        shards' own fallback dispatches with the fused mesh steps.
        """
        agg = dict.fromkeys(self.shards[0].stats, 0)
        for sh in self.shards:
            for k, v in sh.stats.items():
                agg[k] += v
        for k, v in self._extra.items():
            agg[k] = agg.get(k, 0) + v
        agg["windows"] = self._extra["windows"]
        return agg

    # --- admission: the host-side router ------------------------------------

    def try_admit(self, req: EventRequest,
                  slot: Optional[int] = None) -> bool:
        """Admit to the least-loaded shard; False when every shard is full.

        The router: by default the request lands on the shard with the
        fewest active slots (lowest shard index on ties) — keeping shard
        occupancy balanced so the fused mesh step's per-device work stays
        even.  ``slot`` pins a *global* slot id, mapped onto its
        (shard, local) pair — the streaming runtime's placement hook.
        """
        if slot is not None:
            if not 0 <= int(slot) < self.N:
                raise ValueError(f"slot {slot} out of range 0..{self.N - 1}")
            s, loc = divmod(int(slot), self.spd)
            return self.shards[s].try_admit(req, slot=loc)
        for s in sorted(range(self.D),
                        key=lambda i: (self.shards[i].n_active, i)):
            if self.shards[s].n_free:
                return self.shards[s].try_admit(req)
        return False

    def evict_slot(self, slot: int) -> Optional[EventRequest]:
        """Release a global slot without completing its request."""
        s, loc = divmod(int(slot), self.spd)
        return self.shards[s].evict_slot(loc)

    # --- the pipeline phases -------------------------------------------------

    def _collect_phase(self) -> Optional[MeshCollectedWindow]:
        """Collect every shard's window (pure host work), or None."""
        cols = [sh._collect_phase() for sh in self.shards]
        if all(c is None for c in cols):
            return None
        part = np.concatenate(
            [self.spd * s + c.part_idx
             for s, c in enumerate(cols) if c is not None])
        seq = self.n_collected
        self.n_collected += 1
        for c in cols:
            if c is not None:
                c.seq = seq     # shard windows carry the mesh's number
        return MeshCollectedWindow(cols=cols, part_idx=part, seq=seq)

    def _launch_phase(self, col: MeshCollectedWindow
                      ) -> Tuple[Optional[MeshInflightWindow], List[int]]:
        """Dispatch one mesh window; returns (in-flight, finished slots).

        Every shard with at least one dense slot -> the fused mesh step
        (one shard_map'd launch over the whole slot axis).  Any shard
        entirely idle -> per-shard dispatch, so the idle shard launches
        nothing.  Host time/skip bookkeeping is the local engine's
        `_account_window`, run per shard — mesh and local accounting
        share one implementation.
        """
        self._extra["windows"] += 1
        cols = col.cols
        dense = [sh._select_dense(c) if c is not None
                 else np.empty((0,), np.int64)
                 for sh, c in zip(self.shards, cols)]
        finished: List[int] = []
        if all(c is not None and len(d)
               for c, d in zip(cols, dense)):
            inflight = self._launch_global(cols, dense)
            for s, (sh, c, d) in enumerate(zip(self.shards, cols, dense)):
                finished += [self.spd * s + f
                             for f in sh._account_window(c, d)]
            return inflight, finished
        self._extra["mesh_shard_windows"] += 1
        pers: List[Tuple[int, InflightWindow]] = []
        idx_parts = []
        for s, (sh, c) in enumerate(zip(self.shards, cols)):
            if c is None:
                continue
            win, fin = sh._launch_phase(c)
            if win is not None:
                pers.append((s, win))
                idx_parts.append(self.spd * s + win.idx)
            finished += [self.spd * s + f for f in fin]
        if not pers:
            return None, finished
        return MeshInflightWindow(
            idx=np.concatenate(idx_parts), seq=col.seq,
            per_shard=pers), finished

    def _launch_global(self, cols: List[CollectedWindow],
                       dense: List[np.ndarray]) -> MeshInflightWindow:
        """Assemble and dispatch ONE fused mesh step over all shards.

        The global batch is the full slot axis in order (batch position
        == global slot), event axis trimmed to the window's occupancy
        exactly as the local engine trims it.  Idle-skipped slots ride
        along frozen — gate and liveness zeroed, leak deferred into
        their shard's ``pending_dt`` — which holds their state bitwise
        (the local engine's dense branch already proves frozen rows
        exact), so results per slot match the local oracle.
        """
        W, N, n = self.W, self.N, self.spd
        if self.idle_skip:
            # the SAME adaptive ladder trim the local engine applies
            # (serve.event_engine.event_bucket — single-sourced on purpose)
            mb = max(c.max_bucket for c in cols)
            Eb = event_bucket(mb, self.caps[0])
            Eb_pow2 = EventServeEngine._bucket(max(mb, 8), self.caps[0])
        else:
            Eb = Eb_pow2 = self.caps[0]
        xyc = np.zeros((W, N, Eb, 3), np.int32)
        gate = np.zeros((W, N, Eb), np.float32)
        alive = np.zeros((W, N), np.float32)
        pre = np.zeros((N,), np.int64)
        for s, (sh, c, d) in enumerate(zip(self.shards, cols, dense)):
            off = n * s
            xyc[:, off:off + n] = c.xyc[:, :, :Eb]
            gate[:, off:off + n] = c.gate[:, :, :Eb]
            alive[:, off:off + n] = c.alive
            idle = np.setdiff1d(c.part_idx, d)
            if len(idle):
                gate[:, off + idle] = 0.0
                alive[:, off + idle] = 0.0
            if sh.idle_skip and sh.pending_dt[d].any():
                pre[off + d] = sh.pending_dt[d]
                sh.pending_dt[d] = 0
                sh.stats["leak_flushes"] += 1
            sh.dense_ts[d] += c.alive[:, d].sum(axis=0).astype(np.int64)
        states_g = tuple(
            self._assemble([sh.states[li] for sh in self.shards],
                           ndim=self.shards[0].states[li].ndim)
            for li in range(len(self.shards[0].states)))
        cc_g = self._assemble([sh.class_counts for sh in self.shards],
                              ndim=2)
        # host inputs go straight to the device that owns each slot block
        ins = [self._put_slots(a, 1) for a in (xyc, gate, alive)]
        ins.append(self._put_slots(pre, 0))
        states_g, cc_g, counts, drops = self._mesh_step(
            self._mesh_params, states_g, cc_g, *ins)
        split_states = [self._split(v) for v in states_g]
        split_cc = self._split(cc_g)
        for s, sh in enumerate(self.shards):
            sh.states = tuple(sv[s] for sv in split_states)
            sh.class_counts = split_cc[s]
        self._extra["step_calls"] += 1
        fusion = effective_fusion(self.program, W)
        if fusion == FUSED_NETWORK:
            self._extra["kernel_launches"] += 1
        elif fusion == FUSED_WINDOW:
            self._extra["kernel_launches"] += len(self.program.ops)
        else:
            self._extra["kernel_launches"] += W * len(self.program.ops)
        self._extra["launched_events"] += int(gate.sum())
        self._extra["padded_event_slots"] += W * N * Eb
        self._extra["padded_event_slots_pow2"] += W * N * Eb_pow2
        self._extra["launch_bytes"] += xyc.nbytes + gate.nbytes + alive.nbytes
        self._extra["mesh_global_windows"] += 1
        idx = np.concatenate([n * s + d for s, d in enumerate(dense)])
        return MeshInflightWindow(idx=idx, seq=cols[0].seq, dense=dense,
                                  counts=counts, drops=drops)

    def _retire_phase(self, w: MeshInflightWindow) -> None:
        """Block on one in-flight mesh window; apply per-shard accounting."""
        if w.counts is not None:        # fused mesh step
            with Span(self.phase_s, "serve.retire.wait", win=w.seq):
                counts_np = np.asarray(w.counts, np.float64)
                drops_np = np.asarray(w.drops, np.float64)
            for s, (sh, d) in enumerate(zip(self.shards, w.dense)):
                sh.acc_counts[:, d] += counts_np[:, self.spd * s + d]
                sh.acc_drops[:, d] += drops_np[:, self.spd * s + d]
                sh.total_drops += drops_np[:, self.spd * s + d].sum(axis=1)
            return
        for s, win in w.per_shard:      # per-shard dispatches
            self.shards[s]._retire_phase(win)

    def inter_layer_drops(self) -> dict:
        """Engine-lifetime drop totals per boundary, summed over shards."""
        per_shard = [sh.inter_layer_drops() for sh in self.shards]
        total = np.sum([d["inter_layer_dropped"] for d in per_shard], axis=0)
        return {
            "inter_layer_dropped": [float(d) for d in total],
            "inter_layer_dropped_total": float(total.sum()),
            "collector_dropped": sum(d["collector_dropped"]
                                     for d in per_shard),
            "out_of_range_dropped": sum(d["out_of_range_dropped"]
                                        for d in per_shard),
        }

    def _finish(self, slot: int) -> None:
        """Complete a finished request and release its global slot."""
        s, loc = divmod(int(slot), self.spd)
        self.shards[s]._finish(loc)
