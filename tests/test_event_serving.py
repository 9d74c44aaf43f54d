"""Event-serving subsystem: batched kernel, collector, engine, telemetry.

Covers the PR-1 checklist: pack/unpack round-trip across EventFormat
variants, overflow/back-pressure accounting, batched-kernel vs per-slot
reference equivalence (bit-for-bit), and admission/release/drain of
EventServeEngine — plus the pack_events range checks and mapping mode 1
of the analytic model.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:           # container has no hypothesis; see the shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import events as ev
from repro.core.engine import SneConfig, inference_time_s
from repro.core.policies import ExecutionPolicy
from repro.core.quant import quantize_net
from repro.core.sne_net import dense_apply, init_snn, spike_counts, tiny_net
from repro.data.events_ds import TINY, batch_at
from repro.kernels.event_conv.ops import event_conv_batched
from repro.kernels.event_conv.ref import selfcheck_batched_bitexact
from repro.serve.event_engine import (EventRequest, EventServeEngine,
                                      default_step_capacities, event_bucket)
from repro.serve.runtime import ManualClock, StreamingRuntime
from repro.serve.telemetry import (proportionality_r2, request_telemetry,
                                   summarize)

# ---------------------------------------------------------------------------
# pack/unpack round trip across EventFormat variants (+ range checks)
# ---------------------------------------------------------------------------

FORMATS = [
    ev.EventFormat(),                                       # default (Fig. 1)
    ev.EventFormat(op_bits=2, t_bits=10, c_bits=6, x_bits=7, y_bits=7),
    ev.EventFormat(op_bits=2, t_bits=6, c_bits=2, x_bits=4, y_bits=4),
    ev.EventFormat(op_bits=2, t_bits=16, c_bits=2, x_bits=6, y_bits=6),
]


def _stream_for(fmt: ev.EventFormat, seed: int, n: int = 64):
    rng = np.random.default_rng(seed)
    k = int(rng.integers(1, n))
    def mk(bits):
        return jnp.asarray(
            rng.integers(0, 1 << bits, size=n).astype(np.int32))
    valid = jnp.asarray(np.arange(n) < k)
    return ev.EventStream(t=mk(fmt.t_bits), x=mk(fmt.x_bits),
                          y=mk(fmt.y_bits), c=mk(fmt.c_bits),
                          op=mk(fmt.op_bits), valid=valid)


@given(seed=st.integers(0, 2 ** 16))
@settings(max_examples=12, deadline=None)
def test_pack_roundtrip_all_formats(seed):
    """Valid slots survive pack->unpack exactly, for every field split."""
    for fmt in FORMATS:
        s = _stream_for(fmt, seed)
        back = ev.unpack_events(ev.pack_events(s, fmt), s.valid, fmt)
        m = np.asarray(s.valid)
        for a, b in zip(s, back):
            np.testing.assert_array_equal(np.asarray(a)[m],
                                          np.asarray(b)[m])


def test_pack_raises_on_out_of_range_valid_slot():
    s = ev.EventStream(t=jnp.array([1 << 12], jnp.int32),
                       x=jnp.zeros(1, jnp.int32), y=jnp.zeros(1, jnp.int32),
                       c=jnp.zeros(1, jnp.int32), op=jnp.zeros(1, jnp.int32),
                       valid=jnp.array([True]))
    with pytest.raises(ValueError, match="field 't'"):
        ev.pack_events(s)
    # same fields on a padding slot are fine (masked, no guarantee)
    s_pad = s._replace(valid=jnp.array([False]))
    ev.pack_events(s_pad)
    # mask-and-count face: jit-safe violation counter
    assert int(ev.pack_violations(s)) == 1
    assert int(ev.pack_violations(s_pad)) == 0
    # check=False silently masks (hardware DMA behaviour)
    assert ev.pack_events(s, check=False).dtype == jnp.uint32


def test_pack_checked_under_jit_does_not_crash():
    s = _stream_for(ev.DEFAULT_FORMAT, 0)
    words = jax.jit(ev.pack_events)(s)
    assert words.dtype == jnp.uint32


# ---------------------------------------------------------------------------
# batched kernel vs per-slot reference (bit-for-bit)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,H,W,Co,K,Ci,E", [
    (1, 10, 10, 8, 3, 2, 16),
    (3, 10, 10, 8, 3, 2, 16),
    (4, 8, 8, 16, 5, 4, 32),
    (2, 12, 12, 4, 1, 1, 8),
])
def test_batched_kernel_matches_per_slot_reference(N, H, W, Co, K, Ci, E):
    # shared checker: batched == per-slot kernel == oracle, bit-for-bit
    selfcheck_batched_bitexact(N, H, W, Co, K, Ci, E, seed=N + Co + E)


def test_batched_kernel_slot_isolation():
    """Events of slot i must never touch slot j's slab."""
    rng = np.random.default_rng(0)
    v = jnp.zeros((2, 8, 8, 4), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 2, 4)).astype(np.float32))
    xyc = jnp.asarray([[[2, 2, 0]], [[3, 3, 1]]], jnp.int32)
    gate = jnp.asarray([[1.0], [0.0]], jnp.float32)   # slot 1 gated off
    out = np.asarray(event_conv_batched(v, w, xyc, gate, co_blk=4))
    assert np.abs(out[0]).sum() > 0
    np.testing.assert_array_equal(out[1], 0.0)


def test_batched_kernel_rejects_slot_mismatch():
    v = jnp.zeros((2, 8, 8, 4), jnp.float32)
    w = jnp.zeros((3, 3, 2, 4), jnp.float32)
    xyc = jnp.zeros((3, 1, 3), jnp.int32)
    gate = jnp.zeros((3, 1), jnp.float32)
    with pytest.raises(ValueError, match="slot-axis mismatch"):
        event_conv_batched(v, w, xyc, gate, co_blk=4)


# ---------------------------------------------------------------------------
# collector overflow / back-pressure accounting
# ---------------------------------------------------------------------------

def _mini_engine(n_slots=2, window=4, caps=None, **kw):
    spec = tiny_net()
    params = init_snn(jax.random.PRNGKey(0), spec)
    eng = EventServeEngine(spec, params, n_slots=n_slots, window=window,
                           step_capacities=caps, use_pallas=False, **kw)
    return spec, params, eng


def test_collector_overflow_drops_and_counts():
    spec, params, eng = _mini_engine(
        n_slots=1, caps=[8] + default_step_capacities(tiny_net())[1:])
    T, H, W, C = (spec.n_timesteps,) + spec.in_shape
    spikes = jnp.zeros((T, H, W, C)).at[0, :4, :4, 0].set(1.0)  # 16 > cap 8
    req = EventRequest.from_dense(0, spikes)
    eng.run([req])
    assert req.done
    t = req.telemetry
    assert t.input_dropped == 8                      # 16 events, bucket of 8
    assert t.per_layer_events[0] == 8.0              # consumed = capacity
    assert eng.stats["collector_dropped"] == 8


def _sites_request(uid, sites, T, order=None):
    """Request with one t=0 UPDATE event per (x, y, c) site, in a given
    arrival order (the collector's bins preserve arrival order)."""
    arr = np.asarray(sites, np.int64)
    if order is not None:
        arr = arr[np.asarray(order)]
    n = len(arr)
    stream = ev.EventStream(
        t=jnp.zeros((n,), jnp.int32),
        x=jnp.asarray(arr[:, 0], jnp.int32),
        y=jnp.asarray(arr[:, 1], jnp.int32),
        c=jnp.asarray(arr[:, 2], jnp.int32),
        op=jnp.full((n,), ev.OP_UPDATE, jnp.int32),
        valid=jnp.ones((n,), bool))
    return EventRequest(uid=uid, stream=stream, n_timesteps=T)


def test_collector_overflow_drop_priority_deterministic():
    """An overfull timestep must drop by the routing sort key (lowest
    row-major flat site index survives), not by arrival order — the same
    deterministic priority `frame_to_events` applies between layers.

    Regression: the collector once truncated ``rows[:E0]`` in arrival
    order, so a permuted sensor stream changed which events survived."""
    spec = tiny_net()
    T = spec.n_timesteps
    # 16 distinct sites in one timestep against a capacity-8 collector;
    # the 8 lowest row-major keys are exactly the x in {0, 1} rows
    sites = [(x, y, 0) for x in range(4) for y in range(4)]
    survivors = [s for s in sites if s[0] < 2]
    rng = np.random.default_rng(42)

    def serve(req):
        _, _, eng = _mini_engine(
            n_slots=1, caps=[8] + default_step_capacities(tiny_net())[1:])
        eng.run([req])
        return req

    got = [serve(_sites_request(i, sites, T,
                                order=rng.permutation(len(sites))))
           for i in range(2)]
    ref = serve(_sites_request(9, survivors, T))
    assert all(r.telemetry.input_dropped == 8 for r in got)
    assert ref.telemetry.input_dropped == 0
    for r in got:
        np.testing.assert_array_equal(r.class_counts, ref.class_counts)
        assert r.prediction == ref.prediction


@pytest.mark.parametrize("fusion", ["fused-window", "fused-network"])
def test_donated_dummy_tail_mirrors_midflight_slot(fusion):
    """Idle-skip slot compaction with donated buffers and a NON-prefix
    active set: lengths 16/4/16/16 on 4 slots leave active = {0, 2, 3}
    after the first window, so the power-of-two dummy tail mirrors slot 0
    while slot 0 is itself mid-flight — its donated slab must be read
    for the mirror before being consumed by the step."""
    spec = tiny_net()
    params = init_snn(jax.random.PRNGKey(0), spec)
    spikes, _ = batch_at(11, 0, 4, TINY)
    mk = [spikes[0], spikes[1][:4], spikes[2], spikes[3]]

    solo = []
    for i, s in enumerate(mk):
        e = EventServeEngine(spec, params, n_slots=1, window=4,
                             use_pallas=False, donate_buffers=True,
                             policy=ExecutionPolicy(fusion_policy=fusion))
        r = EventRequest.from_dense(i, s)
        e.run([r])
        solo.append(r)

    eng = EventServeEngine(spec, params, n_slots=4, window=4,
                           use_pallas=False, donate_buffers=True,
                           policy=ExecutionPolicy(fusion_policy=fusion))
    reqs = [EventRequest.from_dense(i, s) for i, s in enumerate(mk)]
    for r in reqs:
        assert eng.try_admit(r)
    while eng.step():
        pass
    for got, want in zip(reqs, solo):
        np.testing.assert_array_equal(got.class_counts, want.class_counts)
        assert got.prediction == want.prediction


def test_event_bucket_ladder_properties():
    """The adaptive event ladder: sorted, bounded-waste, pow2-dominated."""
    from repro.serve.event_engine import event_bucket, event_bucket_ladder
    lad = event_bucket_ladder(256)
    assert lad[0] == 8 and lad[-1] == 256
    assert all(a < b for a, b in zip(lad, lad[1:]))
    assert len(lad) <= 2 * 256 .bit_length()      # O(log cap) jit retraces
    for n in range(257):
        b = event_bucket(n, 256)
        assert b in lad and b >= min(n, 256)
        # worst-case padding 1.5x (vs 2x for pure pow2 buckets)
        if n >= 8:
            assert 2 * b <= 3 * n or b == 8
        # the pow2 counterfactual the waste stats compare against can
        # never be smaller than the adaptive rung
        assert EventServeEngine._bucket(max(n, 8), 256) >= b
    # degenerate caps collapse to a single rung
    assert event_bucket_ladder(8) == (8,)
    assert event_bucket(3, 8) == 8


def test_bucket_fill_hist_sized_from_capacity():
    """The fill histogram derives its bins from caps[0] (regression: it
    was hard-coded to 34 bins and mis-sized for small collectors)."""
    _, _, small = _mini_engine(
        n_slots=1, caps=[8] + default_step_capacities(tiny_net())[1:])
    assert small.bucket_fill_hist.shape == (8 .bit_length() + 2,)
    _, _, eng = _mini_engine(n_slots=1)
    assert eng.bucket_fill_hist.shape == \
        (int(eng.caps[0]).bit_length() + 2,)
    spikes = jnp.zeros((tiny_net().n_timesteps,) + tiny_net().in_shape)
    spikes = spikes.at[0, :4, :4, 0].set(1.0)
    eng.run([EventRequest.from_dense(0, spikes)])
    assert int(eng.bucket_fill_hist.sum()) > 0


def test_ingest_overflow_counted():
    spikes = jnp.ones((2, 4, 4, 1))                  # 32 events
    req = EventRequest.from_dense(0, spikes, capacity=16)
    assert req.dropped_at_ingest == 16
    assert int(req.stream.count()) == 16


def test_admission_backpressure_when_full():
    spec, params, eng = _mini_engine(n_slots=2)
    spikes, _ = batch_at(0, 0, 3, TINY)
    reqs = [EventRequest.from_dense(i, spikes[i]) for i in range(3)]
    assert eng.try_admit(reqs[0]) and eng.try_admit(reqs[1])
    assert not eng.try_admit(reqs[2])                # engine full
    assert eng.n_free == 0
    while eng.step():
        pass
    assert eng.n_free == 2                           # slots released
    assert eng.try_admit(reqs[2])


# ---------------------------------------------------------------------------
# engine admission / release / drain + correctness vs the dense path
# ---------------------------------------------------------------------------

def test_engine_matches_dense_path_per_slot():
    """Served class counts == dense-path rate decode, request by request."""
    spec, params, eng = _mini_engine(n_slots=2, window=4)
    spikes, _ = batch_at(0, 0, 4, TINY)
    reqs = [EventRequest.from_dense(i, spikes[i]) for i in range(4)]
    eng.run(reqs)
    for i, r in enumerate(reqs):
        dense_out, _ = dense_apply(params, spec, spikes[i])
        want = np.asarray(spike_counts(dense_out))
        np.testing.assert_allclose(r.class_counts, want, atol=1e-4)
        assert r.prediction == int(np.argmax(want))


def test_engine_continuous_batching_drains_more_requests_than_slots():
    spec, params, eng = _mini_engine(n_slots=2, window=8)
    spikes, _ = batch_at(1, 0, 5, TINY)
    reqs = [EventRequest.from_dense(i, spikes[i]) for i in range(5)]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    assert eng.stats["admitted"] == 5
    assert eng.stats["completed"] == 5
    assert eng.n_active == 0
    # slot state is zeroed after release
    for v in eng.states:
        np.testing.assert_array_equal(np.asarray(v), 0.0)


def test_engine_variable_length_requests():
    """A short request in a long window must freeze cleanly at its T."""
    spec, params, eng = _mini_engine(n_slots=2, window=8)
    spikes, _ = batch_at(2, 0, 2, TINY)
    short = spikes[0][:5]                            # T=5, window 8
    reqs = [EventRequest.from_dense(0, short),
            EventRequest.from_dense(1, spikes[1])]   # T=16
    eng.run(reqs)
    assert all(r.done for r in reqs)
    d0, _ = dense_apply(params, spec, short)
    np.testing.assert_allclose(reqs[0].class_counts,
                               np.asarray(spike_counts(d0)), atol=1e-4)
    assert reqs[0].telemetry.n_windows == 1
    assert reqs[1].telemetry.n_windows == 2


def test_engine_slot_isolation_identical_results_any_cohort():
    """A request's result must not depend on its slot neighbours."""
    spec, params, _ = _mini_engine()
    spikes, _ = batch_at(3, 0, 3, TINY)
    solo_eng = EventServeEngine(spec, params, n_slots=1, window=4,
                                use_pallas=False)
    solo = EventRequest.from_dense(0, spikes[0])
    solo_eng.run([solo])
    _, _, eng = _mini_engine(n_slots=3)
    cohort = [EventRequest.from_dense(i, spikes[i]) for i in range(3)]
    eng.run(cohort)
    np.testing.assert_array_equal(solo.class_counts, cohort[0].class_counts)
    assert solo.telemetry.total_events == cohort[0].telemetry.total_events


def test_engine_rejects_non_update_opcodes():
    """The batched step has no RST/FIRE datapath — refuse loudly."""
    spec, params, eng = _mini_engine()
    spikes, _ = batch_at(5, 0, 1, TINY)
    req = EventRequest.from_dense(0, spikes[0])
    rst = ev.EventStream(
        t=jnp.array([1], jnp.int32), x=jnp.array([0], jnp.int32),
        y=jnp.array([0], jnp.int32), c=jnp.array([0], jnp.int32),
        op=jnp.array([ev.OP_RST], jnp.int32), valid=jnp.array([True]))
    req.stream = ev.concatenate_streams(req.stream, rst)
    with pytest.raises(ValueError, match="non-UPDATE"):
        eng.try_admit(req)


def test_engine_rejects_bad_config():
    spec = tiny_net()
    params = init_snn(jax.random.PRNGKey(0), spec)
    with pytest.raises(ValueError):
        EventServeEngine(spec, params, n_slots=0)
    with pytest.raises(ValueError):
        EventServeEngine(spec, params, n_slots=1, step_capacities=[4])


# ---------------------------------------------------------------------------
# telemetry + analytic-model mapping mode 1
# ---------------------------------------------------------------------------

def test_inference_time_mapping_modes():
    cfg = SneConfig(n_slices=8)
    t_serial = inference_time_s(cfg, 100.0)
    # ideal-balance bound
    assert inference_time_s(cfg, 100.0, n_parallel_slices=4) == \
        pytest.approx(t_serial / 4)
    # busiest-slice critical path with measured layer counts
    t = inference_time_s(cfg, 100.0, n_parallel_slices=2,
                         per_layer_events=[60.0, 30.0, 10.0])
    assert t == pytest.approx(0.6 * t_serial)
    # clamped to physical slices
    assert inference_time_s(cfg, 100.0, n_parallel_slices=64) == \
        pytest.approx(t_serial / 8)
    with pytest.raises(ValueError):
        inference_time_s(cfg, 100.0, n_parallel_slices=0)


def test_request_telemetry_fields():
    cfg = SneConfig()
    t = request_telemetry(cfg, uid=7, n_timesteps=16, n_windows=4,
                          per_layer_events=[80.0, 20.0],
                          per_layer_sops=[800.0, 100.0],
                          input_sites=288, input_dropped=3,
                          inter_layer_dropped=[0.0, 2.0],
                          n_parallel_slices=2)
    assert t.total_events == 100.0
    assert t.total_sops == 900.0
    assert t.sne_time_par_s <= t.sne_time_s
    assert t.sne_energy_j == pytest.approx(t.sne_power_w * t.sne_time_s)
    assert t.sne_rate_hz == pytest.approx(1.0 / t.sne_time_s)
    agg = summarize([t, t])
    assert agg["n_requests"] == 2
    assert agg["total_events"] == 200.0
    assert agg["total_dropped"] == 10.0


# ---------------------------------------------------------------------------
# window-level idle skip: bit-exactness vs the dense path, launch accounting
# ---------------------------------------------------------------------------

def _pattern_request(uid, spec, active_ts, seed=0, k=6):
    """Request whose events occur only at the given timesteps."""
    T, (H, W, C) = spec.n_timesteps, spec.in_shape
    rng = np.random.default_rng(seed + uid)
    s = np.zeros((T, H, W, C), np.float32)
    for t in active_ts:
        idx = rng.choice(H * W * C, size=k, replace=False)
        s[t].reshape(-1)[idx] = 1.0
    return EventRequest.from_dense(uid, jnp.asarray(s))


def _run_idle_pair(patterns, window=4, seed=0):
    """Serve the same cohort with idle_skip on and off; return both."""
    spec = tiny_net()
    params = init_snn(jax.random.PRNGKey(0), spec)
    out = {}
    for skip in (True, False):
        eng = EventServeEngine(spec, params, n_slots=len(patterns),
                               window=window, use_pallas=False,
                               policy=ExecutionPolicy(idle_skip=skip))
        reqs = [_pattern_request(i, spec, p, seed=seed)
                for i, p in enumerate(patterns)]
        eng.run(reqs)
        assert all(r.done for r in reqs)
        out[skip] = (reqs, eng)
    return out


def _assert_bitexact(out):
    for a, b in zip(out[True][0], out[False][0]):
        np.testing.assert_array_equal(a.class_counts, b.class_counts)
        assert a.prediction == b.prediction
        assert a.telemetry.total_events == b.telemetry.total_events
        assert a.telemetry.per_layer_events == b.telemetry.per_layer_events


def test_idle_skip_all_idle_launches_nothing():
    """A fully idle cohort must produce zero kernel launches (and match)."""
    out = _run_idle_pair([[], [], []])
    _assert_bitexact(out)
    eng = out[True][1]
    assert eng.stats["kernel_launches"] == 0
    assert eng.stats["step_calls"] == 0
    assert eng.stats["skipped_slot_windows"] == 3 * 4   # 3 slots x 4 windows
    assert out[False][1].stats["kernel_launches"] > 0
    for r, _ in [out[True]]:
        assert r[0].telemetry.n_dense_timesteps == 0
        assert r[0].telemetry.n_skipped_windows == 4


def test_idle_skip_alternating_windows_bitexact():
    """Slots alternate active/idle windows (deferred decay is flushed)."""
    w0 = [0, 1, 2, 3, 8, 9, 10, 11]       # windows 0 and 2 active
    w1 = [4, 5, 6, 7, 12, 13, 14, 15]     # windows 1 and 3 active
    out = _run_idle_pair([w0, w1, w0])
    _assert_bitexact(out)
    r = out[True][0][0].telemetry
    assert r.n_dense_timesteps == 8 and r.n_skipped_windows == 2
    assert out[True][1].stats["leak_flushes"] > 0


def test_idle_skip_single_active_slot_bitexact():
    """One busy slot must not drag idle neighbours through the kernel."""
    out = _run_idle_pair([[], list(range(16)), []])
    _assert_bitexact(out)
    eng = out[True][1]
    assert eng.stats["skipped_slot_windows"] == 2 * 4
    assert eng.stats["dense_slot_windows"] == 4
    # the kernel still launches every window (slot 1 is always active)…
    assert eng.stats["step_calls"] == 4
    # …but idle slots' telemetry shows they never stepped
    assert out[True][0][0].telemetry.n_dense_timesteps == 0
    assert out[True][0][1].telemetry.n_dense_timesteps == 16


def test_idle_skip_bursty_matches_dense_apply():
    """Skip path vs the *frame-based* dense reference, not just the dense
    engine: decay across skipped windows must be the analytic TLU form."""
    spec = tiny_net()
    params = init_snn(jax.random.PRNGKey(0), spec)
    eng = EventServeEngine(spec, params, n_slots=2, window=4,
                           use_pallas=False,
                           policy=ExecutionPolicy(idle_skip=True))
    reqs = [_pattern_request(i, spec, p, seed=5)
            for i, p in enumerate([[0, 1, 14, 15], [6]])]
    spikes = [np.asarray(ev.events_to_dense(
        r.stream, (spec.n_timesteps,) + spec.in_shape)) for r in reqs]
    eng.run(reqs)
    assert eng.stats["skipped_slot_windows"] > 0
    for r, s in zip(reqs, spikes):
        dense_out, _ = dense_apply(params, spec, jnp.asarray(s))
        np.testing.assert_allclose(
            r.class_counts, np.asarray(spike_counts(dense_out)), atol=1e-4)


def test_idle_skip_disabled_for_soft_reset():
    """Soft-reset neurons can fire without input — skip must disengage."""
    import dataclasses as dc
    spec = tiny_net()
    soft = dc.replace(spec, layers=tuple(
        dc.replace(l, lif=dc.replace(l.lif, reset_mode="subtract"))
        for l in spec.layers))
    params = init_snn(jax.random.PRNGKey(0), soft)
    eng = EventServeEngine(soft, params, n_slots=1, use_pallas=False,
                           policy=ExecutionPolicy(idle_skip=True))
    assert not eng.idle_skip          # silently fell back to dense stepping
    spikes = jnp.zeros((8,) + soft.in_shape).at[0, 2, 2, 0].set(1.0)
    req = EventRequest.from_dense(0, spikes)
    eng.run([req])
    assert req.done
    assert eng.stats["skipped_slot_windows"] == 0


@pytest.mark.parametrize("idle_skip", [True, False])
def test_non_prefix_active_set_after_release(idle_skip):
    """A freed middle slot must not corrupt its still-active neighbours.

    Requests of lengths 16/4/16 on 3 slots: slot 1 finishes after the
    first window, leaving active set {0, 2} — not a prefix of the slot
    range. Both engine modes must keep serving slots 0 and 2 correctly
    (regression: the dense branch once masked batch positions >= len(idx),
    wiping slot 2's events)."""
    spec = tiny_net()
    params = init_snn(jax.random.PRNGKey(0), spec)
    spikes, _ = batch_at(7, 0, 3, TINY)
    mk = [spikes[0], spikes[1][:4], spikes[2]]

    solo = []
    for i, s in enumerate(mk):
        e = EventServeEngine(spec, params, n_slots=1, window=4,
                             use_pallas=False,
                             policy=ExecutionPolicy(idle_skip=idle_skip))
        r = EventRequest.from_dense(i, s)
        e.run([r])
        solo.append(r)

    eng = EventServeEngine(spec, params, n_slots=3, window=4,
                           use_pallas=False,
                           policy=ExecutionPolicy(idle_skip=idle_skip))
    reqs = [EventRequest.from_dense(i, s) for i, s in enumerate(mk)]
    for r in reqs:
        assert eng.try_admit(r)
    while eng.step():
        pass
    for got, want in zip(reqs, solo):
        np.testing.assert_array_equal(got.class_counts, want.class_counts)
        assert got.telemetry.total_events == want.telemetry.total_events


# ---------------------------------------------------------------------------
# slot compaction inside the one jitted window program
# ---------------------------------------------------------------------------

HERE = Path(__file__).resolve().parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(scope="module")
def int_net():
    """tiny_net on the int4 grid: integer arithmetic, so the engine and
    `dense_apply` agree exactly."""
    spec = tiny_net()
    return quantize_net(init_snn(jax.random.PRNGKey(0), spec), spec)


def _busy_spikes(T, in_shape, seed, k=6):
    """``(T, H, W, C)`` spikes with ``k`` events at every timestep, so no
    window of the request is idle-skipped."""
    H, W, C = in_shape
    rng = np.random.default_rng(seed)
    s = np.zeros((T, H * W * C), np.float32)
    for t in range(T):
        s[t, rng.choice(H * W * C, size=k, replace=False)] = 1.0
    return s.reshape(T, H, W, C)


def _serve_placed(net, n_slots, placed, idle_skip, **kw):
    """Serve each ``(slot, spikes)`` pinned to its slot; (requests,
    engine)."""
    eng = EventServeEngine(net.spec, net.params_for("f32-carrier"),
                           n_slots=n_slots, window=4, use_pallas=False,
                           policy=ExecutionPolicy(idle_skip=idle_skip), **kw)
    reqs = []
    for uid, (slot, s) in enumerate(placed):
        r = EventRequest.from_dense(uid, jnp.asarray(s))
        assert eng.try_admit(r, slot=slot)
        reqs.append(r)
    while eng.step():
        pass
    return reqs, eng


def _assert_dense_parity(net, got, dense, spikes):
    """Bitwise equal to the dense engine and to `dense_apply`."""
    params = net.params_for("f32-carrier")
    for g, d, s in zip(got, dense, spikes):
        np.testing.assert_array_equal(g.class_counts, d.class_counts)
        assert g.telemetry.per_layer_events == d.telemetry.per_layer_events
        assert (g.telemetry.inter_layer_dropped
                == d.telemetry.inter_layer_dropped)
        out, _ = dense_apply(params, net.spec, jnp.asarray(s))
        np.testing.assert_array_equal(g.class_counts,
                                      np.asarray(spike_counts(out)))


@pytest.mark.parametrize("n_slots,slots,compacted", [
    (8, (0, 2, 5), True),        # A = 3, Ab = 4: the dummy reads slot 0
    (10, (1, 4, 7, 9), True),    # A = Ab = 4: no dummies
    (4, (0, 1, 2, 3), False),    # full in-order batch: the identity index
], ids=["slot0-mirrored", "no-dummies", "full-batch"])
def test_compacted_window_parity(int_net, n_slots, slots, compacted):
    """One jitted program gathers, steps and scatters back the batch.

    With busy slots {0, 2, 5} the dummy tail reads slot 0, which is also
    a real row: the dummy must be dropped at scatter-back, not written
    over slot 0.  Each case matches the dense engine (idle skip off)
    and `dense_apply` bitwise."""
    T = int_net.spec.n_timesteps
    spikes = [_busy_spikes(T, int_net.spec.in_shape, seed=s) for s in slots]
    placed = list(zip(slots, spikes))
    got, eng = _serve_placed(int_net, n_slots, placed, idle_skip=True,
                             donate_buffers=True)
    dense, _ = _serve_placed(int_net, n_slots, placed, idle_skip=False)
    n_win = T // eng.W
    assert eng.stats["step_calls"] == n_win
    assert eng.stats["compacted_windows"] == (n_win if compacted else 0)
    _assert_dense_parity(int_net, got, dense, spikes)


def test_compacted_non_prefix_after_release_streaming(int_net):
    """Under the streaming runtime a short request's release leaves a
    non-prefix busy set ({0, 2, 3} while slot 1 waits to retire) and a
    queued request then takes the freed slot; every answer matches the
    dense engine and `dense_apply` bitwise."""
    lengths = [16, 4, 16, 8, 12]
    spikes = [_busy_spikes(t, int_net.spec.in_shape, seed=20 + i)
              for i, t in enumerate(lengths)]
    eng = EventServeEngine(int_net.spec, int_net.params_for("f32-carrier"),
                           n_slots=4, window=4, use_pallas=False,
                           donate_buffers=True)
    rt = StreamingRuntime(eng, queue_capacity=8, clock=ManualClock())
    got = [EventRequest.from_dense(i, jnp.asarray(s))
           for i, s in enumerate(spikes)]
    rt.submit(got)
    assert rt.serve()["completed"] == len(got)
    assert eng.stats["compacted_windows"] > 0
    dense_eng = EventServeEngine(
        int_net.spec, int_net.params_for("f32-carrier"), n_slots=4,
        window=4, use_pallas=False, policy=ExecutionPolicy(idle_skip=False))
    dense = [EventRequest.from_dense(i, jnp.asarray(s))
             for i, s in enumerate(spikes)]
    dense_eng.run(dense)
    _assert_dense_parity(int_net, got, dense, spikes)


def test_compacted_windows_compile_nothing_after_warm_up(int_net):
    """After one warm-up window at every busy count A in 1..N, windows
    over non-prefix busy sets compile nothing, and the step holds one
    program per (Ab, Eb) pair: the identity batch (A = N) and the
    compacted batches with Ab = N share a program."""
    N, T, k = 4, 4, 6
    eng = EventServeEngine(int_net.spec, int_net.params_for("f32-carrier"),
                           n_slots=N, window=T, use_pallas=False,
                           donate_buffers=True)
    shape = int_net.spec.in_shape

    def cohort(slots, seed):
        return [(s, EventRequest.from_dense(
            s, jnp.asarray(_busy_spikes(T, shape, seed + s, k))))
            for s in slots]

    def serve(placed):
        for slot, r in placed:
            assert eng.try_admit(r, slot=slot)
        while eng.step():
            pass

    for a in range(1, N + 1):
        serve(cohort(range(a), seed=100 * a))
    programs = eng._step._cache_size()
    pairs = {(eng._bucket(a, N), event_bucket(k, eng.caps[0]))
             for a in range(1, N + 1)}
    assert programs == len(pairs) == 3

    sets = [(1, 3), (0, 2, 3), (2,), (0, 1, 2, 3), (1, 2, 3), (0, 3)]
    cohorts = [cohort(s, seed=1000 + 10 * i) for i, s in enumerate(sets)]
    c0 = dict(eng.stats)
    compiles = []

    def on_event(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for placed in cohorts:
            serve(placed)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []
    assert eng._step._cache_size() == programs
    assert eng.stats["step_calls"] - c0["step_calls"] == len(sets)
    assert (eng.stats["compacted_windows"] - c0["compacted_windows"]
            == sum(len(s) < N for s in sets))
    assert all(r.done for placed in cohorts for _, r in placed)


def test_launch_window_dispatches_no_eager_primitive(int_net,
                                                     monkeypatch):
    """A compacted window's launch starts no eager device op: the slot
    gather and scatter-back run inside the one jitted ``_step``, so
    ``_launch_window`` only puts its inputs and makes that call."""
    from jax._src import dispatch

    T = 8
    shape = int_net.spec.in_shape
    eng = EventServeEngine(int_net.spec, int_net.params_for("f32-carrier"),
                           n_slots=4, window=4, use_pallas=False,
                           donate_buffers=True)
    for s in (0, 2, 3):
        r = EventRequest.from_dense(
            s, jnp.asarray(_busy_spikes(T, shape, seed=s)))
        assert eng.try_admit(r, slot=s)
    eager = []
    apply = dispatch.apply_primitive

    def counting(prim, *a, **k):
        eager.append(prim)
        return apply(prim, *a, **k)

    launch = eng._launch_window

    def watched(*a, **k):
        with monkeypatch.context() as m:
            m.setattr(dispatch, "apply_primitive", counting)
            return launch(*a, **k)

    eng._launch_window = watched
    while eng.step():
        pass
    assert eng.stats["compacted_windows"] == eng.stats["step_calls"] == 2
    assert eager == []


def test_compacted_windows_counts_non_identity_local(int_net):
    """``compacted_windows`` counts the window steps whose batch is not
    the identity: a full cohort adds none, a prefix or a non-prefix
    subset one per window, and a dense engine (idle skip off) with an
    idle slot counts too, since its batch leaves that slot out."""
    T = int_net.spec.n_timesteps
    shape = int_net.spec.in_shape
    n_win = T // 4
    for slots, idle_skip, want in [((0, 1, 2, 3), True, 0),
                                   ((0, 1, 2), True, n_win),
                                   ((1, 3), True, n_win),
                                   ((0, 1, 2, 3), False, 0),
                                   ((0, 2), False, n_win)]:
        placed = [(s, _busy_spikes(T, shape, seed=s)) for s in slots]
        _, eng = _serve_placed(int_net, 4, placed, idle_skip=idle_skip)
        assert eng.stats["step_calls"] == n_win, slots
        assert eng.stats["compacted_windows"] == want, (slots, idle_skip)


def test_compacted_windows_counts_non_identity_mesh():
    """On the mesh engine over four CPU devices the counter sums the
    shards': a per-shard window with one of a shard's two slots busy
    counts, a shard with both busy does not, and a fused mesh step
    (every shard busy) counts none (``tests/compact_mesh_check.py``)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(HERE.parent / "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, str(HERE / "compact_mesh_check.py")], env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4, out
    # slots 0, 1 (shard 0, both) and 2 (shard 1, one of two); shards 2
    # and 3 idle -> per-shard windows, shard 1's compacted
    assert out["per_shard"] == {"compacted": 2, "shard_sum": 2,
                                "step_calls": 4, "mesh_shard_windows": 2,
                                "mesh_global_windows": 0}, out
    # one slot of every shard busy -> fused mesh steps, nothing compacted
    assert out["fused"] == {"compacted": 0, "shard_sum": 0,
                            "step_calls": 2, "mesh_shard_windows": 0,
                            "mesh_global_windows": 2}, out


def test_boundary_cost_credits_idle_skip():
    """With cycles_per_boundary set, skipped timesteps cost less energy."""
    cfg = SneConfig(cycles_per_boundary=64)
    kw = dict(uid=0, n_timesteps=16, n_windows=4,
              per_layer_events=[50.0], per_layer_sops=[500.0],
              input_sites=288)
    full = request_telemetry(cfg, **kw)                    # all 16 stepped
    skipped = request_telemetry(cfg, n_dense_timesteps=4,
                                n_skipped_windows=3, **kw)
    assert full.n_dense_timesteps == 16                    # default = all
    assert skipped.sne_time_s < full.sne_time_s
    assert skipped.sne_energy_j < full.sne_energy_j
    assert skipped.sne_time_par_s < full.sne_time_par_s
    # default config stays calibrated: boundary term is zero
    base = request_telemetry(SneConfig(), **kw)
    lazy = request_telemetry(SneConfig(), n_dense_timesteps=0, **kw)
    assert base.sne_time_s == lazy.sne_time_s


def test_served_energy_proportionality():
    """More input events => proportionally more modeled serving energy."""
    spec, params, eng = _mini_engine(n_slots=2)
    spikes, _ = batch_at(4, 0, 2, TINY)
    tele = []
    for frac in (0.3, 0.6, 1.0):
        mask = (jax.random.uniform(jax.random.PRNGKey(9),
                                   spikes[0].shape) < frac)
        req = EventRequest.from_dense(0, spikes[0] * mask)
        eng.run([req])
        tele.append(req.telemetry)
    evs = [t.total_events for t in tele]
    es = [t.sne_energy_j for t in tele]
    assert evs == sorted(evs) and es == sorted(es)
    assert proportionality_r2(tele) > 0.97
