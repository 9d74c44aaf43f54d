"""Whole-network launch accounting for the unified layer-program executor.

The refactor's claim: a full-network window step is a *chain of Pallas
launches* — one slot-batched scatter kernel per layer per timestep (conv,
pool AND fc), with inter-layer event routing staying on device — instead
of the per-layer dense fallback composition (event scatter emulated by
gather/scatter/dynamic-slice primitive chains).  This benchmark measures
that claim the way `benchmarks/idle_skip.py` measures the TLU skip:

  * per layer x timestep, trace `layer_program.layer_timestep` (plus its
    `frame_to_events` routing) and count device-op dispatches (recursive
    jaxpr equations) and Pallas kernel launches, for the unified Pallas
    path vs the pure-jnp fallback (``use_pallas=False``);
  * assert the unified path dispatches strictly fewer device ops per
    window on `tiny_net` — each layer's scatter collapses into exactly
    one launch;
  * trace the WHOLE `window_step` under every **fusion policy** and
    count Pallas launches: the fused-window lowering must be exactly L
    launches per window (one fused kernel per layer, time loop inside)
    vs L x W for the per-step oracle — the launch-overhead delta the
    regression gate pins (``fused_launch_ratio_min``) — and the
    fused-network megakernel exactly ONE launch per window (the whole
    layer chain + ring-buffer routing in one kernel,
    ``network_fused_launches_max``), with every lowering decoding a
    served cohort bitwise identically; report each policy's resident
    membrane/scratch bytes and the megakernel's VMEM plan + ring-overflow
    drop totals per layer boundary;
  * serve a small cohort through `EventServeEngine` (which jits exactly
    this executor, fused windows by default) and record the
    serving-level events/J headline;
  * compare the two **dtype policies** on the quantized net: per-layer
    bytes one scatter launch moves (f32 carrier vs int8-native — the
    int8 path must be strictly smaller on EVERY layer), the effective
    per-SOP energy each policy implies (the ASIC's 0.221 pJ/SOP scaled
    by relative bytes/SOP — the carrier pays the emulation's extra
    traffic), and bitwise parity of a served cohort across policies.

Emits ``BENCH_layer_program.json`` for CI's regression gate
(`benchmarks/check_regression.py`), which pins ``int8_bytes_ratio``.

    PYTHONPATH=src python -m benchmarks.layer_program [--fast]
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from jax.extend import core as jax_core

from benchmarks.policy_report import policy_accounting
from repro.core import layer_program as lp
from repro.core.quant import quantize_net
from repro.core.sne_net import init_snn, tiny_net
from repro.serve.event_engine import EventRequest, EventServeEngine
from repro.serve.telemetry import summarize

WINDOW = 4
SLOTS = 2


def _count_ops(jaxpr) -> tuple:
    """Recursively count (equations, pallas_call launches) in a jaxpr."""
    n_eqns = n_pallas = 0
    for eqn in jaxpr.eqns:
        n_eqns += 1
        if eqn.primitive.name == "pallas_call":
            n_pallas += 1
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                e, p = _count_ops(sub)
                n_eqns += e
                n_pallas += p
    return n_eqns, n_pallas


def _subjaxprs(v):
    vals = v if isinstance(v, (list, tuple)) else [v]
    for u in vals:
        if isinstance(u, jax_core.ClosedJaxpr):
            yield u.jaxpr
        elif isinstance(u, jax_core.Jaxpr):
            yield u


def _count_executed(jaxpr) -> tuple:
    """Like :func:`_count_ops`, but weighted by *execution* count: a
    ``lax.scan`` body's ops and launches run once per trip, so they are
    multiplied by the scan length (the per-step window driver scans over
    timesteps — its launches must be charged W times, exactly what the
    device replays)."""
    n_eqns = n_pallas = 0
    for eqn in jaxpr.eqns:
        n_eqns += 1
        if eqn.primitive.name == "pallas_call":
            n_pallas += 1
            continue
        mult = (eqn.params.get("length", 1)
                if eqn.primitive.name == "scan" else 1)
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                e, p = _count_executed(sub)
                n_eqns += mult * e
                n_pallas += mult * p
    return n_eqns, n_pallas


def layer_dispatches(spec, params, use_pallas):
    """Trace one (layer, timestep) step per layer; count its device ops.

    Layer 0 consumes collector events; deeper layers include the
    `frame_to_events` routing of the previous FIRE frame, so the count is
    the full per-layer cost of one executor timestep.
    """
    prog = lp.compile_program(spec)
    alive = jnp.ones((SLOTS,), jnp.float32)
    rows = []
    for op, p in zip(prog.ops, params):
        vp = lp.padded_state(op, jnp.float32, n_slots=SLOTS)
        H, W, C = op.spec.in_shape

        if op.index == 0:
            def fn(vp, xyc, gate, op=op, p=p):
                return lp.layer_timestep(op, p, vp, xyc, gate, alive,
                                         use_pallas=use_pallas)
            cap = op.step_capacity
            xyc = jnp.zeros((SLOTS, cap, 3), jnp.int32)
            gate = jnp.zeros((SLOTS, cap), jnp.float32)
            jx = jax.make_jaxpr(fn)(vp, xyc, gate)
        else:
            def fn(vp, s_prev, op=op, p=p):
                xyc, gate, _ = lp.frame_to_events(s_prev, op.step_capacity)
                return lp.layer_timestep(op, p, vp, xyc, gate, alive,
                                         use_pallas=use_pallas)
            s_prev = jnp.zeros((SLOTS, H, W, C), jnp.float32)
            jx = jax.make_jaxpr(fn)(vp, s_prev)
        n_ops, n_pallas = _count_ops(jx.jaxpr)
        rows.append({"layer": op.index, "kind": op.kind,
                     "device_ops": n_ops, "pallas_launches": n_pallas})
    return rows


def window_launches(spec, params, fusion_policy, use_pallas=None):
    """Trace one whole `window_step` under a fusion policy; count launches.

    Returns ``(device_ops, pallas_launches)`` for the full L-layer,
    W-timestep serving step — the figure the fused lowering collapses
    from L x W to L.
    """
    from functools import partial
    prog = lp.compile_program(spec, policy=lp.ExecutionPolicy(
        fusion_policy=fusion_policy))
    states = tuple(lp.padded_state(op, n_slots=SLOTS) for op in prog.ops)
    cc = jnp.zeros((SLOTS, spec.n_classes), jnp.float32)
    E0 = prog.ops[0].step_capacity
    xyc = jnp.zeros((WINDOW, SLOTS, E0, 3), jnp.int32)
    gate = jnp.zeros((WINDOW, SLOTS, E0), jnp.float32)
    alive = jnp.ones((WINDOW, SLOTS), jnp.float32)
    pre_dt = jnp.zeros((SLOTS,), jnp.int32)
    jx = jax.make_jaxpr(partial(lp.window_step, program=prog,
                                use_pallas=use_pallas))(
        params, states, cc, xyc, gate, alive, pre_dt)
    return _count_executed(jx.jaxpr)


def serve_cohort(spec, params, n_timesteps, seed=0,
                 dtype_policy=lp.F32_CARRIER,
                 fusion_policy=lp.FUSED_WINDOW):
    """Serve a small random cohort; return engine stats + events/J."""
    rng = np.random.default_rng(seed)
    H, W, C = spec.in_shape
    reqs = []
    for uid in range(SLOTS):
        spikes = (rng.random((n_timesteps, H, W, C)) < 0.1)
        reqs.append(EventRequest.from_dense(
            uid, jnp.asarray(spikes.astype(np.float32))))
    eng = EventServeEngine(spec, params, n_slots=SLOTS, window=WINDOW,
                           use_pallas=False,
                           policy=lp.ExecutionPolicy(
                               dtype_policy=dtype_policy,
                               fusion_policy=fusion_policy))
    t0 = time.time()
    eng.run(reqs)
    wall = time.time() - t0
    agg = summarize([r.telemetry for r in reqs])
    return {
        "wall_s": wall,
        "kernel_launches": eng.stats["kernel_launches"],
        "launches_per_window": eng.stats["kernel_launches"]
        / max(eng.stats["step_calls"], 1),
        "events": agg["total_events"],
        "events_per_joule": agg["events_per_joule"],
        "inter_layer_drops": eng.inter_layer_drops(),
        "class_counts": np.stack([r.class_counts for r in reqs]),
    }


def fusion_memory_rows(spec, n_timesteps):
    """Per-fusion-policy peak membrane + VMEM scratch bytes (satellite of
    the megakernel PR: the state/scratch footprint each lowering keeps
    resident, the figure the fused-network budget fallback guards)."""
    rows = []
    for fusion in (lp.PER_STEP, lp.FUSED_WINDOW, lp.FUSED_NETWORK):
        prog = lp.compile_program(spec, policy=lp.ExecutionPolicy(
            fusion_policy=fusion))
        rows.append({
            "fusion_policy": fusion,
            "membrane_bytes": lp.state_bytes(prog, SLOTS),
            "scratch_bytes": lp.window_scratch_bytes(prog, WINDOW),
        })
    plan = lp.network_window_plan(
        lp.compile_program(spec, policy=lp.ExecutionPolicy(
            fusion_policy=lp.FUSED_NETWORK)), WINDOW)
    return rows, plan


def dtype_policy_accounting(spec, params):
    """Quantize the net and run the shared per-policy accounting
    (`benchmarks/policy_report.py` — one formula for every BENCH report;
    asserts the int8 launch is strictly smaller on every layer)."""
    qn = quantize_net(params, spec)
    rows, policies, bytes_ratio = policy_accounting(qn.spec, SLOTS)
    return qn, rows, policies, bytes_ratio


def main(fast: bool = False) -> None:
    print("layer_program [unified executor: one launch per layer x step]")
    n_ts = 8 if fast else 16
    spec = tiny_net(n_timesteps=n_ts)
    params = init_snn(jax.random.PRNGKey(0), spec)

    unified = layer_dispatches(spec, params, use_pallas=None)
    fallback = layer_dispatches(spec, params, use_pallas=False)
    print(f"  {'layer':>5} {'kind':>5} {'pallas ops':>10} {'launches':>8} "
          f"{'fallback ops':>12}")
    for u, f in zip(unified, fallback):
        print(f"  {u['layer']:>5} {u['kind']:>5} {u['device_ops']:>10} "
              f"{u['pallas_launches']:>8} {f['device_ops']:>12}")

    ops_u = sum(r["device_ops"] for r in unified)
    ops_f = sum(r["device_ops"] for r in fallback)
    launches = sum(r["pallas_launches"] for r in unified)
    L = len(spec.layers)
    # the executor contract: exactly ONE scatter launch per layer per step
    assert launches == L, (launches, L)
    assert all(r["pallas_launches"] == 0 for r in fallback)
    # per-window totals: W timesteps x per-layer cost
    win_u, win_f = WINDOW * ops_u, WINDOW * ops_f
    assert win_u < win_f, (win_u, win_f)
    print(f"  per-window device ops: {win_u} unified (x{WINDOW} steps, "
          f"{WINDOW * launches} kernel launches) vs {win_f} fallback "
          f"-> {win_f / win_u:.2f}x fewer dispatches")

    # --- fusion policies: L launches per fused window vs L x W ----------
    ops_fused, launches_fused = window_launches(spec, params,
                                                lp.FUSED_WINDOW)
    ops_step, launches_step = window_launches(spec, params, lp.PER_STEP)
    # the fused-window contract: exactly ONE launch per LAYER per WINDOW
    assert launches_fused == L, (launches_fused, L)
    assert launches_step == WINDOW * L, (launches_step, WINDOW * L)
    fused_ratio = launches_step / launches_fused
    print(f"  window launches: {launches_fused} fused vs {launches_step} "
          f"per-step -> x{fused_ratio:.1f} fewer launches "
          f"({ops_fused} vs {ops_step} device ops per window)")

    # --- fused-network megakernel: the WHOLE window in ONE launch -------
    ops_net, launches_net = window_launches(spec, params, lp.FUSED_NETWORK)
    # the megakernel contract: exactly ONE launch per WINDOW (vs L fused,
    # L x W per-step)
    assert launches_net == 1, launches_net
    net_ratio = launches_fused / launches_net
    print(f"  network window launches: {launches_net} megakernel vs "
          f"{launches_fused} fused-window -> x{net_ratio:.1f} fewer "
          f"launches ({ops_net} device ops per window)")

    mem_rows, plan = fusion_memory_rows(spec, WINDOW)
    print(f"  {'fusion':>13} {'membrane B':>10} {'scratch B':>10}")
    for r in mem_rows:
        print(f"  {r['fusion_policy']:>13} {r['membrane_bytes']:>10} "
              f"{r['scratch_bytes']:>10}")
    print(f"  megakernel VMEM plan: {plan.membrane_bytes} membrane + "
          f"{plan.ring_bytes} rings + {plan.io_bytes} I/O = "
          f"{plan.total_bytes} B (budget {lp.DEFAULT_VMEM_BUDGET})")

    served = serve_cohort(spec, params, n_ts)
    served_step = serve_cohort(spec, params, n_ts,
                               fusion_policy=lp.PER_STEP)
    served_net = serve_cohort(spec, params, n_ts,
                              fusion_policy=lp.FUSED_NETWORK)
    # the engine accounts one launch per window under the megakernel, one
    # per layer per window when fused, one per layer per timestep on the
    # per-step oracle lowering
    assert served["launches_per_window"] == L
    assert served_step["launches_per_window"] == WINDOW * L
    assert served_net["launches_per_window"] == 1
    # and the three lowerings must decode bitwise identically
    np.testing.assert_array_equal(served["class_counts"],
                                  served_step["class_counts"])
    np.testing.assert_array_equal(served["class_counts"],
                                  served_net["class_counts"])
    # wall-time: interpret-mode CPU timing, so report a loose ratio (> 1
    # means the megakernel window is cheaper end to end)
    net_wall_ratio = served["wall_s"] / max(served_net["wall_s"], 1e-9)
    drops = served_net["inter_layer_drops"]
    print(f"  served {served['events']:.0f} events, "
          f"{served_net['launches_per_window']:.0f} launch/window "
          f"megakernel (vs {served['launches_per_window']:.0f} fused, "
          f"{served_step['launches_per_window']:.0f} per-step, "
          f"bitwise-equal decode), wall x{net_wall_ratio:.2f} vs fused, "
          f"{served['events_per_joule']:.3e} events/J")
    print(f"  inter-layer ring drops per boundary: "
          f"{drops['inter_layer_dropped']} "
          f"(total {drops['inter_layer_dropped_total']:.0f})")

    # --- dtype policies: bytes per launch + effective pJ/SOP + parity ----
    qn, byte_rows, policies, bytes_ratio = dtype_policy_accounting(spec,
                                                                   params)
    print(f"  {'layer':>5} {'kind':>5} {'f32 bytes':>10} {'int8 bytes':>10} "
          f"{'ratio':>6}")
    for r in byte_rows:
        print(f"  {r['layer']:>5} {r['kind']:>5} {r['bytes_f32']:>10} "
              f"{r['bytes_int8']:>10} {r['ratio']:>6.2f}")
    for pol, d in policies.items():
        print(f"  {pol}: {d['bytes_per_sop']:.2f} B/SOP, "
              f"{d['pj_per_sop_effective']:.3f} pJ/SOP effective")
    assert bytes_ratio > 1.0
    # the int8-native path hits the ASIC's modeled figure by construction;
    # the carrier pays the bytes ratio on top
    assert (policies[lp.INT8_NATIVE]["pj_per_sop_effective"]
            < policies[lp.F32_CARRIER]["pj_per_sop_effective"])
    # dual-policy serve: the quantized cohort must decode identically
    served_q = {pol: serve_cohort(qn.spec, qn.params_for(pol), n_ts,
                                  dtype_policy=pol)
                for pol in (lp.F32_CARRIER, lp.INT8_NATIVE)}
    np.testing.assert_array_equal(
        served_q[lp.F32_CARRIER]["class_counts"],
        served_q[lp.INT8_NATIVE]["class_counts"])
    print(f"  int8-native == f32-carrier on served cohort (bitwise); "
          f"launch bytes ratio x{bytes_ratio:.2f}")

    out = {
        "bench": "layer_program",
        "config": {"net": "tiny_net", "n_timesteps": n_ts, "window": WINDOW,
                   "slots": SLOTS, "use_pallas": False},
        "per_layer": [
            {**u, "fallback_device_ops": f["device_ops"]}
            for u, f in zip(unified, fallback)],
        "ops_per_window_unified": win_u,
        "ops_per_window_fallback": win_f,
        "dispatch_ratio": win_f / win_u,
        "fused_launches_per_window": launches_fused,
        "perstep_launches_per_window": launches_step,
        "fused_launch_ratio": fused_ratio,
        "fused_parity": True,
        "network_fused_launches": launches_net,
        "network_launch_ratio": net_ratio,
        "network_wall_ratio": net_wall_ratio,
        "network_parity": True,
        "network_vmem_plan": {
            "membrane_bytes": plan.membrane_bytes,
            "ring_bytes": plan.ring_bytes,
            "io_bytes": plan.io_bytes,
            "total_bytes": plan.total_bytes,
            "budget_bytes": lp.DEFAULT_VMEM_BUDGET,
        },
        "fusion_memory": mem_rows,
        "inter_layer_dropped": drops["inter_layer_dropped"],
        "inter_layer_dropped_total": drops["inter_layer_dropped_total"],
        "launches_per_window": served["launches_per_window"],
        "events_per_joule": served["events_per_joule"],
        "per_layer_launch_bytes": byte_rows,
        "dtype_policies": policies,
        "int8_bytes_ratio": bytes_ratio,
        "int8_parity": True,
        "int8_events_per_joule":
            served_q[lp.INT8_NATIVE]["events_per_joule"],
    }
    with open("BENCH_layer_program.json", "w") as f:
        json.dump(out, f, indent=2)
    print("  wrote BENCH_layer_program.json")


if __name__ == "__main__":
    main(fast="--fast" in sys.argv)
