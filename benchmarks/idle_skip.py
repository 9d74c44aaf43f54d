"""Idle-fraction sweep: the serving engine's window-level lazy skip.

The paper's core claim is energy-to-information proportionality — idle
time must cost (near) nothing.  This benchmark serves request cohorts
whose input events are confined to a shrinking set of *active windows*
(the idle fraction of windows carries zero events, aligned across slots)
and checks that the serving path actually honours the claim:

  * per-inference step wall time decreases monotonically as idle rises
    (skipped windows never reach the batched kernel);
  * modeled SNE energy decreases monotonically (skipped timesteps pay
    neither event cycles nor the boundary FIRE sweep —
    ``SneConfig.cycles_per_boundary`` is set to the TDM depth here);
  * at 90% idle the skip path performs >= 2x fewer kernel launches than
    the dense path on the identical workload (measured: ~8x at this
    configuration);
  * results stay bit-for-bit equal to the dense path (spot-checked per
    sweep point on request 0's class counts).

A second, *spatial* sweep exercises the orthogonal axis — tile-level
spatial sparsity plus adaptive event bucketing: cohorts whose events are
confined to a shrinking sub-square (constant event density, every window
active) must show collector launch bytes (the adaptive ``Eb`` ladder
at work) and wall time both falling monotonically, bitwise equal to the ``tile_sparsity=False`` path, with
``padding_waste()`` beating the power-of-two counterfactual.

Emits ``BENCH_idle_skip.json`` for CI's regression gate
(`benchmarks/check_regression.py`).

    PYTHONPATH=src python -m benchmarks.idle_skip [--fast] [--pallas]
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import SneConfig
from repro.core.policies import ExecutionPolicy
from repro.core.sne_net import init_snn, tiny_net
from repro.serve.event_engine import EventRequest, EventServeEngine
from repro.serve.telemetry import summarize

# model the per-timestep FIRE sweep so skipped boundaries show up in energy
# (64 = one cycle per TDM neuron; 0 would make energy blind to the skip)
CFG = SneConfig(cycles_per_boundary=64)


def make_idle_requests(idle_frac: float, n_requests: int, n_timesteps: int,
                       window: int, in_shape, events_per_step: int = 12,
                       seed: int = 0):
    """Cohort whose events live only in the active windows.

    The active-window set is shared by every request so idle windows align
    across slots (a DVS array watching the same scene goes quiet
    together); per-active-timestep event count is fixed, so total events
    scale with ``1 - idle_frac``.
    """
    H, W, C = in_shape
    n_win = n_timesteps // window
    n_active = max(1, int(round((1.0 - idle_frac) * n_win)))
    active = sorted(np.linspace(0, n_win - 1, n_active).round().astype(int))
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n_requests):
        spikes = np.zeros((n_timesteps, H, W, C), np.float32)
        for w in active:
            for t in range(w * window, (w + 1) * window):
                spikes[t, rng.integers(0, H, events_per_step),
                       rng.integers(0, W, events_per_step),
                       rng.integers(0, C, events_per_step)] = 1.0
        reqs.append(EventRequest.from_dense(uid, jnp.asarray(spikes)))
    return reqs


def serve(eng: EventServeEngine, reqs) -> dict:
    before = dict(eng.stats)
    t0 = time.time()
    eng.run(reqs)
    wall = time.time() - t0
    assert all(r.done for r in reqs)
    agg = summarize([r.telemetry for r in reqs])
    return {
        "wall_s": wall,
        "wall_per_inf_s": wall / len(reqs),
        "kernel_launches": eng.stats["kernel_launches"]
        - before["kernel_launches"],
        "step_calls": eng.stats["step_calls"] - before["step_calls"],
        "skipped_slot_windows": eng.stats["skipped_slot_windows"]
        - before["skipped_slot_windows"],
        "dense_slot_windows": eng.stats["dense_slot_windows"]
        - before["dense_slot_windows"],
        "launch_bytes": eng.stats["launch_bytes"] - before["launch_bytes"],
        "events": agg["total_events"],
        "energy_j": agg["mean_sne_energy_j"] * agg["n_requests"],
        "events_per_joule": agg["events_per_joule"],
        "class_counts0": [float(v) for v in reqs[0].class_counts],
    }


def sweep(idle_fracs=(0.0, 0.5, 0.75, 0.9), n_requests: int = 4,
          n_timesteps: int = 32, window: int = 4, use_pallas=False,
          seed: int = 0, repeats: int = 3):
    spec = tiny_net()
    params = init_snn(jax.random.PRNGKey(seed), spec)

    def mk(skip):
        return EventServeEngine(spec, params, n_slots=n_requests,
                                window=window, sne_cfg=CFG,
                                use_pallas=use_pallas,
                                policy=ExecutionPolicy(idle_skip=skip))

    eng = mk(True)
    eng_dense = mk(False)

    def requests(frac):
        return make_idle_requests(frac, n_requests, n_timesteps, window,
                                  spec.in_shape, seed=seed)

    # warmup pass populates every jit-shape bucket so the measured pass
    # times steady-state serving, not compilation
    for frac in idle_fracs:
        serve(eng, requests(frac))
        serve(eng_dense, requests(frac))

    rows = []
    for frac in idle_fracs:
        # min over repeats: the standard robust wall-clock estimator (the
        # counters and modeled energy are deterministic across repeats)
        trials = [serve(eng, requests(frac)) for _ in range(repeats)]
        dtrials = [serve(eng_dense, requests(frac)) for _ in range(repeats)]
        r, d = trials[-1], dtrials[-1]
        r["wall_per_inf_s"] = min(t["wall_per_inf_s"] for t in trials)
        d["wall_per_inf_s"] = min(t["wall_per_inf_s"] for t in dtrials)
        assert r["class_counts0"] == d["class_counts0"], \
            f"idle-skip diverged from dense path at idle={frac}"
        assert r["events"] == d["events"]
        r.update({
            "idle_frac": frac,
            "dense_wall_per_inf_s": d["wall_per_inf_s"],
            "dense_kernel_launches": d["kernel_launches"],
            "dense_energy_j": d["energy_j"],
            "launch_ratio": d["kernel_launches"] / max(r["kernel_launches"],
                                                       1),
        })
        rows.append(r)
    return rows


def make_spatial_requests(spatial_frac: float, n_requests: int,
                          n_timesteps: int, in_shape,
                          peak_events_per_step: int = 48, seed: int = 0):
    """Cohort whose events live in a shrinking top-left sub-square.

    A DVS watching a smaller moving object: the active region covers
    ``spatial_frac`` of the array and the per-timestep event count scales
    with it (constant event *density*), so both the collector's adaptive
    buckets and the layer-0 tile bitmap genuinely shrink.  Every timestep
    stays active — this sweep isolates the spatial axis from the
    window-level idle skip.
    """
    H, W, C = in_shape
    side = np.sqrt(spatial_frac)
    sh, sw = max(1, round(H * side)), max(1, round(W * side))
    draws = max(3, round(peak_events_per_step * spatial_frac))
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n_requests):
        spikes = np.zeros((n_timesteps, H, W, C), np.float32)
        for t in range(n_timesteps):
            spikes[t, rng.integers(0, sh, draws),
                   rng.integers(0, sw, draws),
                   rng.integers(0, C, draws)] = 1.0
        reqs.append(EventRequest.from_dense(uid, jnp.asarray(spikes)))
    return reqs


def spatial_sweep(spatial_fracs=(1.0, 0.5, 0.25, 0.1), n_requests: int = 4,
                  n_timesteps: int = 24, window: int = 4, use_pallas=False,
                  seed: int = 0, repeats: int = 3):
    """Tile-sparsity sweep: launch bytes + wall vs the active region."""
    spec = tiny_net()
    params = init_snn(jax.random.PRNGKey(seed), spec)

    def mk(tiles):
        return EventServeEngine(spec, params, n_slots=n_requests,
                                window=window, sne_cfg=CFG,
                                use_pallas=use_pallas,
                                policy=ExecutionPolicy(tile_sparsity=tiles))

    eng = mk(True)
    eng_dense = mk(False)

    def requests(frac):
        return make_spatial_requests(frac, n_requests, n_timesteps,
                                     spec.in_shape, seed=seed)

    for frac in spatial_fracs:                                   # warmup
        serve(eng, requests(frac))
        serve(eng_dense, requests(frac))

    rows = []
    for frac in spatial_fracs:
        trials = [serve(eng, requests(frac)) for _ in range(repeats)]
        dtrials = [serve(eng_dense, requests(frac)) for _ in range(repeats)]
        r, d = trials[-1], dtrials[-1]
        r["wall_per_inf_s"] = min(t["wall_per_inf_s"] for t in trials)
        # the tile bitmaps are bitwise invisible on the identical workload
        assert r["class_counts0"] == d["class_counts0"], \
            f"tile sparsity diverged from the dense path at frac={frac}"
        assert r["events"] == d["events"]
        assert r["launch_bytes"] == d["launch_bytes"]  # same adaptive Eb
        r.update({
            "spatial_frac": frac,
            "dense_wall_per_inf_s": min(t["wall_per_inf_s"]
                                        for t in dtrials),
        })
        rows.append(r)
    return rows, eng.padding_waste()


def main(fast: bool = False, use_pallas: bool = False) -> None:
    print("idle_skip [window-level lazy TLU skip at serving scale]")
    # 24 (not 16) in fast mode keeps every sweep point's active-window
    # count distinct, so the strict energy-monotonicity assert stays sharp
    n_ts = 24 if fast else 32
    rows = sweep(n_timesteps=n_ts, use_pallas=use_pallas)
    print(f"  {'idle':>5} {'events':>7} {'launches':>8} {'dense':>6} "
          f"{'ratio':>6} {'skipW':>6} {'ms/inf':>8} {'dense':>8} "
          f"{'uJ':>8} {'ev/J':>10}")
    for r in rows:
        print(f"  {r['idle_frac']:>5.2f} {r['events']:>7.0f} "
              f"{r['kernel_launches']:>8} {r['dense_kernel_launches']:>6} "
              f"{r['launch_ratio']:>6.1f} {r['skipped_slot_windows']:>6} "
              f"{r['wall_per_inf_s'] * 1e3:>8.2f} "
              f"{r['dense_wall_per_inf_s'] * 1e3:>8.2f} "
              f"{r['energy_j'] * 1e6:>8.3f} {r['events_per_joule']:>10.3e}")

    # the idle-costs-nothing claims, asserted
    walls = [r["wall_per_inf_s"] for r in rows]
    energies = [r["energy_j"] for r in rows]
    launches = [r["kernel_launches"] for r in rows]
    for i in range(1, len(rows)):
        # wall time: monotone within a 10% scheduler-jitter guard
        assert walls[i] <= walls[i - 1] * 1.10, \
            (rows[i - 1]["idle_frac"], rows[i]["idle_frac"], walls)
        assert energies[i] < energies[i - 1], energies
        assert launches[i] <= launches[i - 1], launches
    assert walls[-1] < walls[0], walls
    hi = rows[-1]
    assert hi["idle_frac"] >= 0.9
    assert hi["launch_ratio"] >= 2.0, hi["launch_ratio"]
    # skipping must also beat the dense path's *energy* (boundary sweeps)
    assert hi["energy_j"] < hi["dense_energy_j"], \
        (hi["energy_j"], hi["dense_energy_j"])
    print(f"  90% idle: {hi['launch_ratio']:.1f}x fewer launches, "
          f"{walls[0] / walls[-1]:.1f}x faster per inference, "
          f"{hi['dense_energy_j'] / hi['energy_j']:.2f}x less modeled "
          f"energy than dense")

    # --- spatial axis: tile sparsity + adaptive event bucketing ----------
    print("  spatial sweep [tile bitmaps + adaptive collector buckets]")
    srows, waste = spatial_sweep(n_timesteps=n_ts, use_pallas=use_pallas)
    print(f"  {'frac':>5} {'events':>7} {'bytes':>9} "
          f"{'ms/inf':>8} {'dense':>8}")
    for r in srows:
        print(f"  {r['spatial_frac']:>5.2f} "
              f"{r['events']:>7.0f} {r['launch_bytes']:>9} "
              f"{r['wall_per_inf_s'] * 1e3:>8.2f} "
              f"{r['dense_wall_per_inf_s'] * 1e3:>8.2f}")
    s_bytes = [r["launch_bytes"] for r in srows]
    s_walls = [r["wall_per_inf_s"] for r in srows]
    for i in range(1, len(srows)):
        # bytes fall strictly with the active region (adaptive Eb is
        # deterministic); wall within the jitter guard
        assert s_bytes[i] < s_bytes[i - 1], s_bytes
        assert s_walls[i] <= s_walls[i - 1] * 1.10, s_walls
    assert s_walls[-1] < s_walls[0], s_walls
    # adaptive bucketing must beat the pow2 counterfactual it replaced
    assert waste["padding_waste_improvement"] > 1.0, waste
    print(f"  spatial: {s_bytes[0] / s_bytes[-1]:.1f}x fewer launch bytes, "
          f"{s_walls[0] / s_walls[-1]:.1f}x faster per inference; "
          f"padding waste "
          f"{waste['padding_waste_improvement']:.2f}x better than pow2")

    out = {
        "bench": "idle_skip",
        "config": {"n_timesteps": n_ts, "window": 4, "slots": 4,
                   "cycles_per_boundary": CFG.cycles_per_boundary,
                   "use_pallas": bool(use_pallas)},
        "rows": [{k: v for k, v in r.items() if k != "class_counts0"}
                 for r in rows],
        "spatial_rows": [{k: v for k, v in r.items()
                          if k != "class_counts0"} for r in srows],
        "events_per_joule": rows[0]["events_per_joule"],
        "launch_ratio_90": hi["launch_ratio"],
        "spatial_bytes": s_bytes,
        "padding_waste_improvement": waste["padding_waste_improvement"],
    }
    with open("BENCH_idle_skip.json", "w") as f:
        json.dump(out, f, indent=2)
    print("  wrote BENCH_idle_skip.json")


if __name__ == "__main__":
    main(fast="--fast" in sys.argv, use_pallas="--pallas" in sys.argv)
