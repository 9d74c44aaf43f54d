"""Serve concurrent DVS event streams through the slot-batched engine.

    PYTHONPATH=src python examples/serve_events.py [--requests 8] \
        [--slots 4] [--window 4] [--oracle] [--no-idle-skip] \
        [--dtype-policy int8-native] [--fusion-policy per-step] \
        [--backend mesh]
    PYTHONPATH=src python examples/serve_events.py --source file \
        [--file path/to/recording.npz|.aedat] [--speedup 2000]
    PYTHONPATH=src python examples/serve_events.py --mode streaming \
        [--arrival-rate 200] [--queue-cap 16] [--slo-ms 500]

Two sources:

  * ``--source synthetic`` (default): tiny synthetic DVS recordings are
    admitted all at once into the fixed-slot event engine.
  * ``--source file``: a real recording (AEDAT3.1 or the portable .npz
    event format; default = the bundled sample) is segmented into
    per-inference requests and *replayed at sensor pace* — the ReplayClient
    admits each segment at its recording-relative arrival time and paces
    engine windows to (scaled) sensor time.

All active slots advance together through the jitted per-window step
(fused windows by default: ONE Pallas launch per layer per window); with
the window-level idle skip (default on) all-idle (slot, window) pairs
bypass the batched Pallas launch entirely and their leak is applied
analytically.  ``--dtype-policy int8-native`` quantizes the net
(`core.quant.quantize_net`) and serves it on the native integer datapath;
``--fusion-policy per-step`` selects the launch-per-timestep oracle
lowering and ``--fusion-policy fused-network`` the whole-network
megakernel (ONE launch per window); ``--backend mesh`` shards the slot
axis across the visible JAX
devices (simulate some on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``) — the four knobs
together form the `repro.serve.ExecutionPolicy` the engine is built
with.  Each completed inference reports its measured event counts
mapped through the analytic SNE hardware model — latency, energy, and
activity per request.

``--mode streaming`` serves the same requests through the
double-buffered `StreamingRuntime` instead of the synchronous ``run``
loop: arrivals follow an open-loop Poisson process at ``--arrival-rate``
requests/s (the source — synthetic batch or segmented recording — only
decides the payloads), admission is a bounded queue (``--queue-cap``)
with graceful rejection, and ``--slo-ms`` attaches a deadline to every
request (expiry in queue, eviction mid-service).  The engine runs with
donated device buffers and reports sustained events/s plus window-
latency percentiles alongside the analytic telemetry.

This example's flags mirror `ExecutionPolicy`'s axes and the runtimes'
constructor kwargs; CI runs it under both policies and both modes so the
surfaces cannot drift apart.  Everything imports from the curated
`repro.serve` public API.
"""
import argparse
import time

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.policies import (BACKENDS, BACKEND_LOCAL, DTYPE_POLICIES,
                                 F32_CARRIER, FUSED_WINDOW, FUSION_POLICIES,
                                 INT8_NATIVE)
from repro.core.quant import quantize_net
from repro.core.sne_net import init_snn, tiny_net
from repro.data.events_ds import (TINY, ReplayClient, batch_at,
                                  load_recording, sample_recording_path,
                                  segment_recording)
from repro.serve import (EventRequest, EventServeEngine, ExecutionPolicy,
                         PoissonLoadGen, StreamingRuntime,
                         proportionality_r2, summarize)


def main():
    use_compile_cache()   # before anything compiles
    ap = argparse.ArgumentParser()
    ap.add_argument("--source", choices=("synthetic", "file"),
                    default="synthetic")
    ap.add_argument("--file", default=None,
                    help="recording path (.npz/.aedat); default = bundled "
                    "sample (requires --source file)")
    ap.add_argument("--window-us", type=int, default=1000,
                    help="sensor time per timestep bin (file source)")
    ap.add_argument("--speedup", type=float, default=2000.0,
                    help="replay pace: sensor time / wall time (file source)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--window", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--oracle", action="store_true",
                    help="use the pure-jnp kernel oracle instead of the "
                    "Pallas kernel (interpret mode on CPU)")
    ap.add_argument("--no-idle-skip", action="store_true",
                    help="step every window densely (the pre-skip engine)")
    ap.add_argument("--tile-sparsity", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="skip cold spatial tiles inside the window kernels "
                    "(bitwise invisible; --no-tile-sparsity runs every tile "
                    "densely, the pre-bitmap kernels)")
    ap.add_argument("--dtype-policy", choices=DTYPE_POLICIES,
                    default=F32_CARRIER,
                    help="datapath dtype domain; int8-native quantizes the "
                    "net and serves int8 codes/storage (paper §III-D4)")
    ap.add_argument("--fusion-policy", choices=FUSION_POLICIES,
                    default=FUSED_WINDOW,
                    help="window lowering: fused-window (one launch per "
                    "layer per window, default), the per-step oracle, or "
                    "fused-network (the whole network in ONE megakernel "
                    "launch per window, VMEM budget permitting)")
    ap.add_argument("--backend", choices=BACKENDS, default=BACKEND_LOCAL,
                    help="local = single-device engine (the parity "
                    "oracle); mesh = slot axis sharded across the visible "
                    "JAX devices with per-shard idle-skip compaction")
    ap.add_argument("--weights", choices=("random", "trained"),
                    default="random",
                    help="random = init_snn(seed) synthetic weights; "
                    "trained = the bundled surrogate-gradient-trained "
                    "tiny-gesture checkpoint "
                    "(train/snn_loop.load_trained_tiny)")
    ap.add_argument("--mode", choices=("sync", "streaming"), default="sync",
                    help="sync = EventServeEngine.run (the parity oracle); "
                    "streaming = the double-buffered StreamingRuntime under "
                    "open-loop Poisson load")
    ap.add_argument("--arrival-rate", type=float, default=200.0,
                    help="streaming: Poisson arrival rate, requests/s")
    ap.add_argument("--queue-cap", type=int, default=16,
                    help="streaming: bounded admission queue capacity")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="streaming: per-request SLO deadline; past it a "
                    "queued request expires and a running one is evicted")
    args = ap.parse_args()

    if args.weights == "trained":
        from repro.train.snn_loop import load_trained_tiny
        spec, params, meta = load_trained_tiny()
        print(f"=== trained checkpoint: {int(meta['steps'])} steps, "
              f"eval acc {float(meta['eval_acc']):.3f}, "
              f"qat={bool(meta['qat'])} ===")
        # serve what training saw: the layer-shared int4 grid
        qn = quantize_net(params, spec, per_channel=False)
        spec, params = qn.spec, qn.params_for(args.dtype_policy)
    else:
        spec = tiny_net()
        params = init_snn(jax.random.PRNGKey(args.seed), spec)
        if args.dtype_policy == INT8_NATIVE:
            qn = quantize_net(params, spec)
            spec, params = qn.spec, qn.params_for(args.dtype_policy)
    policy = ExecutionPolicy(dtype_policy=args.dtype_policy,
                             fusion_policy=args.fusion_policy,
                             idle_skip=not args.no_idle_skip,
                             tile_sparsity=args.tile_sparsity,
                             backend=args.backend)
    eng = EventServeEngine(spec, params, n_slots=args.slots,
                           window=args.window,
                           use_pallas=False if args.oracle else None,
                           policy=policy,
                           donate_buffers=(args.mode == "streaming"))
    if args.backend != BACKEND_LOCAL:
        print(f"=== mesh backend: {eng.D} shard(s) x {eng.spd} slot(s) "
              f"over {jax.device_count()} visible device(s) ===")

    labels = None
    client = None
    if args.source == "file":
        path = args.file or sample_recording_path()
        rec = load_recording(path)
        reqs = segment_recording(rec, spec.in_shape, spec.n_timesteps,
                                 args.window_us)
        if args.mode == "sync":
            client = ReplayClient(reqs, spec.n_timesteps, args.window_us,
                                  speedup=args.speedup)
        print(f"=== replaying {rec.name}: {rec.n_events} events / "
              f"{rec.duration_us / 1e3:.0f} ms -> {len(reqs)} segment "
              f"requests ({args.slots} slots, window {args.window}, "
              f"mode {args.mode}, "
              f"idle_skip={'on' if eng.idle_skip else 'off'}) ===")
    else:
        spikes, labels = batch_at(args.seed, 0, args.requests, TINY)
        reqs = [EventRequest.from_dense(i, spikes[i])
                for i in range(args.requests)]
        print(f"=== serving {args.requests} event streams "
              f"({args.slots} slots, window {args.window}, "
              f"{'oracle' if args.oracle else 'pallas'}, mode {args.mode}, "
              f"idle_skip={'on' if eng.idle_skip else 'off'}) ===")

    t0 = time.time()
    rep = None
    if args.mode == "streaming":
        rt = StreamingRuntime(eng, queue_capacity=args.queue_cap)
        lg = PoissonLoadGen(
            reqs, rate_hz=args.arrival_rate, seed=args.seed,
            slo_s=args.slo_ms / 1e3 if args.slo_ms is not None else None)
        rep = rt.serve(lg)
    elif client is not None:
        client.run(eng)
    else:
        eng.run(reqs)
    dt = time.time() - t0
    if args.mode == "sync":
        assert all(r.done for r in reqs)
    reqs = [r for r in reqs if r.done]   # streaming may shed load (by SLO)

    print(f"{'req':>4} {'pred':>4} {'label':>5} {'events':>8} {'act%':>6} "
          f"{'sne_ms':>7} {'par_ms':>7} {'uJ':>7} {'drops':>5} {'skipW':>5}")
    labels = np.asarray(labels) if labels is not None else None
    for r in reqs:
        lab = labels[r.uid] if labels is not None else None
        t = r.telemetry
        print(f"{r.uid:>4} {r.prediction:>4} "
              f"{'-' if lab is None else int(lab):>5} "
              f"{t.total_events:>8.0f} {t.activity * 100:>6.2f} "
              f"{t.sne_time_s * 1e3:>7.2f} {t.sne_time_par_s * 1e3:>7.2f} "
              f"{t.sne_energy_j * 1e6:>7.2f} "
              f"{t.input_dropped + int(sum(t.inter_layer_dropped)):>5} "
              f"{t.n_skipped_windows:>5}")

    agg = summarize([r.telemetry for r in reqs])
    slot_ts = eng.stats["windows"] * args.window * args.slots
    occ = (sum(r.n_timesteps for r in reqs) / slot_ts) if slot_ts else 0.0
    skipped = eng.stats["skipped_slot_windows"]
    total_sw = skipped + eng.stats["dense_slot_windows"]
    print(f"done in {dt:.2f}s wall | {eng.stats['windows']} windows | "
          f"mean occupancy {occ:.2f} | idle-skipped {skipped}/{total_sw} "
          f"slot-windows | {eng.stats['kernel_launches']} kernel launches")
    if client is not None:
        print(f"replay: slept {client.stats['slept_s']:.2f}s of "
              f"{client.stats['wall_s']:.2f}s wall "
              f"({client.stats['stalled_windows']} stalled windows)")
    if rep is not None:
        print(f"streaming: {rep['completed']} completed | "
              f"{rep['rejected_queue_full']} rejected | "
              f"{rep['expired_in_queue']} expired | "
              f"{rep['evicted_deadline']} evicted | sustained "
              f"{rep['sustained_events_per_s']:.0f} events/s")
        print(f"streaming: window p50/p99 "
              f"{rep['p50_window_latency_ms']:.2f}/"
              f"{rep['p99_window_latency_ms']:.2f} ms | e2e p99 "
              f"{rep['p99_e2e_latency_ms']:.2f} ms | mean queue depth "
              f"{rep['mean_queue_depth']:.2f} | padding waste "
              f"x{rep['padding']['padding_waste_ratio']:.2f}")
    if reqs:
        print(f"modeled: {agg['modeled_rate_hz']:.0f} inf/s | "
              f"{agg['mean_sne_energy_j'] * 1e6:.2f} uJ/inf | "
              f"energy-vs-events R^2 = "
              f"{proportionality_r2([r.telemetry for r in reqs]):.5f}")
    else:
        # streaming under a tight SLO can shed every request
        print("modeled: no completed requests (all load shed)")


if __name__ == "__main__":
    main()
