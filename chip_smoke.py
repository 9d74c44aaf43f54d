#!/usr/bin/env python3
"""Smoke run of the served path on a TPU: the paper's DVS-Gesture network.

    python chip_smoke.py [--seed 0]           # one chip
    python chip_smoke.py --mesh [--seed 0]    # four chips: the mesh backend

One chip: the paper's accuracy network (`sne_net.dvs_gesture_net`, Fig. 6:
128x128x2 input, 7 layers, 100 timesteps) with `init_snn(PRNGKey(seed))`
weights put on the paper's layer-shared int4 grid
(`quantize_net(..., per_channel=False)`), served as `qn.spec` with the
float32-carrier weights through `StreamingRuntime` on a 4-slot
`EventServeEngine` under the default `ExecutionPolicy()`.  Eight
synthetic DVS-Gesture recordings are served; one carries a full-frame
burst (every input site fires at one timestep), so the collector's top
event rung (32768) runs too.  Every membrane sum is then a small integer
held exactly in float32, so each request's class counts must equal
`sne_net.dense_apply` on the same net exactly, with zero dropped events.
The requests are served twice through the same engine: the first pass
compiles every window-step program it meets (set-up), the second is the
serving time and must compile nothing.

``--mesh`` runs only the four-chip path: a `MeshEventServeEngine` with 8
slots over 4 chips serves the same requests, and its class counts must
equal the local engine's, in the same process, bit for bit.

Fails (non-zero exit, no result line) when JAX finds no TPU.  The last
line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import use_compile_cache  # noqa: E402

N_REQUESTS = 8
LOCAL_SLOTS = 4
MESH_SLOTS = 8
MESH_CHIPS = 4
BURST = (3, 50)            # (request, timestep) where every site fires
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds XLA spends compiling programs, or fetching them from the
    persistent cache, summed from JAX's monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration


def build(seed: int):
    """The quantized paper network and its eight requests' spikes."""
    import jax
    from repro.core.policies import F32_CARRIER
    from repro.core.quant import quantize_net
    from repro.core.sne_net import dvs_gesture_net, init_snn
    from repro.data.events_ds import DVS_GESTURE, batch_at

    spec = dvs_gesture_net()
    qn = quantize_net(init_snn(jax.random.PRNGKey(seed), spec), spec,
                      per_channel=False)
    spikes, labels = batch_at(seed, 0, N_REQUESTS, DVS_GESTURE)
    spikes = spikes.at[BURST].set(1.0)
    return qn.spec, qn.params_for(F32_CARRIER), spikes, labels


def reference_counts(spec, params, spikes):
    """Class counts of the plain float32 dense reference, per request."""
    import jax
    import numpy as np
    from repro.core.sne_net import dense_apply

    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda x: dense_apply(params, spec, x)[0])
        outs = [np.asarray(fwd(spikes[i])) for i in range(len(spikes))]
    return [o.reshape(-1, o.shape[-1]).sum(axis=0) for o in outs]


def make_requests(spikes):
    import jax.numpy as jnp
    from repro.serve import EventRequest

    n = int(jnp.max(jnp.sum(spikes != 0, axis=(1, 2, 3, 4))))
    cap = -(-n // 8) * 8            # one capacity: one nonzero program
    return [EventRequest.from_dense(i, spikes[i], capacity=cap)
            for i in range(len(spikes))]


def serve(engine, spikes):
    """Serve every request through the streaming runtime; wall seconds."""
    from repro.serve import StreamingRuntime

    reqs = make_requests(spikes)
    rt = StreamingRuntime(engine, queue_capacity=len(reqs))
    rt.submit(reqs)
    t0 = time.perf_counter()
    rep = rt.serve()
    wall = time.perf_counter() - t0
    if rep["completed"] != len(reqs) or not all(r.done for r in reqs):
        raise RuntimeError(f"served {rep['completed']} of {len(reqs)}")
    return reqs, rep, wall


def check(reqs, want, tag: str) -> None:
    """Exact class-count agreement and zero drops, per request."""
    import numpy as np

    bad = []
    for r in reqs:
        t = r.telemetry
        drops = t.input_dropped + int(sum(t.inter_layer_dropped))
        same = np.array_equal(r.class_counts, want[r.uid])
        log(f"{tag} request {r.uid}: events {t.total_events:.0f} "
            f"drops {drops} counts {r.class_counts.astype(int).tolist()} "
            f"equal {same}")
        if not same or drops:
            bad.append(r.uid)
    if bad:
        raise AssertionError(f"{tag}: requests {bad} disagree or dropped")


def kernel_calls(engine) -> int:
    """Pallas kernels in the compiled window step at the top event rung."""
    import jax
    import jax.numpy as jnp

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    W, N, E0 = engine.W, engine.N, engine.caps[0]
    args = (jax.tree.map(sds, engine.params),
            tuple(sds(v) for v in engine.states), sds(engine.class_counts),
            jax.ShapeDtypeStruct((2, N), jnp.int32),
            jax.ShapeDtypeStruct((W, N, E0, 3), jnp.int32),
            jax.ShapeDtypeStruct((W, N, E0), jnp.float32),
            jax.ShapeDtypeStruct((W, N), jnp.float32),
            jax.ShapeDtypeStruct((N,), jnp.int32))
    return engine._step.lower(*args).compile().as_text().count(
        "tpu_custom_call")


def run_one_chip(spec, params, spikes, want, clock) -> None:
    from repro.serve import EventServeEngine, ExecutionPolicy
    from repro.serve.event_engine import event_bucket

    eng = EventServeEngine(spec, params, n_slots=LOCAL_SLOTS,
                           policy=ExecutionPolicy(), donate_buffers=True)
    log(f"engine: {eng.N} slots, window {eng.W}, policy {eng.policy}, "
        f"event capacities {list(eng.caps)}")
    c0 = clock.seconds
    reqs, rep, wall = serve(eng, spikes)
    programs = eng._step._cache_size()
    log(f"set-up pass: {wall:.3f} s wall, compile seconds "
        f"{clock.seconds - c0:.3f}, window-step programs compiled "
        f"{programs}")
    check(reqs, want, "set-up")
    c0, s0 = clock.seconds, dict(eng.stats)
    reqs, rep, wall = serve(eng, spikes)
    if eng._step._cache_size() != programs:
        raise AssertionError("the serving pass compiled new programs")
    log(f"serving pass: {wall:.3f} s wall, compile seconds "
        f"{clock.seconds - c0:.3f}, requests completed {rep['completed']}, "
        f"events served {rep['events_served']}, windows "
        f"{eng.stats['windows'] - s0['windows']}, kernel launches "
        f"{eng.stats['kernel_launches'] - s0['kernel_launches']}, padded "
        f"event slots "
        f"{eng.stats['padded_event_slots'] - s0['padded_event_slots']}")
    check(reqs, want, "serving")
    burst = int(spikes[BURST].sum())
    log(f"burst: request {BURST[0]} timestep {BURST[1]} carries {burst} "
        f"events, so its window runs at Eb = "
        f"{event_bucket(burst, eng.caps[0])} (the top rung)")
    if burst != eng.caps[0]:
        raise AssertionError("the burst does not fill the input")
    n_kernels = kernel_calls(eng)
    log(f"tpu_custom_call in the compiled window step: {n_kernels}")
    if n_kernels < len(eng.program.ops):
        raise AssertionError("the window step holds no Pallas kernels")


def run_mesh(spec, params, spikes, want, clock) -> None:
    import jax
    import numpy as np
    from repro.serve import EventServeEngine, ExecutionPolicy

    if len(jax.devices()) != MESH_CHIPS:
        raise RuntimeError(f"--mesh needs {MESH_CHIPS} chips, JAX sees "
                           f"{len(jax.devices())}")
    local = EventServeEngine(spec, params, n_slots=LOCAL_SLOTS,
                             policy=ExecutionPolicy(), donate_buffers=True)
    mesh = EventServeEngine(spec, params, n_slots=MESH_SLOTS,
                            policy=ExecutionPolicy(backend="mesh"),
                            donate_buffers=True)
    homes = {d for sh in mesh.shards for v in sh.states
             for d in v.devices()}
    log(f"mesh engine: {mesh.D} shards x {mesh.spd} slots; shard states "
        f"on {len(homes)} distinct devices")
    if mesh.D != MESH_CHIPS or len(homes) != MESH_CHIPS:
        raise AssertionError("shard states are not on 4 distinct devices")
    c0 = clock.seconds
    ref, _, wall = serve(local, spikes)
    log(f"local pass ({LOCAL_SLOTS} slots, 1 chip): {wall:.3f} s wall, "
        f"compile seconds {clock.seconds - c0:.3f}")
    check(ref, want, "local")
    c0 = clock.seconds
    reqs, rep, wall = serve(mesh, spikes)
    log(f"mesh pass ({MESH_SLOTS} slots, {MESH_CHIPS} chips): {wall:.3f} "
        f"s wall, compile seconds {clock.seconds - c0:.3f}, requests "
        f"completed {rep['completed']}, events served "
        f"{rep['events_served']}, fused mesh windows "
        f"{mesh.stats['mesh_global_windows']}, per-shard windows "
        f"{mesh.stats['mesh_shard_windows']}")
    check(reqs, want, "mesh")
    local_counts = {r.uid: r.class_counts for r in ref}
    same = all(np.array_equal(r.class_counts, local_counts[r.uid])
               for r in reqs)
    log(f"mesh class counts bitwise equal to the local engine: {same}")
    if not same:
        raise AssertionError("mesh and local engines disagree")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", action="store_true",
                    help="run only the 4-chip mesh backend and its "
                    "comparison with the local engine")
    args = ap.parse_args()

    cache = use_compile_cache()
    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is "
              f"{jax.default_backend()!r}); nothing was run",
              file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind} x {len(jax.devices())}; "
        f"compile cache {cache}")
    clock = CompileClock(jax)
    spec, params, spikes, labels = build(args.seed)
    want = reference_counts(spec, params, spikes)
    log(f"net: dvs_gesture_net {spec.in_shape} x {spec.n_timesteps} "
        f"timesteps, {len(spec.layers)} layers, int4 grid, seed "
        f"{args.seed}; {N_REQUESTS} requests, labels "
        f"{[int(x) for x in labels]}")
    if args.mesh:
        run_mesh(spec, params, spikes, want, clock)
    else:
        run_one_chip(spec, params, spikes, want, clock)
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
