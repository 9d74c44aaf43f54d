"""Count the mesh engine's compacted windows on four CPU devices; prints
one JSON line (``tests/test_event_serving.py`` runs it).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python tests/compact_mesh_check.py

Eight slots over four shards of two.  Two cohorts of two-window requests
(every timestep busy): slots {0, 1, 2}, which leaves shards 2 and 3 idle
and so runs per-shard windows (shard 0 full, shard 1 compacted), and
slots {0, 2, 4, 6}, one busy slot a shard, which runs the fused mesh
step.  For each, the growth of the mesh's ``compacted_windows``, the sum
of its shards' own counters, and the window counters.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np


def busy_request(uid: int, spec, T: int, seed: int):
    """A request with six input events at every one of ``T`` timesteps."""
    from repro.serve.event_engine import EventRequest

    H, W, C = spec.in_shape
    rng = np.random.default_rng(seed)
    s = np.zeros((T, H * W * C), np.float32)
    for t in range(T):
        s[t, rng.choice(H * W * C, size=6, replace=False)] = 1.0
    return EventRequest.from_dense(uid, jnp.asarray(s.reshape(T, H, W, C)))


def main() -> None:
    from repro.core.policies import ExecutionPolicy
    from repro.core.sne_net import init_snn, tiny_net
    from repro.serve.event_engine import EventServeEngine

    spec = tiny_net()
    eng = EventServeEngine(spec, init_snn(jax.random.PRNGKey(0), spec),
                           n_slots=8, window=4, use_pallas=False,
                           policy=ExecutionPolicy(backend="mesh"))
    keys = ("step_calls", "mesh_shard_windows", "mesh_global_windows")
    out = {"devices": eng.D}
    for name, slots in (("per_shard", (0, 1, 2)), ("fused", (0, 2, 4, 6))):
        before = dict(eng.stats)
        shards = sum(sh.stats["compacted_windows"] for sh in eng.shards)
        for s in slots:
            assert eng.try_admit(busy_request(s, spec, 8, seed=s), slot=s)
        while eng.step():
            pass
        after = eng.stats
        row = {"compacted": after["compacted_windows"]
               - before["compacted_windows"],
               "shard_sum": sum(sh.stats["compacted_windows"]
                                for sh in eng.shards) - shards}
        row.update({k: after[k] - before[k] for k in keys})
        out[name] = row
    print(json.dumps(out))


if __name__ == "__main__":
    main()
