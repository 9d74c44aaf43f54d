"""Count the host-to-device bytes of a mesh engine run on four CPU
devices; prints one JSON line (``tests/test_serve_spans.py`` runs it).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python tests/h2d_mesh_check.py

Every host array handed to ``jax.device_put`` while the runtime serves is
summed, under a guard that refuses any other host-to-device transfer, and
set beside the engine's ``h2d_bytes`` growth over the same run.
"""
from __future__ import annotations

import json

import jax
import numpy as np


def main() -> None:
    from repro.core.policies import ExecutionPolicy
    from repro.core.sne_net import init_snn, tiny_net
    from repro.serve.event_engine import EventServeEngine
    from repro.serve.runtime import (ManualClock, StreamingRuntime,
                                     requests_synthetic)

    spec = tiny_net()
    eng = EventServeEngine(spec, init_snn(jax.random.PRNGKey(0), spec),
                           n_slots=4, window=4, use_pallas=False,
                           donate_buffers=True,
                           policy=ExecutionPolicy(backend="mesh"))
    rt = StreamingRuntime(eng, queue_capacity=8, clock=ManualClock())
    rt.submit(requests_synthetic(4, seed=3))
    for _ in range(2):                     # compile outside the guard
        rt.tick()
    put = jax.device_put
    seen = {"bytes": 0}

    def recording(x, *a, **k):
        seen["bytes"] += sum(leaf.nbytes for leaf in jax.tree.leaves(x)
                             if isinstance(leaf, (np.ndarray, np.generic)))
        return put(x, *a, **k)

    jax.device_put = recording
    before = dict(eng.stats)
    with jax.transfer_guard_host_to_device("disallow"):
        rep = rt.serve()
    jax.device_put = put
    after = eng.stats
    print(json.dumps({
        "devices": eng.D, "completed": rep["completed"],
        "fused_windows": after["mesh_global_windows"]
        - before["mesh_global_windows"],
        "counted": after["h2d_bytes"] - before["h2d_bytes"],
        "put": seen["bytes"]}))


if __name__ == "__main__":
    main()
