"""Run the tiny network on the mesh backend over four CPU devices, sound
and with the exchange between chips left out; prints one JSON line.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python -m bench.tests.mesh_check

The fault: after each fused mesh step, the results of shards 1..3 are not
handed back to them (each keeps its state from before the step), so only
shard 0's slots advance.
"""
from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main() -> None:
    import jax

    from bench import run
    from repro.serve.mesh_engine import MeshEventServeEngine

    cfg = json.loads((HERE / "tiny_ecnn.json").read_text())
    cfg["program"] = dict(cfg["program"], slots=4,
                          policy=dict(cfg["program"]["policy"],
                                      backend="mesh"))
    mix = {"arrivals": "backlog", "activity_band": [0.05, 0.08],
           "pool_size": 4, "n_blobs": 1, "warm_requests": 4}
    cell = {"name": "gesture-mesh4-backlog", "chips": 4}

    def once():
        res = run.run_cell(cell, cfg, mix, 2 ** 33 + 7, 0.3, False, [], [],
                           devices=jax.devices()[:4])
        return {"correct": res["correct"],
                "checks": {n["name"]: n["value"] for n in res["checks"]}}

    out = {"sound": once()}
    launch = MeshEventServeEngine._launch_global

    def no_exchange(self, cols, dense):
        kept = [(sh.states, sh.class_counts) for sh in self.shards[1:]]
        win = launch(self, cols, dense)
        for sh, (states, cc) in zip(self.shards[1:], kept):
            sh.states, sh.class_counts = states, cc
        return win

    MeshEventServeEngine._launch_global = no_exchange
    out["no_exchange"] = once()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
