"""The trace reduction on a small trace recorded on a TPU v5e chip: a
0.3 s traced window of the program's N-MNIST-sized network under a
backlog (``bench/run.py --trace 1 --keep-trace``), committed gzipped under
``data/`` with the network's geometry (``data/nmnist_net.json``)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
KINDS = ["conv", "pool", "conv", "pool", "fc"]     # nmnist's layers
SPANS = {"admit", "collect", "launch", "retire", "wait_arrival",
         "host.other"}


@pytest.fixture(scope="module")
def reduced():
    import gzip

    import jax

    raw = gzip.decompress((DATA / "nmnist_window.xplane.pb.gz").read_bytes())
    space = jax.profiler.ProfileData.from_serialized_xspace(raw)
    return trace.reduce_space(space, n_devices=1, n_kernels=len(KINDS))


def test_window_and_busy_time(reduced):
    assert 0.25 < reduced["window_s"] < 0.5
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    idle = reduced["idle_share"][0]
    assert idle == pytest.approx(1 - reduced["busy_s"] / reduced["window_s"])


def test_kernels_attributed_by_order(reduced):
    assert reduced["steps"] > 0
    for name, kind in zip(reduced["kernel_names"], KINDS):
        assert name.startswith(f"event_{kind}_window_pallas")
    per_layer = sum(reduced["kernel_s"])
    assert 0 < per_layer <= reduced["kernel_total_s"] <= reduced["busy_s"]
    assert reduced["step_xla_s"] > 0


def test_gaps_and_host_spans(reduced):
    gaps = reduced["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert {n for n, _ in gaps} <= SPANS
    assert all(a >= b for (_, a), (_, b) in zip(gaps, gaps[1:]))
    assert reduced["host_spans"]["bench.collect"][0] > 0
    ops = reduced["breakdown"]["device_ops"]
    assert 0 < len(ops) <= 10
    assert ops[0][0].startswith("kernel")


def test_union_and_clip():
    assert trace.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert trace.union([(0, 1), (2, 3)]) == [(0, 1), (2, 3)]
    assert trace.clip([(0, 5), (6, 7)], 1, 6) == [(1, 5)]
    assert trace.op_name("%sort.11 = (s32[4]) sort(...)") == "sort.11"


def test_every_reader_reads_the_trace(reduced):
    """Each per-layer reader of BENCHMARK.json returns a number or None,
    and a share stays within [0, 100]."""
    import json
    import types

    import numpy as np

    from bench import run, stats
    from bench.configs import ecnn_reference as ref

    root = DATA.parents[2]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((DATA / "nmnist_net.json").read_text())
    layers = ref.layer_shapes(cfg)
    T = cfg["n_timesteps"]
    window = run.Window()
    window.t0, window.t1 = 0.0, reduced["window_s"]
    window.launched = [{0: (0, 4), 1: (4, 8)}, {0: (4, 8)}]
    counters = {"windows": 2, "launched_events": 700,
                "padded_event_slots": 1000, "mesh_global_windows": 3,
                "mesh_shard_windows": 1}
    ctx = stats.Context(
        cfg=cfg, layers=layers, reference=ref, mix={},
        pool=types.SimpleNamespace(n_timesteps=T, counts=np.asarray([70])),
        payload_of={0: 0, 1: 0}, spikes={0: np.ones((T, len(layers)))},
        window=window, trace=reduced,
        counters={"t0": dict.fromkeys(counters, 0), "t1": counters},
        outcomes=[{"uid": 0, "arrival_s": 0.01, "admit_s": 0.02}],
        late_s=[0.001], chips=1, device_kind="TPU v5 lite", n_slots=16)
    for m in bench["per_layer"]:
        path = root / "bench" / "metrics" / f"{m['name']}.py"
        v = run.load_module(path, "reader_under_test").read(ctx)
        assert v is None or np.isfinite(v), m["name"]
        if v is not None and m["unit"] == "%":
            assert 0 <= v <= 100, (m["name"], v)
