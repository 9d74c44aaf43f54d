"""The reduction of the program's ``serve.*`` spans (``bench/program_spans.py``)
on synthetic intervals and on recorded traces, and the per-layer readers
added with the spans' counters.

``data/gesture_window.xplane.pb.gz`` is a 0.6 s traced window of
gesture-hi-backlog on a TPU v5e chip (``bench/run.py --trace 1 --seconds
0.6 --keep-trace``, seed 3700000003), recorded with the program's spans;
to keep it small, the host plane keeps only the line that holds the
``bench.*`` and ``serve.*`` spans (the runtime's own threads are dropped;
every reduction of it is unchanged).  ``data/nmnist_window.xplane.pb.gz``
predates the spans.
"""
from __future__ import annotations

import json
import types
from pathlib import Path

import numpy as np
import pytest

from bench import program_spans as ps
from bench import run, stats, trace

DATA = Path(__file__).resolve().parent / "data"
ROOT = DATA.parents[2]
NEW_READERS = ("admit_ms.tput", "h2d_kb.tput")
# every span of the program a backlog window holds (no arrival is waited
# for: the queue never runs dry)
BACKLOG_SPANS = {"serve.tick", "serve.intake", "serve.admit",
                 "serve.collect", "serve.launch", "serve.retire",
                 "serve.retire.wait", "serve.finish"}


def test_innermost_names_each_piece_by_the_deepest_span():
    spans = [(0, 10, "serve.tick"), (2, 5, "serve.admit"),
             (5, 8, "serve.retire"), (6, 7, "serve.finish")]
    assert ps.innermost(spans) == [
        (0, 2, "serve.tick"), (2, 5, "serve.admit"), (5, 6, "serve.retire"),
        (6, 7, "serve.finish"), (7, 8, "serve.retire"),
        (8, 10, "serve.tick")]
    assert ps.innermost([]) == []
    # a gap between two spans is no piece
    assert ps.innermost([(0, 1, "a"), (2, 3, "b")]) == [(0, 1, "a"),
                                                       (2, 3, "b")]


def test_span_times_count_total_and_self_inside_the_window():
    ns = 1e9
    spans = [(0, 10 * ns, "serve.tick"), (2 * ns, 5 * ns, "serve.admit"),
             (12 * ns, 20 * ns, "serve.tick")]
    pieces = ps.innermost(spans)
    got = ps.span_times(spans, pieces, 1 * ns, 15 * ns)
    assert got["serve.tick"] == [2, pytest.approx(12.0),
                                 pytest.approx(9.0)]
    assert got["serve.admit"] == [1, pytest.approx(3.0), pytest.approx(3.0)]


def test_split_idle_by_innermost_span_and_the_rest():
    ns = 1e9
    pieces = ps.innermost([(0, 10 * ns, "serve.tick"),
                           (2 * ns, 5 * ns, "serve.admit")])
    idle = [(1 * ns, 3 * ns), (4 * ns, 6 * ns), (9 * ns, 12 * ns)]
    by, rest = ps.split_idle(idle, pieces)
    assert by == {"serve.tick": pytest.approx(3.0),
                  "serve.admit": pytest.approx(2.0)}
    assert rest == pytest.approx(2.0)
    assert ps.split_idle(idle, []) == ({}, pytest.approx(7.0))


@pytest.fixture(scope="module")
def nmnist():
    return ps.load_space(DATA / "nmnist_window.xplane.pb.gz")


def test_a_trace_without_program_spans_reduces_to_none(nmnist):
    red = ps.reduce_space(nmnist, n_devices=1)
    assert red["program_spans"] == {} and red["idle_by_span"] == [{}]
    assert red["idle_unspanned_s"][0] == pytest.approx(red["idle_s"][0])
    old = trace.reduce_space(nmnist, n_devices=1, n_kernels=5)
    assert red["window_s"] == pytest.approx(old["window_s"])
    assert red["idle_s"][0] == pytest.approx(
        old["idle_share"][0] * old["window_s"])


def _context(reduced, counters, cfg_file):
    cfg = json.loads(cfg_file.read_text())
    window = run.Window()
    window.t0, window.t1 = 0.0, reduced["window_s"]
    return stats.Context(
        cfg=cfg, layers=[], reference=None, mix={}, pool=None,
        payload_of={}, spikes={}, window=window, trace=reduced,
        counters={"t0": dict.fromkeys(counters, 0), "t1": counters},
        outcomes=[], late_s=[], chips=1, device_kind="TPU v5 lite",
        n_slots=16)


def _read(name, ctx):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    return run.load_module(path, "reader_under_test").read(ctx)


def test_h2d_reader_needs_the_programs_counter(nmnist):
    old = trace.reduce_space(nmnist, n_devices=1, n_kernels=5)
    ctx = _context(old, {"step_calls": 4}, DATA / "nmnist_net.json")
    assert _read("h2d_kb.tput", ctx) is None
    ctx = _context(old, {"step_calls": 4, "h2d_bytes": 4 * 2048 * 1024},
                   DATA / "nmnist_net.json")
    assert _read("h2d_kb.tput", ctx) == pytest.approx(2048.0)


def test_new_readers_are_listed_for_the_backlog_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert metrics[name]["workloads"] == ["gesture-hi-backlog",
                                              "gesture-mesh4-backlog"]
        assert metrics[name]["moves"] == "events_per_s"


@pytest.fixture(scope="module")
def gesture():
    return ps.load_space(DATA / "gesture_window.xplane.pb.gz")


GESTURE_KINDS = ["pool", "conv", "pool", "conv", "pool", "fc", "fc"]


def test_chip_trace_names_each_kernel_by_its_layer(gesture):
    red = trace.reduce_space(gesture, n_devices=1,
                             n_kernels=len(GESTURE_KINDS))
    assert red["steps"] > 0
    for i, (name, kind) in enumerate(zip(red["kernel_names"],
                                         GESTURE_KINDS)):
        assert name.startswith(f"layer{i}_{kind}_window"), name


def test_chip_trace_idle_falls_inside_program_spans(gesture):
    red = ps.reduce_space(gesture, n_devices=1)
    assert set(red["program_spans"]) >= BACKLOG_SPANS - {"serve.finish"}
    idle, rest = red["idle_s"][0], red["idle_unspanned_s"][0]
    assert idle > 0 and rest <= 0.1 * idle
    assert sum(red["idle_by_span"][0].values()) == pytest.approx(idle - rest)
    # the window opens on a cohort's admission, the chip idle throughout
    assert red["idle_by_span"][0]["serve.admit"] == pytest.approx(
        red["program_spans"]["serve.admit"][2], rel=0.05)


def test_every_reader_of_the_cell_reads_the_chip_trace(gesture):
    """Each per-layer reader of gesture-hi-backlog returns a finite number
    on the chip trace, and a share stays within [0, 100]."""
    from bench.configs import ecnn_reference as ref

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "dvs_gesture.json").read_text())
    layers = ref.layer_shapes(cfg)
    T = cfg["n_timesteps"]
    red = trace.reduce_space(gesture, n_devices=1, n_kernels=len(layers))
    window = run.Window()
    window.t0, window.t1 = 0.0, red["window_s"]
    window.launched = [{u: (0, 4) for u in range(16)},
                       {u: (4, 8) for u in range(16)}]
    counters = {"step_calls": 9, "h2d_bytes": 9 * 2097560,
                "launched_events": 700, "padded_event_slots": 1000}
    ctx = stats.Context(
        cfg=cfg, layers=layers, reference=ref, mix={},
        pool=types.SimpleNamespace(n_timesteps=T,
                                   counts=np.asarray([1600])),
        payload_of=dict.fromkeys(range(16), 0),
        spikes={0: np.ones((T, len(layers)))}, window=window, trace=red,
        counters={"t0": dict.fromkeys(counters, 0), "t1": counters},
        outcomes=[], late_s=[], chips=1, device_kind="TPU v5 lite",
        n_slots=16)
    for m in bench["per_layer"]:
        if "gesture-hi-backlog" not in m.get("workloads",
                                             ["gesture-hi-backlog"]):
            continue
        v = _read(m["name"], ctx)
        assert v is not None and np.isfinite(v), m["name"]
        if m["unit"] == "%":
            assert 0 <= v <= 100, (m["name"], v)
    assert _read("admit_ms.tput", ctx) == pytest.approx(
        red["host_spans"]["bench.admit"][1] / 16 * 1e3)
    assert _read("h2d_kb.tput", ctx) == pytest.approx(2048.4, abs=0.1)
