"""The harness end to end on the CPU, at a tiny size.

``run_cell`` is driven directly, past ``bench/run.py``'s look for a chip:
a sound run must come out correct, and a run with the served path broken
underneath must not (one test per fault a serving cell can have).  The
entry point itself must refuse to run without a TPU.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import checks, run

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CFG = json.loads((HERE / "tiny_ecnn.json").read_text())
SEED = 2 ** 33 + 12345
BACKLOG = {"arrivals": "backlog", "activity_band": [0.05, 0.08],
           "pool_size": 4, "n_blobs": 1, "warm_requests": 2}
POISSON = {"arrivals": "poisson", "activity_band": [0.05, 0.08],
           "pool_size": 4, "n_blobs": 1, "rate_hz": 50.0,
           "queue_capacity": 8, "latency_limit_ms": 1000.0}


@pytest.fixture(scope="module", autouse=True)
def compile_cache(tmp_path_factory):
    """The runs of this module share compiled programs through a
    persistent cache of their own, put back as it was afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update(keys[1], 0.0)
    cc.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def cell_run(name: str, mix, seconds: float = 0.3):
    cell = {"name": name, "chips": 1}
    e2e = [m for m in BENCH["end_to_end"]
           if name in m.get("workloads", [name])]
    return run.run_cell(cell, CFG, mix, SEED, seconds, False, [], e2e)


def check_value(result, name):
    numbers = result.get("checks") or result["numbers"]
    return {n["name"]: n["value"] for n in numbers}[name]


@pytest.mark.parametrize("name,mix", [("gesture-hi-backlog", BACKLOG),
                                      ("gesture-lo-poisson", POISSON)])
def test_sound_run_is_correct(name, mix):
    res = cell_run(name, mix)
    assert res["correct"], res["checks"]
    assert check_value(res, "compared_requests") > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"setup_s"}
    assert res["device"]["count"] == 1


def _altered_answer(monkeypatch):
    from repro.serve.event_engine import EventServeEngine
    finish = EventServeEngine._finish

    def altered(self, slot):
        req = self.slot_req[slot]
        finish(self, slot)
        req.class_counts = req.class_counts + 1.0

    monkeypatch.setattr(EventServeEngine, "_finish", altered)


def _state_unchanged(monkeypatch):
    import repro.serve.event_engine as ee

    def frozen(params, states, class_counts, ev_xyc, *a, program, **k):
        L, N = len(program.ops), class_counts.shape[0]
        return (states, class_counts, jnp.zeros((L, N), jnp.float32),
                jnp.zeros((L, N), jnp.int32))

    monkeypatch.setattr(ee, "window_step", frozen)


def _half_batch(monkeypatch):
    import repro.serve.event_engine as ee
    step = ee.window_step

    def half(params, states, class_counts, ev_xyc, ev_gate, *a, **k):
        n = ev_gate.shape[1]
        ev_gate = ev_gate.at[:, n // 2:].set(0.0)
        return step(params, states, class_counts, ev_xyc, ev_gate, *a, **k)

    monkeypatch.setattr(ee, "window_step", half)


@pytest.mark.parametrize("fault", [_altered_answer, _state_unchanged,
                                   _half_batch])
def test_broken_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = cell_run("gesture-hi-backlog", BACKLOG)
    assert not res["correct"]
    assert check_value(res, "mismatched_requests") > 0


def test_control_is_not_correct():
    """The control, the reference with an int4 membrane, put in the
    program's place, fails the comparison on every payload of a pool."""
    from bench import traffic
    from bench.configs import ecnn_reference as ref

    codes = ref.make_codes(CFG, SEED)
    pool = traffic.make_pool(SEED, BACKLOG, tuple(CFG["input"]),
                             CFG["n_timesteps"], CFG["n_classes"])
    x = jnp.stack([pool.dense(p) for p in range(len(pool))])
    want = jnp.asarray(ref.forward(CFG, codes, x)[0])
    lower = jnp.asarray(ref.forward(CFG, codes, x, state_bits=4)[0])
    outcomes = [{"uid": p, "status": "done", "counts": lower[p],
                 "drops": 0} for p in range(len(pool))]
    res = checks.compare(outcomes, {p: p for p in range(len(pool))},
                         {p: want[p] for p in range(len(pool))})
    assert not res["correct"]
    assert check_value(res, "mismatched_requests") > 0


def test_compare_limits():
    want = {0: jnp.asarray([1.0, 2.0])}
    ok = {"uid": 0, "status": "done", "counts": jnp.asarray([1.0, 2.0]),
          "drops": 0}
    bad = dict(ok, uid=1, counts=jnp.asarray([1.0, 3.0]))
    assert checks.compare([ok], {0: 0}, want)["correct"]
    res = checks.compare([ok, bad], {0: 0, 1: 0}, want)
    assert not res["correct"] and res["failed"] == 1
    assert not checks.compare([], {}, want)["correct"]


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "gesture-hi-backlog", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
