"""Host spans and counters of the serving path (`repro.serve.spans`).

A `StreamingRuntime` run under the JAX profiler on the CPU backend must
record every ``serve.*`` span, each inside a ``serve.tick``, with one
``serve.admit`` and one ``serve.finish`` per request carrying its uid; the
same seconds must reach ``report()``.  ``stats["h2d_bytes"]`` must equal
the bytes of every host array put on a device, and no host-to-device
transfer may bypass the puts (checked under a transfer guard), on the
local engine here and on the mesh engine over four virtual CPU devices
(its own process: the device count is fixed when JAX starts).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.sne_net import init_snn, tiny_net
from repro.serve.event_engine import EventServeEngine
from repro.serve.runtime import (ManualClock, PoissonLoadGen,
                                 StreamingRuntime, requests_synthetic)
from repro.serve.spans import Span

HERE = Path(__file__).resolve().parent
SPANS = {"serve.tick", "serve.intake", "serve.admit", "serve.collect",
         "serve.launch", "serve.retire", "serve.retire.wait",
         "serve.finish", "serve.wait_arrival"}
N_REQ = 3


def _engine(n_slots=2):
    spec = tiny_net()
    params = init_snn(jax.random.PRNGKey(0), spec)
    return EventServeEngine(spec, params, n_slots=n_slots, window=4,
                            use_pallas=False, donate_buffers=True)


def _serve_poisson(rt):
    # arrivals far apart, so the runtime drains and waits between them
    lg = PoissonLoadGen(requests_synthetic(N_REQ, seed=5), rate_hz=2.0,
                        seed=1)
    return rt.serve(lg)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One served run under the profiler: ``(report, serve.* events)``,
    each event ``(start_ns, end_ns, name, line, {stat: value})``."""
    rt = StreamingRuntime(_engine(), queue_capacity=8, clock=ManualClock())
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        rep = _serve_poisson(rt)
    finally:
        jax.profiler.stop_trace()
    [path] = sorted(out.rglob("*.xplane.pb"))
    space = jax.profiler.ProfileData.from_file(str(path))
    events = []
    for li, line in enumerate(space.find_plane_with_name("/host:CPU").lines):
        for e in line.events:
            if e.name.startswith("serve."):
                events.append((e.start_ns, e.start_ns + e.duration_ns,
                               e.name.split("#", 1)[0], li, dict(e.stats)))
    return rep, events


def test_every_span_is_recorded_inside_a_tick(traced):
    _, events = traced
    assert {n for _, _, n, _, _ in events} == SPANS
    ticks = [(s, e, li) for s, e, n, li, _ in events if n == "serve.tick"]
    for s, e, n, li, _ in events:
        if n != "serve.tick":
            assert any(li == tl and ts <= s and e <= te
                       for ts, te, tl in ticks), n
    seqs = [m["tick"] for _, _, n, _, m in events if n == "serve.tick"]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


def test_one_admit_and_one_finish_per_request(traced):
    rep, events = traced
    assert rep["completed"] == N_REQ
    for name in ("serve.admit", "serve.finish"):
        uids = sorted(m["uid"] for _, _, n, _, m in events if n == name)
        assert uids == list(range(N_REQ)), name
    # a window's retire carries the number its collect and launch had
    wins = {n: {m["win"] for _, _, k, _, m in events if k == n}
            for n in ("serve.collect", "serve.launch", "serve.retire",
                      "serve.retire.wait")}
    assert wins["serve.retire"] == wins["serve.retire.wait"]
    assert wins["serve.retire"] <= wins["serve.launch"] <= wins[
        "serve.collect"]


def test_report_carries_phase_seconds_and_slowest_tick(traced):
    rep, events = traced
    assert set(rep["phase_s"]) == SPANS
    for name, sec in rep["phase_s"].items():
        traced_s = sum(e - s for s, e, n, _, _ in events if n == name) / 1e9
        assert sec > 0 and sec == pytest.approx(traced_s, rel=0.05,
                                                abs=1e-3), name
    slow = rep["slowest_tick"]
    [traced_tick] = [(e - s) / 1e9 for s, e, n, _, m in events
                     if n == "serve.tick" and m["tick"] == slow["tick"]]
    assert slow["tick_s"] == pytest.approx(traced_tick, rel=0.05, abs=1e-4)
    assert "serve.tick" not in slow["phase_s"]
    top = ("serve.intake", "serve.admit", "serve.collect", "serve.launch",
           "serve.retire", "serve.wait_arrival")
    assert sum(slow["phase_s"].get(k, 0.0) for k in top) <= slow["tick_s"]


def test_span_adds_seconds_even_when_the_block_raises():
    phase_s = {}
    with pytest.raises(KeyError):
        with Span(phase_s, "serve.x", uid=1):
            raise KeyError("boom")
    with Span(phase_s, "serve.x") as s:
        pass
    assert phase_s["serve.x"] >= s.seconds >= 0


def _recorded_puts(monkeypatch):
    """Sum the bytes of every host array handed to ``jax.device_put``."""
    put = jax.device_put
    seen = {"bytes": 0}

    def recording(x, *a, **k):
        seen["bytes"] += sum(leaf.nbytes for leaf in jax.tree.leaves(x)
                             if isinstance(leaf, (np.ndarray, np.generic)))
        return put(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", recording)
    return seen


def test_h2d_bytes_counts_every_put_on_the_local_engine(monkeypatch):
    eng = _engine()
    rt = StreamingRuntime(eng, queue_capacity=8, clock=ManualClock())
    rt.submit(requests_synthetic(2, seed=3))
    for _ in range(2):                     # compile outside the guard
        rt.tick()
    seen = _recorded_puts(monkeypatch)
    before = eng.stats["h2d_bytes"]
    windows = eng.stats["step_calls"]
    with jax.transfer_guard_host_to_device("disallow"):
        rep = rt.serve()
    assert rep["completed"] == 2
    assert eng.stats["step_calls"] - windows >= 2
    assert eng.stats["h2d_bytes"] - before == seen["bytes"] > 0


def test_h2d_bytes_counts_every_put_on_the_mesh_engine():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(HERE.parent / "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, str(HERE / "h2d_mesh_check.py")],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["fused_windows"] >= 2, out
    assert out["completed"] == 4, out
    assert out["counted"] == out["put"] > 0, out
