"""The system under test: the one module of the benchmark that imports the
program (``src/repro``).

It builds the served path a configuration file names (the network
constructor's geometry with the configuration's integer LIF plan, an
``EventServeEngine`` or ``MeshEventServeEngine`` behind a
``StreamingRuntime``), turns payloads into the program's requests, and
wraps the engine's phases in host spans.  Everything it hands back to the
harness is plain numbers and numpy arrays.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

from repro.compile_cache import use_compile_cache  # noqa: E402


def compile_cache() -> str:
    """Turn on the program's persistent compilation cache (inside the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` places it)."""
    import jax

    where = use_compile_cache()
    # cache every program, the small eager ones too, so a warm run
    # compiles nothing at all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def program_spec(cfg: Dict):
    """The program's ``SNNSpec`` for ``cfg``: the constructor's layers with
    the configuration's integer thresholds, leaks and 8-bit clip.

    Refuses a configuration whose geometry is not the constructor's."""
    from repro.core import sne_net

    prog = cfg["program"]
    spec = getattr(sne_net, prog["constructor"])(**prog.get("args", {}))
    if (len(spec.layers) != len(cfg["layers"])
            or tuple(spec.in_shape) != tuple(cfg["input"])
            or spec.n_timesteps != cfg["n_timesteps"]
            or spec.n_classes != cfg["n_classes"]):
        raise ValueError(f"{cfg['name']}: the constructor's network is not "
                         f"the configuration's")
    layers = []
    for l, c in zip(spec.layers, cfg["layers"]):
        got = (l.kind, l.out_channels)
        want = (c["kind"], c["out_channels"])
        if l.kind != "fc":          # an fc layer has no window geometry
            got += (l.kernel, l.stride, l.padding)
            want += (c["kernel"], c["stride"], c["padding"])
        if got != want:
            raise ValueError(f"{cfg['name']}: layer {got} != {want}")
        lif = dataclasses.replace(
            l.lif, threshold=float(c["threshold"]), leak=float(c["leak"]),
            state_clip=float(cfg["state_clip"]))
        layers.append(dataclasses.replace(l, lif=lif))
    return dataclasses.replace(spec, layers=tuple(layers))


def build_engine(cfg: Dict, codes: Sequence):
    """The configuration's serving engine over the benchmark's codes."""
    from repro.core.econv import EConvParams
    from repro.serve import EventServeEngine, ExecutionPolicy

    prog = cfg["program"]
    spec = program_spec(cfg)
    params = [EConvParams(w=w) for w in codes]
    return EventServeEngine(spec, params, n_slots=prog["slots"],
                            window=prog["window"],
                            policy=ExecutionPolicy(**prog["policy"]),
                            donate_buffers=True)


def runtime(engine, queue_capacity: int):
    """A ``StreamingRuntime`` over ``engine`` (FIFO slot placement)."""
    from repro.serve import StreamingRuntime

    return StreamingRuntime(engine, queue_capacity=queue_capacity)


def event_request(uid: int, events: tuple, n_timesteps: int):
    """The program's request for one payload's host event arrays."""
    from repro.core.events import EventStream, OP_UPDATE
    from repro.serve import EventRequest

    t, x, y, c = events
    n = len(t)
    stream = EventStream(t=t, x=x, y=y, c=c,
                         op=np.full((n,), OP_UPDATE, np.int64),
                         valid=np.ones((n,), bool))
    return EventRequest(uid=uid, stream=stream, n_timesteps=n_timesteps)


def stream_request(req, arrival_s: float):
    """The runtime's wrapper of a request arriving at ``arrival_s`` (no
    deadline: the runtime never evicts it)."""
    from repro.serve.runtime.admission import StreamRequest

    return StreamRequest(req=req, arrival_s=arrival_s)


DONE = "done"


def outcome(sreq) -> Dict:
    """One request's result as plain values."""
    req = sreq.req
    t = req.telemetry
    drops = None
    if t is not None:
        drops = int(t.input_dropped + sum(t.inter_layer_dropped))
    return {"uid": req.uid, "status": sreq.status,
            "counts": None if req.class_counts is None
            else np.asarray(req.class_counts, np.float64),
            "drops": drops, "arrival_s": sreq.arrival_s,
            "admit_s": sreq.admit_s, "finish_s": sreq.finish_s}


def step_programs(engine) -> int:
    """Window-step programs the engine's jits hold (mesh and shards)."""
    steps = [getattr(engine, "_step", None),
             getattr(engine, "_mesh_step", None)]
    steps += [sh._step for sh in getattr(engine, "shards", [])]
    return sum(s._cache_size() for s in steps if s is not None)


def warm_shapes(engine, payload_events: List[tuple], n_timesteps: int,
                slot_counts: Sequence[int]) -> int:
    """Compile every program a window of ``a`` busy slots in
    ``slot_counts`` uses, at the event rung of each payload given: one
    window each, served through the engine's own phases and then evicted.
    Returns the windows run."""
    runs = 0
    for events in payload_events:
        for a in slot_counts:
            for s in range(a):
                engine.try_admit(event_request(-1, events, n_timesteps),
                                 slot=s)
            engine.step()
            for s in range(a):
                engine.evict_slot(s)
            runs += 1
    return runs


def wrap_spans(engine, rt, annotate) -> None:
    """Put a host span around each phase the runtime drives: admission,
    collect, launch, retire, and the wait for an arrival.  ``annotate``
    is a context-manager factory taking the span's name."""
    def wrap(obj, attr, name):
        fn = getattr(obj, attr)

        def spanned(*a, **k):
            with annotate(name):
                return fn(*a, **k)
        setattr(obj, attr, spanned)

    wrap(engine, "try_admit", "bench.admit")
    wrap(engine, "_collect_phase", "bench.collect")
    wrap(engine, "_launch_phase", "bench.launch")
    wrap(engine, "_retire_phase", "bench.retire")
    wrap(rt.clock, "wait_until", "bench.wait_arrival")


def counters(engine) -> Dict:
    """The engine's counters (a copy)."""
    return dict(engine.stats)
