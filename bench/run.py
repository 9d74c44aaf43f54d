#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout.  It names a configuration (``bench/configs/<name>.json``,
whose plain reference is ``bench/configs/<reference>.py``) and a traffic
mix (``bench/traffic/<name>.json``); each per-layer metric is read by
``bench/metrics/<name>.py``.  Nothing here is specific to one cell.

A run: make the weight codes and the payload pool on the device from the
seed, build the configuration's serving engine behind the program's
``StreamingRuntime``, compile every shape the cell's traffic uses
(set-up), then drive the runtime with the mix's arrivals for
``--seconds``.  After the window it drains what was admitted, reads the
device's peak memory, frees the program's state, runs the plain reference
over every payload a finished request carried, and compares each answer
with it (``bench/checks.py``).

With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the measured window (at most ``TRACE_WINDOW_S``) runs under
the JAX profiler and the result holds the cell's per-layer metrics, the
device's busy time and a breakdown.  The last line of standard output is
one JSON object; the numbers compared for ``correct`` close standard
error.  Exits non-zero, with no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"      # profiles, deleted once reduced
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import checks, stats, sut, traffic  # noqa: E402

DRAIN_S = 60.0          # the longest wait past the window for an answer
TRACE_WINDOW_S = 10.0   # a traced run measures at most this long
REF_BLOCK = 8           # payloads per reference call
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def accelerator(chips: int):
    """The cell's devices; raises :class:`NoAccelerator`."""
    import jax

    if jax.default_backend() != "tpu":
        raise NoAccelerator(f"no TPU: JAX's backend is "
                            f"{jax.default_backend()!r}")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Programs XLA compiles (or loads from the persistent cache), counted
    from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration


def load_json(path: Path) -> Dict:
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    """Import one file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT):
    """``(cell, configuration, mix, end-to-end metrics, per-layer
    metrics)`` of the cell ``name``, as ``BENCHMARK.json`` gives them."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(root / cfg_entry["file"])
    mix = traffic.load_mix(cell["traffic"])
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    return cell, cfg, mix, e2e, per_layer


class Window:
    """What the measured window saw, tick by tick."""

    def __init__(self):
        self.t0 = self.t1 = 0.0
        self.ticks: List[tuple] = []      # (end time, events collected)
        self.launched: List[Dict] = []    # per tick: uid -> (tau0, tau1)
        self.carry_events = 0             # collected by the tick before
        self.counters: Dict = {}          # engine counters at t0 and t1


def tick_progress(engine, rt, last: Dict) -> Dict:
    """Timesteps each running request had launched into windows by this
    tick: ``uid -> (before, after)`` for those that moved."""
    moved = {}
    for slot, sreq in rt.running.items():
        tau = int(engine.tau[slot])
        uid = sreq.req.uid
        before = last.get(uid, 0)
        if tau != before:
            moved[uid] = (before, tau)
            last[uid] = tau
    return moved


class CellRun:
    """One run of one cell: the served path, its traffic and its answers."""

    def __init__(self, cfg: Dict, mix: Dict, seed: int):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.reference = load_module(
            BENCH / "configs" / f"{cfg['reference']}.py",
            f"bench_reference_{cfg['reference']}")
        self.layers = self.reference.layer_shapes(cfg)
        self.T = cfg["n_timesteps"]
        self.n_slots = cfg["program"]["slots"]
        self.phases: Dict[str, float] = {}
        self.payload_of: Dict[int, int] = {}

    def set_up(self) -> None:
        """Weights, payloads, engine, runtime; every shape compiled."""
        cfg, mix = self.cfg, self.mix
        t = time.perf_counter()
        self.codes = self.reference.make_codes(cfg, self.seed)
        self.pool = traffic.make_pool(self.seed, mix, tuple(cfg["input"]),
                                      self.T, cfg["n_classes"])
        # requests cycle through the pool in a seeded order
        self.order = np.random.default_rng(np.random.SeedSequence(
            [self.seed, 3])).permutation(len(self.pool))
        self.phases["weights_payloads"] = time.perf_counter() - t
        t = time.perf_counter()
        self.engine = sut.build_engine(cfg, self.codes)
        backlog = mix["arrivals"] == "backlog"
        self.rt = sut.runtime(self.engine, self.n_slots if backlog
                              else mix["queue_capacity"])
        warm = traffic.warm_plan(mix, self.pool, self.n_slots)
        sut.warm_shapes(self.engine,
                        [self.pool.events[p] for p in warm["payloads"]],
                        self.T, warm["slot_counts"])
        self.phases["engine_warm_shapes"] = time.perf_counter() - t
        t = time.perf_counter()
        self.window = Window()
        self.arrivals = None
        if backlog:
            self.arrivals = traffic.Arrivals(
                "backlog", self.make, queue=self.rt.queue,
                depth=self.n_slots)
            while self.rt.metrics.completed < warm["requests"]:
                self.window.carry_events = self.tick()
        self.phases["warm_traffic"] = time.perf_counter() - t

    def make(self, i: int, at: float):
        """Request ``i`` of the run, arriving at clock time ``at``."""
        p = int(self.order[i % len(self.order)])
        self.payload_of[i] = p
        return sut.stream_request(
            sut.event_request(i, self.pool.events[p], self.T), at)

    def tick(self) -> int:
        """One runtime tick; the input events it collected."""
        e = self.engine.stats["collected_events"]
        self.rt.tick(self.arrivals)
        return self.engine.stats["collected_events"] - e

    def measure(self, seconds: float, trace_dir: Optional[Path]) -> Window:
        """Drive the runtime for ``seconds``; under the profiler if
        ``trace_dir`` is given."""
        import jax

        win, engine, rt = self.window, self.engine, self.rt
        if self.arrivals is None:
            self.arrivals = traffic.Arrivals(
                "poisson", self.make, rate_hz=self.mix["rate_hz"],
                seed=self.seed, start_s=rt.clock.now())
        span = contextlib.nullcontext()
        if trace_dir is not None:
            sut.wrap_spans(engine, rt, jax.profiler.TraceAnnotation)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            span = jax.profiler.TraceAnnotation("bench.window")
        last: Dict[int, int] = {}
        tick_progress(engine, rt, last)
        win.counters["t0"] = sut.counters(engine)
        with span:
            win.t0 = rt.clock.now()
            end = win.t0 + seconds
            while rt.clock.now() < end:
                n = self.tick()
                win.ticks.append((rt.clock.now(), n))
                win.launched.append(tick_progress(engine, rt, last))
            win.t1 = rt.clock.now()
        win.counters["t1"] = sut.counters(engine)
        if trace_dir is not None:
            jax.profiler.stop_trace()
        return win

    def drain(self, devices) -> None:
        """Answer everything handed over (at most ``DRAIN_S`` past the
        window), read the peak memory, and free the program's state."""
        self.arrivals.stop()
        self.drain_end = self.window.t1 + DRAIN_S
        while (self.rt.clock.now() < self.drain_end
               and self.rt.tick(self.arrivals)):
            pass
        self.memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices)
        self.outcomes = [sut.outcome(s) for s in self.rt.requests]
        self.late_s = list(self.arrivals.late_s)
        del self.rt, self.engine
        gc.collect()

    def check(self, control: bool) -> Dict:
        """Compare every finished request with the plain reference.

        ``control`` puts the control in the program's place: every
        finished request's answer is replaced by the reference's with an
        int4 membrane (``state_bits=4``), which the comparison has to
        refuse."""
        import jax.numpy as jnp

        t = time.perf_counter()
        used = sorted({self.payload_of[o["uid"]] for o in self.outcomes
                       if o["status"] == sut.DONE})
        want, self.spikes, lower = {}, {}, {}
        for i in range(0, len(used), REF_BLOCK):
            block = used[i:i + REF_BLOCK]
            x = jnp.stack([self.pool.dense(p) for p in block])
            cc, n = self.reference.forward(self.cfg, self.codes, x)
            for p, c, s in zip(block, np.asarray(cc), np.asarray(n)):
                want[p], self.spikes[p] = c.astype(np.float64), s
            if control:
                cc, _ = self.reference.forward(self.cfg, self.codes, x,
                                               state_bits=4)
                lower.update(zip(block, np.asarray(cc, np.float64)))
        if control:
            own = checks.compare(self.outcomes, self.payload_of, want)
            log(f"the program's own answers: correct {own['correct']}; "
                + "; ".join(checks.lines(own["numbers"])))
            for o in self.outcomes:
                if o["status"] == sut.DONE:
                    o["counts"] = lower[self.payload_of[o["uid"]]]
        result = checks.compare(self.outcomes, self.payload_of, want)
        log(f"reference over {len(used)} payloads: "
            f"{time.perf_counter() - t:.3f} s")
        return result

    def end_to_end(self, e2e: List[Dict], setup_s: float,
                   seconds: float) -> Dict:
        """The cell's end-to-end metrics, from the host clock."""
        win = self.window
        values = {"setup_s": lambda: setup_s,
                  "events_per_s": lambda: stats.events_per_s(win),
                  "p95_request_ms": lambda: stats.p95_request_ms(
                      self.outcomes, win, seconds,
                      self.mix.get("latency_limit_ms"), self.drain_end)}
        return {m["name"]: {"value": values[m["name"]](), "unit": m["unit"]}
                for m in e2e}

    def per_layer(self, per_layer: List[Dict], reduced: Dict, chips: int,
                  device_kind: str) -> Dict:
        """The cell's per-layer metrics that their readers find."""
        ctx = stats.Context(
            cfg=self.cfg, layers=self.layers, reference=self.reference,
            mix=self.mix, pool=self.pool, payload_of=self.payload_of,
            spikes=self.spikes, window=self.window, trace=reduced,
            counters=self.window.counters, outcomes=self.outcomes,
            late_s=self.late_s, chips=chips, device_kind=device_kind,
            n_slots=self.n_slots)
        out = {}
        for m in per_layer:
            reader = load_module(
                BENCH / "metrics" / f"{m['name']}.py",
                f"bench_metric_{m['name'].replace('.', '_')}")
            v = reader.read(ctx)
            if v is not None:
                out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        return out


def run_cell(cell: Dict, cfg: Dict, mix: Dict, seed: int, seconds: float,
             trace: bool, per_layer: List[Dict], e2e: List[Dict],
             devices=None, keep_trace: Optional[Path] = None,
             control: bool = False) -> Dict:
    """One run of one cell; returns the result object (see module doc)."""
    import jax

    counter = CompileCounter()
    devices = devices or jax.devices()[:cell["chips"]]
    run = CellRun(cfg, mix, seed)
    run.phases["start"] = time.perf_counter() - T_START
    run.set_up()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s ("
        + ", ".join(f"{k} {v:.3f}" for k, v in run.phases.items())
        + f"); programs compiled in set-up {counter.n} "
        f"({counter.seconds:.3f} s); window-step programs "
        f"{sut.step_programs(run.engine)}")

    trace_dir = None
    if trace:
        seconds = min(seconds, TRACE_WINDOW_S)
        trace_dir = TRACE_DIR / f"trace_{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    c0 = counter.n
    win = run.measure(seconds, trace_dir)
    log(f"programs compiled inside the measured window: {counter.n - c0}")
    ends = [win.t0] + [t for t, _ in win.ticks]
    longest = sorted(((b - a, a - win.t0) for a, b in zip(ends, ends[1:])),
                     reverse=True)[:3]
    log(f"{len(win.ticks)} ticks; longest "
        + ", ".join(f"{d * 1e3:.1f} ms at {at:.2f} s" for d, at in longest))
    run.drain(devices)
    check = run.check(control)

    dev = devices[0]
    result = {"correct": check["correct"], "attempted": check["attempted"],
              "failed": check["failed"],
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": int(run.memory_peak)}}
    if trace:
        from bench import trace as tr

        reduced = tr.reduce(trace_dir, len(devices), len(run.layers))
        if keep_trace is not None:
            shutil.copy(tr.find_xplane(trace_dir), keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
        result["metrics"] = run.per_layer(per_layer, reduced, len(devices),
                                          dev.device_kind)
    else:
        result["metrics"] = run.end_to_end(e2e, setup_s, seconds)
    result["checks"] = check["numbers"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="copy the traced run's .xplane.pb here")
    ap.add_argument("--rate-hz", type=float, default=None,
                    help="override a Poisson mix's rate (knee sweeps)")
    ap.add_argument("--control", action="store_true",
                    help="answer with the int4-membrane control instead "
                    "of the program's answers (it must not be correct)")
    args = ap.parse_args(argv)

    cell, cfg, mix, e2e, per_layer = load_cell(args.workload)
    if args.rate_hz is not None:
        mix = dict(mix, rate_hz=args.rate_hz)
    sut.compile_cache()
    try:
        devices = accelerator(cell["chips"])
    except NoAccelerator as e:
        log(f"bench/run.py: {e}; nothing was run")
        return 2
    result = run_cell(cell, cfg, mix, args.seed, args.seconds,
                      bool(args.trace), per_layer, e2e, devices=devices,
                      keep_trace=args.keep_trace, control=args.control)
    for line in checks.lines(result["checks"]):
        log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
