"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The traced window is the host span ``bench.window`` that ``bench/run.py``
puts around its measured loop; everything is clipped to it.  On each
device plane (``/device:TPU:<n>``):

* busy time is the union of the intervals of the ``XLA Ops`` line;
* a window step is an execution on the ``XLA Modules`` line that holds
  Pallas kernels (ops whose HLO text names ``tpu_custom_call``).  Its
  kernels are attributed to layers by their order inside the execution
  (the kernels carry no ``name=`` of their own), where it holds one per
  layer; every other op inside it is the step's XLA time (routing, state
  gathers and scatters, decay);
* an idle gap is a stretch of the window in which no op runs; it is named
  by the innermost ``bench.*`` host span (admit, collect, launch, retire,
  wait_arrival) that covers its middle, or ``host.other``.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Optional, Tuple

KERNEL_MARK = "tpu_custom_call"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: Path) -> Path:
    """The one ``.xplane.pb`` under a profiler output directory."""
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    """Intervals cut to ``[lo, hi]``; empty ones dropped."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def op_name(hlo: str) -> str:
    """The op's name from its HLO text (``%name.3 = ...`` -> ``name.3``)."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def _events(line):
    return [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def host_spans(space) -> List[Tuple[float, float, str]]:
    """Every ``bench.*`` host span, ``(start_ns, end_ns, name)``."""
    plane = space.find_plane_with_name("/host:CPU")
    out = []
    if plane is None:
        return out
    for line in plane.lines:
        out += [ev for ev in _events(line) if ev[2].startswith(SPAN_PREFIX)]
    return out


def device_planes(space, n_devices: int):
    """The first ``n_devices`` TPU device planes, by index."""
    planes = {}
    for p in space.planes:
        if p.name.startswith("/device:TPU:") and p.name[12:].isdigit():
            planes[int(p.name[12:])] = p
    return [planes[i] for i in sorted(planes)[:n_devices]]


def reduce_space(space, n_devices: int, n_kernels: int) -> Dict:
    """The numbers of one trace (see the module doc)."""
    spans = host_spans(space)
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = windows[0][0], windows[0][1]
    inner = [s for s in spans if s[2] != WINDOW_SPAN]
    planes = device_planes(space, n_devices)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    busy, kernel_ns, step_xla_ns, steps = [], [0.0] * n_kernels, 0.0, 0
    kernel_total_ns = 0.0
    kernel_names = [""] * n_kernels
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[float, str]] = []
    odd_steps = 0

    def cut(s, e):
        return max(0.0, min(e, w1) - max(s, w0))

    for d, plane in enumerate(planes):
        lines = {l.name: l for l in plane.lines}
        ops = sorted(_events(lines["XLA Ops"])) if "XLA Ops" in lines \
            else []
        merged = union(clip([(s, e) for s, e, _ in ops], w0, w1))
        busy.append(sum(e - s for s, e in merged))
        mods = sorted(_events(lines["XLA Modules"])) \
            if "XLA Modules" in lines else []
        label = {}
        j = 0
        for ms, me, _ in mods:
            if me <= w0 or ms >= w1:
                continue
            while j < len(ops) and ops[j][0] < ms:
                j += 1
            k = j
            while k < len(ops) and ops[k][0] < me:
                k += 1
            inside = range(j, k)
            kern = [i for i in inside if KERNEL_MARK in ops[i][2]]
            if not kern:
                continue
            steps += 1
            kernel_total_ns += sum(cut(*ops[i][:2]) for i in kern)
            if len(kern) != n_kernels:      # cut off at the trace's start
                odd_steps += 1
                continue
            for layer, i in enumerate(kern):
                s, e, n = ops[i]
                kernel_ns[layer] += cut(s, e)
                kernel_names[layer] = kernel_names[layer] or op_name(n)
                label[i] = f"kernel{layer}.{_family(op_name(n))}"
            step_xla_ns += sum(cut(*ops[i][:2]) for i in inside
                               if KERNEL_MARK not in ops[i][2])
        for i, (s, e, n) in enumerate(ops):
            name = label.get(i) or _family(op_name(n))
            op_time[name] = op_time.get(name, 0.0) + cut(s, e)
        if d == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append((e - s, _host_at(inner, (s + e) / 2)))
    window_ns = w1 - w0
    span_tot: Dict[str, List[float]] = {}
    for s, e, n in inner:
        if cut(s, e) > 0:
            t = span_tot.setdefault(n, [0, 0.0])
            t[0] += 1
            t[1] += cut(s, e) / 1e9
    ndev = len(planes)
    top_ops = sorted(((n, t) for n, t in op_time.items() if t > 0),
                     key=lambda kv: -kv[1])[:10]
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / ndev / 1e9,
        "busy_s_per_device": [b / 1e9 for b in busy],
        "idle_share": [1.0 - b / window_ns for b in busy],
        "steps": steps,
        "steps_with_other_kernel_count": odd_steps,
        "kernel_s": [k / 1e9 for k in kernel_ns],
        "kernel_total_s": kernel_total_ns / 1e9,
        "kernel_names": kernel_names,
        "step_xla_s": step_xla_ns / 1e9,
        "host_spans": span_tot,
        "breakdown": {
            "device_ops": [[n, t / 1e9] for n, t in top_ops],
            "idle_gaps": [[n, g / 1e9] for g, n in gaps[:10]],
        },
    }


def _family(name: str) -> str:
    """An op name without its numeric suffix (``sort.11`` -> ``sort``)."""
    base, _, tail = name.rpartition(".")
    return base if base and tail.isdigit() else name


def _host_at(spans, t: float) -> str:
    best: Optional[Tuple[float, str]] = None
    for s, e, n in spans:
        if s <= t < e and (best is None or s > best[0]):
            best = (s, n)
    return best[1][len(SPAN_PREFIX):] if best else "host.other"


def reduce(trace_dir: Path, n_devices: int, n_kernels: int) -> Dict:
    """Reduce the trace written under ``trace_dir``."""
    import jax

    space = jax.profiler.ProfileData.from_file(str(find_xplane(trace_dir)))
    return reduce_space(space, n_devices, n_kernels)
