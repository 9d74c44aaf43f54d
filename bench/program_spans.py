"""Reduce the program's own ``serve.*`` host spans in a profiler trace.

The serving path emits one ``jax.profiler.TraceAnnotation`` per phase
(``repro.serve.spans``): ``serve.tick``, ``serve.intake``,
``serve.admit``, ``serve.collect``, ``serve.launch``, ``serve.retire``,
``serve.retire.wait``, ``serve.finish`` and ``serve.wait_arrival``.  They
land in the profiler's host plane, on the clock of the device planes that
``bench/trace.py`` reads.  Inside the traced window (the ``bench.window``
span) this gives:

* per span name: count, total and self time (a span's time where it is
  the innermost ``serve.*`` span; the names are cut at any ``#``
  metadata suffix);
* per chip: its idle time split by the innermost ``serve.*`` span that
  covers it, and the part under no such span.

A trace of a program without these spans reduces to empty ones.  Run on a
kept trace (``bench/run.py --trace 1 --keep-trace <file>``):

    python3 -m bench.program_spans <file.xplane.pb[.gz]> --devices <n>
"""
from __future__ import annotations

import argparse
import gzip
import json
from pathlib import Path
from typing import Dict, List, Tuple

from bench import trace

PREFIX = "serve."


def program_spans(space) -> List[Tuple[float, float, str]]:
    """Every ``serve.*`` host span, ``(start_ns, end_ns, name)``."""
    plane = space.find_plane_with_name("/host:CPU")
    out = []
    if plane is None:
        return out
    for line in plane.lines:
        out += [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                 e.name.split("#", 1)[0])
                for e in line.events if e.name.startswith(PREFIX)]
    return out


def innermost(spans) -> List[Tuple[float, float, str]]:
    """The time the spans cover, cut into disjoint ``(start, end, name)``
    pieces in time order, each named by the innermost span over it (the
    one that started last; of two that started together, the shorter)."""
    order = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    cuts = sorted({x for s, e, _ in spans for x in (s, e)})
    out, active, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(order) and order[j][0] <= a:
            active.append(order[j])
            j += 1
        active = [sp for sp in active if sp[1] > a]
        if active:
            top = max(active, key=lambda sp: (sp[0], -sp[1]))
            out.append((a, b, top[2]))
    return out


def clip_named(intervals, lo: float, hi: float):
    """Named intervals cut to ``[lo, hi]``; empty ones dropped."""
    return [(max(s, lo), min(e, hi), n) for s, e, n in intervals
            if min(e, hi) > max(s, lo)]


def span_times(spans, pieces, lo: float, hi: float) -> Dict[str, List]:
    """``name -> [count, total s, self s]`` of the spans inside
    ``[lo, hi]`` (cut to it); self time is where the name is innermost
    (``pieces``, from :func:`innermost`)."""
    out: Dict[str, List] = {}
    for s, e, n in clip_named(spans, lo, hi):
        t = out.setdefault(n, [0, 0.0, 0.0])
        t[0] += 1
        t[1] += (e - s) / 1e9
    for s, e, n in clip_named(pieces, lo, hi):
        out[n][2] += (e - s) / 1e9
    return out


def split_idle(idle: List[Tuple[float, float]], pieces
               ) -> Tuple[Dict[str, float], float]:
    """Idle intervals split by the innermost span over them: ``(seconds
    per span name, seconds under no span)``.  Both inputs are disjoint
    and in time order."""
    by: Dict[str, float] = {}
    covered, j = 0.0, 0
    for s, e in idle:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b, n = pieces[k]
            d = min(b, e) - max(a, s)
            if d > 0:
                by[n] = by.get(n, 0.0) + d / 1e9
                covered += d
            k += 1
    total = sum(e - s for s, e in idle)
    return by, (total - covered) / 1e9


def reduce_space(space, n_devices: int) -> Dict:
    """The program spans of one trace, inside its ``bench.window``."""
    windows = [s for s in trace.host_spans(space)
               if s[2] == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {trace.WINDOW_SPAN!r} span")
    w0, w1 = windows[0][0], windows[0][1]
    spans = program_spans(space)
    pieces = innermost(spans)
    idle_s, by_span, unspanned = [], [], []
    for plane in trace.device_planes(space, n_devices):
        ops = [(float(e.start_ns), float(e.start_ns + e.duration_ns))
               for line in plane.lines if line.name == "XLA Ops"
               for e in line.events]
        merged = trace.union(trace.clip(ops, w0, w1))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        by, rest = split_idle(idle, pieces)
        idle_s.append(sum(e - s for s, e in idle) / 1e9)
        by_span.append(by)
        unspanned.append(rest)
    return {"window_s": (w1 - w0) / 1e9,
            "program_spans": span_times(spans, pieces, w0, w1),
            "idle_s": idle_s, "idle_by_span": by_span,
            "idle_unspanned_s": unspanned}


def load_space(path: Path):
    """A profiler trace from an ``.xplane.pb`` file, gzipped or not."""
    import jax

    raw = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    return jax.profiler.ProfileData.from_serialized_xspace(raw)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("xplane", type=Path)
    ap.add_argument("--devices", type=int, default=1)
    args = ap.parse_args(argv)
    print(json.dumps(reduce_space(load_space(args.xplane), args.devices)))


if __name__ == "__main__":
    main()
