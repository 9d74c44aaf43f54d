"""End-to-end statistics of a measured window, and the context that the
per-layer metric readers (``bench/metrics/*.py``) read from."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from bench import work

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (``q`` in [0, 100]); nan if empty.
    (The arithmetic of ``repro.serve.runtime.metrics.percentile``.)"""
    if not len(xs):
        return float("nan")
    s = sorted(float(x) for x in xs)
    pos = (len(s) - 1) * (q / 100.0)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    frac = pos - lo
    return s[lo] * (1.0 - frac) + s[hi] * frac


def events_per_s(win) -> float:
    """Input events of every window step that retired inside the measured
    window, over the window's seconds.  A tick retires the window the tick
    before it collected, so tick ``k`` retires what tick ``k-1``
    collected (the first retires what set-up left in flight)."""
    collected = [win.carry_events] + [n for _, n in win.ticks]
    return float(sum(collected[:len(win.ticks)])) / (win.t1 - win.t0)


def due(outcomes: List[Dict], t0: float, t1: float) -> List[Dict]:
    """Requests scheduled to arrive inside ``[t0, t1)``."""
    return [o for o in outcomes if t0 <= o["arrival_s"] < t1]


def p95_request_ms(outcomes: List[Dict], win, seconds: float,
                   limit_ms: Optional[float], drain_end: float) -> float:
    """95th percentile of answer time minus scheduled arrival over every
    request due in the window.  A request refused or never answered counts
    as missing the limit: its time is the larger of the limit and how long
    it was in the system."""
    lat = []
    for o in due(outcomes, win.t0, win.t0 + seconds):
        if o["status"] == "done":
            lat.append((o["finish_s"] - o["arrival_s"]) * 1e3)
        else:
            end = o["finish_s"] if o["finish_s"] is not None else drain_end
            lat.append(max(limit_ms or 0.0, (end - o["arrival_s"]) * 1e3))
    return percentile(lat, 95.0)


def peaks(device_kind: str) -> Dict:
    """The chip's peaks; a device not in ``bench/peaks.json`` is an
    error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


@dataclasses.dataclass
class Context:
    """Everything a per-layer reader may read, from one traced run."""

    cfg: Dict
    layers: List[Dict]
    reference: Any
    mix: Dict
    pool: Any
    payload_of: Dict[int, int]
    spikes: Dict[int, np.ndarray]     # payload -> (T, L) layer spikes
    window: Any
    trace: Dict
    counters: Dict                    # engine counters at t0 and t1
    outcomes: List[Dict]
    late_s: List[float]
    chips: int
    device_kind: str
    n_slots: int

    def counter(self, name: str) -> Optional[float]:
        """Growth of an engine counter over the window (None if absent)."""
        a, b = self.counters["t0"], self.counters["t1"]
        if name not in a or name not in b:
            return None
        return float(b[name] - a[name])

    def host_span_ms(self, name: str) -> Optional[float]:
        """Mean milliseconds of the host span ``name`` in the window."""
        spans = self.trace["host_spans"].get(name)
        if not spans or not spans[0]:
            return None
        return spans[1] / spans[0] * 1e3

    def work(self):
        """``(sops, kernel work (L, 2))`` of the windows launched inside
        the measured window, from the reference's per-layer spikes."""
        fan = [self.reference.fan_out(l) for l in self.layers]
        sops = 0.0
        kw = np.zeros((len(self.layers), 2))
        for moved in self.window.launched:
            events = np.zeros(len(self.layers))
            steps = 0
            for uid, (a, b) in moved.items():
                p = self.payload_of[uid]
                ev_in = work.input_events(
                    np.full(self.pool.n_timesteps, self.pool.counts[p]),
                    self.spikes[p])[a:b]
                events += ev_in.sum(axis=0)
                steps = max(steps, b - a)
            if not moved:
                continue
            sops += float(np.dot(events, fan))
            kw += work.kernel_work(self.layers, fan, len(moved), steps,
                                   events)
        return sops, kw

    def due(self) -> List[Dict]:
        """Requests scheduled to arrive inside the measured window."""
        return due(self.outcomes, self.window.t0, self.window.t1)

    def quantile_ms(self, xs: Sequence[float], q: float) -> Optional[float]:
        """The ``q``-th percentile of ``xs`` seconds, in ms."""
        return percentile(xs, q) * 1e3 if len(xs) else None
