"""The comparison that decides ``correct``.

Every request the run finished is compared with the plain reference run
over the same payload: its class counts must be equal, exactly (weights
are integer codes and every membrane sum an integer, so the served path
and the reference compute the same integers).  Four numbers are compared,
each with its limit:

* ``mismatched_requests``: finished requests whose class counts differ
  from the reference's (limit 0);
* ``count_gap_max``: the largest absolute difference of one class count
  (limit 0);
* ``dropped_events``: input and inter-layer events the finished requests
  lost to a full bucket (limit 0: every capacity holds a whole frame);
* ``unanswered_requests``: requests admitted but neither answered nor
  refused by the end of the drain (limit 0).

A run with no finished request is not correct.  Requests refused by the
admission layer (queue full) are answers that count
against the latency metric, not against ``correct``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

DONE = "done"
OPEN = ("queued", "running")

LIMITS = {"mismatched_requests": 0, "count_gap_max": 0,
          "dropped_events": 0, "unanswered_requests": 0}


def compare(outcomes: List[Dict], payload_of: Dict[int, int],
            want: Dict[int, np.ndarray]) -> Dict:
    """Compare finished requests with the reference answers ``want``
    (by payload); returns ``correct``, ``attempted``, ``failed`` and the
    ``numbers`` compared, each beside its limit."""
    mismatched, gap, dropped, failed = 0, 0.0, 0, 0
    finished = 0
    for o in outcomes:
        if o["status"] != DONE:
            failed += 1
            continue
        finished += 1
        ref = want[payload_of[o["uid"]]]
        d = float(np.max(np.abs(o["counts"] - ref)))
        gap = max(gap, d)
        if d != 0.0 or o["drops"]:
            mismatched += d != 0.0
            failed += 1
        dropped += int(o["drops"] or 0)
    unanswered = sum(o["status"] in OPEN for o in outcomes)
    values = {"mismatched_requests": mismatched, "count_gap_max": gap,
              "dropped_events": dropped, "unanswered_requests": unanswered}
    numbers = [{"name": k, "value": values[k], "limit": LIMITS[k]}
               for k in LIMITS]
    correct = finished > 0 and all(n["value"] <= n["limit"]
                                   for n in numbers)
    numbers.append({"name": "compared_requests", "value": finished,
                    "limit": 1})
    return {"correct": bool(correct), "attempted": len(outcomes),
            "failed": failed, "numbers": numbers}


def lines(numbers: List[Dict]) -> List[str]:
    """One line per number compared, with its limit."""
    out = []
    for n in numbers:
        rel = ">=" if n["name"] == "compared_requests" else "<="
        out.append(f"check {n['name']} = {n['value']:g} "
                   f"(must be {rel} {n['limit']:g})")
    return out
