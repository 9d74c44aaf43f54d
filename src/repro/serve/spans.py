"""Host spans of the serving path, on the profiler's clock.

Every phase of `StreamingRuntime.tick` and of the engines' window
lifecycle runs inside one :class:`Span`.  A span does two things:

* it emits a ``jax.profiler.TraceAnnotation`` with its identifiers as
  keyword metadata, so that while a profiler session runs
  (``jax.profiler.start_trace``) it lands in the profiler's own host
  plane, on the same clock as the device planes;
* it adds its host seconds to a ``phase_s`` dict (span name -> seconds),
  which is the only record an untraced run has.

With no profiler session a span costs its annotation's enter and exit and
one ``perf_counter_ns`` pair.  Span names carry the ``serve.`` prefix.
"""
from __future__ import annotations

from time import perf_counter_ns
from typing import Dict

from jax.profiler import TraceAnnotation


class Span:
    """``with Span(phase_s, name, **meta):`` times one phase.

    ``meta`` holds the identifiers that tie spans together (``uid`` of a
    request, ``win`` of a window, ``slot``, ``tick``).  After the block,
    ``seconds`` holds the span's duration.
    """

    __slots__ = ("_phase_s", "_name", "_ann", "_t0", "seconds")

    def __init__(self, phase_s: Dict[str, float], name: str, **meta):
        self._phase_s = phase_s
        self._name = name
        self._ann = TraceAnnotation(name, **meta)

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self._t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = (perf_counter_ns() - self._t0) * 1e-9
        self._phase_s[self._name] = (self._phase_s.get(self._name, 0.0)
                                     + self.seconds)
        self._ann.__exit__(*exc)
