"""What the harness hands the program to read its spans and counters.

* :class:`RecordingRegistry` keeps every observation of the program's
  histograms (the per-gap inter-token latencies, the decode step times)
  besides their buckets, so that percentiles are exact.
* :class:`ProfiledTracer` writes each of the program's spans into the
  profiler's trace as a ``TraceAnnotation`` (so device idle gaps can be
  put down to what the host was doing) and starts and stops the trace
  at span boundaries, so a short steady stretch of a long call is
  traced without touching the program.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, List, Optional

import jax

from repro.obs import Histogram, Registry, Tracer
from repro.obs.metrics import DEFAULT_LATENCY_EDGES_S


class RecordingHistogram(Histogram):
    __slots__ = ("values",)

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.values: List[float] = []

    def observe(self, v: float) -> None:
        super().observe(v)
        self.values.append(float(v))

    def reset(self) -> None:
        super().reset()
        self.values = []


class RecordingRegistry(Registry):
    def histogram(self, name: str, unit: str = "s",
                  edges=DEFAULT_LATENCY_EDGES_S) -> RecordingHistogram:
        return self._get(name, RecordingHistogram, unit=unit, edges=edges)

    def values(self, name: str) -> List[float]:
        """Every observation of histogram ``name`` since the last reset."""
        return list(self.histogram(name).values)


class TraceWindow:
    """Starts the profiler at the first call of :meth:`tick` at or after
    ``start_s`` and stops it at the first at or after ``start_s +
    length_s`` (seconds after :meth:`arm`)."""

    def __init__(self, log_dir: str, start_s: float, length_s: float,
                 options=None):
        self.log_dir = log_dir
        self.options = options
        self.start_s = start_s
        self.length_s = length_s
        self._t0: Optional[float] = None
        self.state = "idle"

    def arm(self) -> None:
        self._t0 = time.perf_counter()
        self.state = "armed"

    def tick(self) -> None:
        if self.state not in ("armed", "tracing"):
            return
        now = time.perf_counter() - self._t0
        if self.state == "armed" and now >= self.start_s:
            jax.profiler.start_trace(self.log_dir,
                                     profiler_options=self.options)
            self.state = "tracing"
            self._t0 = time.perf_counter()
            self.start_s = 0.0
        elif self.state == "tracing" and now >= self.length_s:
            self.stop()

    def stop(self) -> None:
        if self.state == "tracing":
            jax.profiler.stop_trace()
            self.state = "done"


class ProfiledTracer(Tracer):
    """The program's tracer, each span also a profiler annotation; every
    span opening gives ``on_span`` a chance to start or stop tracing."""

    def __init__(self, on_span: Callable[[], None], **kwargs):
        super().__init__(enabled=True, **kwargs)
        self._on_span = on_span
        self.names = set()

    def span(self, name: str, **attrs):
        self._on_span()
        self.names.add(name)
        stack = contextlib.ExitStack()
        stack.enter_context(jax.profiler.TraceAnnotation(name))
        stack.enter_context(super().span(name, **attrs))
        return stack
