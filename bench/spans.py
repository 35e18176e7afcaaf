"""The benchmark's own spans and counters.

Spans are recorded around the calls into each layer of the program, by
the benchmark's files (the program has no spans of its own yet).  Each
span is kept in memory as (name, request, start, end) on the host's
``perf_counter`` clock; while the profiler runs, each also writes a
``TraceAnnotation`` named ``bench.<name>`` into the trace, so that the
device's idle gaps can be put down to what the host was doing.
"""

from __future__ import annotations

import time
from contextlib import contextmanager


class Spans:
    def __init__(self):
        self.records: list[tuple[str, int, float, float]] = []
        self.request = -1
        self.tracing = False

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.tracing:
            import jax

            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        else:
            yield
        self.records.append((name, self.request, t0, time.perf_counter()))

    def total(self, name: str) -> float:
        """Seconds spent in spans of that name, over the window's
        requests (the warm-up request has index -1)."""
        return sum(b - a for n, r, a, b in self.records
                   if n == name and r >= 0)

    def count(self, name: str) -> int:
        return sum(1 for n, r, _, _ in self.records if n == name and r >= 0)


class CompileCounter:
    """Counts JAX's tracing, lowering and compile events, from its own
    monitoring events (a persistent-cache read counts too)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.events = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.events += 1
            self.seconds += duration
