"""The program's own spans: where each layer's calls start and end.

A span wraps one call into a layer (a sweep, the device scan inside it,
a table's distillation, one spot check), never a scan step::

    with tracing.span("batched.sweep", points=len(grid)) as sp:
        ...
        sp.counts["scan_steps"] = n

Each span records its name, an id, the id of the span that was open
when it started (its parent, 0 for none), the id of the root of its
call tree (the request it belongs to), its start and end on
``time.perf_counter_ns`` (the clock ``time.perf_counter`` reads), and
small integer counts.  The open spans are a ``contextvars`` stack, so a
thread starts a tree of its own.

Spans are always on.  The last ``RING`` finished spans are kept for
``records()``; per-name totals (``totals()``) cover every span since the
process started, for an operator's long-running process.  Each span also
opens a profiler ``TraceAnnotation`` named ``repro.<name>``, so that a
profile shows it on the host's clock beside the device's operations.
"""

from __future__ import annotations

import contextvars
import itertools
import threading
import time
from collections import deque

import jax

__all__ = ["Span", "span", "count", "records", "totals", "self_ns", "RING"]

RING = 4096

_open: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_tracing_open", default=())
_lock = threading.Lock()
_ids = itertools.count(1)
_ring: deque = deque(maxlen=RING)
_totals: dict[str, list[int]] = {}


class Span:
    """One call of a layer; its own context manager (``span``)."""

    __slots__ = ("name", "id", "parent", "root", "start_ns", "end_ns",
                 "counts", "children", "_token", "_annotation")

    def __init__(self, name: str, **counts: int):
        self.name = name
        self.id = self.parent = self.root = 0
        self.start_ns = self.end_ns = 0
        self.counts = counts
        # (start_ns, end_ns) of each finished child, for self_ns
        self.children: list[tuple[int, int]] = []

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns

    def __enter__(self) -> "Span":
        stack = _open.get()
        with _lock:
            self.id = next(_ids)
        if stack:
            self.parent, self.root = stack[-1].id, stack[-1].root
        else:
            self.root = self.id
        self._token = _open.set(stack + (self,))
        self._annotation = jax.profiler.TraceAnnotation(f"repro.{self.name}")
        self.start_ns = time.perf_counter_ns()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._annotation.__exit__(*exc)
        self.end_ns = time.perf_counter_ns()
        _open.reset(self._token)
        self._token = self._annotation = None
        stack = _open.get()
        if stack:
            stack[-1].children.append((self.start_ns, self.end_ns))
        with _lock:
            _ring.append(self)
            tot = _totals.setdefault(self.name, [0, 0])
            tot[0] += 1
            tot[1] += self.duration_ns


def span(name: str, **counts: int) -> Span:
    """A span named ``name`` with initial ``counts``; use it in ``with``."""
    return Span(name, **counts)


def count(**counts: int) -> None:
    """Add ``counts`` to the innermost open span, if one is open."""
    stack = _open.get()
    if stack:
        c = stack[-1].counts
        for k, v in counts.items():
            c[k] = c.get(k, 0) + v


def records() -> list[Span]:
    """The last ``RING`` finished spans, oldest first."""
    with _lock:
        return list(_ring)


def totals() -> dict[str, tuple[int, int]]:
    """``{name: (count, total_ns)}`` over every finished span."""
    with _lock:
        return {k: (v[0], v[1]) for k, v in _totals.items()}


def self_ns(record: Span) -> int:
    """The span's duration minus the part of it its children cover."""
    covered, reach = 0, record.start_ns
    for a, b in sorted(record.children):
        a, b = max(a, reach), min(b, record.end_ns)
        if b > a:
            covered += b - a
            reach = b
    return record.duration_ns - covered
