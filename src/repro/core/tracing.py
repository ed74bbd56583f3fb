"""Spans: timed regions of one query's execution, always on.

``span(name, stats)`` marks a region twice from one call:

* in a ``jax.profiler`` trace, as the host event ``mdb.<name>``, on the
  clock of the device timeline, so a trace shows what the engine was doing
  while the device ran or idled;
* in the query's own ``ExecStats``: on close it adds the elapsed time to
  ``span_ms[name]`` and one to ``span_n[name]``.  Nested spans each count
  their whole time.  The totals live on the query's stats object, never in
  process-wide state, so concurrent queries do not mix.

The profiler event is written only once JAX is loaded: a process that
never loaded JAX has no trace to write to, and the host tier must not
load it.  A span costs a few microseconds of host time.
"""

from __future__ import annotations

import sys
from time import perf_counter_ns

PREFIX = "mdb."

_annotation = None      # jax.profiler.TraceAnnotation, once JAX is loaded


def _trace_annotation():
    global _annotation
    if _annotation is None:
        profiler = sys.modules.get("jax.profiler")
        if profiler is not None:
            _annotation = profiler.TraceAnnotation
    return _annotation


def add(stats, name: str, ms: float, n: int = 1) -> None:
    """Add ``n`` closings of span ``name`` taking ``ms`` in all."""
    stats.span_ms[name] = stats.span_ms.get(name, 0.0) + ms
    stats.span_n[name] = stats.span_n.get(name, 0) + n


def take(stats, src) -> None:
    """Move every span total of ``src`` into ``stats``: time recorded
    before a query had stats of its own (the SQL parse) is counted once,
    by the query that executes first."""
    for name, ms in list(src.span_ms.items()):
        add(stats, name, ms, src.span_n.get(name, 0))
    src.span_ms.clear()
    src.span_n.clear()


class span:
    """Context manager for one span.  ``open`` and ``close`` may also be
    called directly, to start a span at the first piece of work that needs
    it or to end it before the enclosing block does; each is a no-op when
    repeated.  Keyword arguments become the profiler event's metadata."""

    __slots__ = ("name", "stats", "meta", "_event", "_t0")

    def __init__(self, name: str, stats, **meta):
        self.name = name
        self.stats = stats
        self.meta = meta
        self._event = None
        self._t0 = None

    def open(self) -> "span":
        if self._t0 is None:
            annotation = _annotation or _trace_annotation()
            if annotation is not None:
                self._event = annotation(PREFIX + self.name, **self.meta)
                self._event.__enter__()
            self._t0 = perf_counter_ns()
        return self

    def close(self) -> float:
        """End the span; returns its milliseconds (0 if it was not open)."""
        t0 = self._t0
        if t0 is None:
            return 0.0
        ms = (perf_counter_ns() - t0) / 1e6
        self._t0 = None
        if self._event is not None:
            self._event.__exit__(None, None, None)
            self._event = None
        add(self.stats, self.name, ms)
        return ms

    __enter__ = open

    def __exit__(self, *exc) -> None:
        self.close()
