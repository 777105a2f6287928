"""Span tracing: a timing context manager whose events land in the
JAX profiler's trace.

``obs.span("codec.compress", method="hybrid")`` is the one-liner call
sites use; it times the enclosed block and feeds a duration histogram
named ``codec.compress.s{method=hybrid}``.  While a profiler trace is
running (``jax.profiler.start_trace``), the span also opens a
``jax.profiler.TraceAnnotation`` of the same name, so every program
span sits on the trace's host plane, on the clock the device's
operations are on.  The profiler keeps the events in memory and writes
them out at ``stop_trace``.

The event's metadata is the span's labels, its ``trace_args`` and the
calling thread's trace context (:func:`trace_context`), which stamps
every span opened under it — the ingest queue gives each group commit's
spans, on the dispatcher and on the writers alike, one ``flush`` id.
Identifiers go only there: as histogram labels they would make one
histogram per flush.  An exception leaving the span is recorded as an
``error`` stat naming its type.

JAX is never imported from here: the annotation is opened only when
``jax.profiler`` is already loaded, so ``repro.obs`` stays stdlib-only
to import and CPU-only tools keep working without JAX.

The disabled-mode twin (:class:`NullSpan`) reads the clock and nothing
else: spans double as the *product's* timing source
(``CompactionResult.wall_s`` comes from ``span.elapsed_s``), so
``duration_s`` must stay correct with observability off.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Any, Dict, Iterator, Optional

from repro.obs.metrics import Histogram

_context = threading.local()


@contextlib.contextmanager
def trace_context(**args: Any) -> Iterator[None]:
    """Metadata for every span this thread opens inside the block (an
    inner context adds to, and may override, an outer one)."""
    outer: Optional[Dict[str, Any]] = getattr(_context, "args", None)
    _context.args = {**outer, **args} if outer else args
    try:
        yield
    finally:
        _context.args = outer


def _annotation(name: str, labels: Dict[str, Any],
                trace_args: Optional[Dict[str, Any]]):
    """An entered ``TraceAnnotation`` if a profiler trace is running in
    this process, else None (the profiler would drop the event anyway)."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None or not profiler.TraceAnnotation.is_enabled():
        return None
    args = dict(getattr(_context, "args", None) or ())
    args.update(labels)
    if trace_args:
        args.update(trace_args)
    ann = profiler.TraceAnnotation(name, **args)
    ann.__enter__()
    return ann


class Span:
    """Enabled-mode span: times the block into its histogram and, while
    a profiler trace runs, into a trace event."""

    __slots__ = ("name", "labels", "trace_args", "_hist", "_ann", "_t0",
                 "duration_s")

    def __init__(self, name: str, labels: Dict[str, Any], hist: Histogram,
                 trace_args: Optional[Dict[str, Any]] = None):
        self.name = name
        self.labels = labels
        self.trace_args = trace_args
        self._hist = hist
        self._ann = None
        self._t0 = 0.0
        self.duration_s = 0.0

    def __enter__(self) -> "Span":
        self._ann = _annotation(self.name, self.labels, self.trace_args)
        self._t0 = time.perf_counter()
        return self

    @property
    def elapsed_s(self) -> float:
        """Seconds since ``__enter__`` (live, readable mid-span)."""
        return time.perf_counter() - self._t0

    def __exit__(self, exc_type, exc, tb) -> None:
        self.duration_s = time.perf_counter() - self._t0
        self._hist.observe(self.duration_s)
        ann, self._ann = self._ann, None
        if ann is not None:
            if exc_type is not None:
                ann.set_metadata(error=exc_type.__name__)
            ann.__exit__(exc_type, exc, tb)


class NullSpan:
    """Disabled-mode span: clock only, records nothing.

    Not a singleton — spans carry per-use timing state — but
    construction is two attribute writes and the context protocol costs
    two ``perf_counter`` reads.
    """

    __slots__ = ("_t0", "duration_s")

    def __init__(self):
        self._t0 = 0.0
        self.duration_s = 0.0

    def __enter__(self) -> "NullSpan":
        self._t0 = time.perf_counter()
        return self

    @property
    def elapsed_s(self) -> float:
        return time.perf_counter() - self._t0

    def __exit__(self, exc_type, exc, tb) -> None:
        self.duration_s = time.perf_counter() - self._t0
