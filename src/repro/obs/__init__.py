"""repro.obs — unified metrics + tracing for the whole runtime.

Call-site API (the only one instrumented code should use; the static
rule REPRO007 flags direct construction of the underlying classes):

* ``obs.counter(name, **labels)`` / ``obs.gauge`` / ``obs.histogram``
  — get-or-create a shared instrument in the process-global registry.
* ``obs.derived_gauge(name, fn, **labels)`` — a gauge whose value is
  computed at snapshot time (live compression ratio, MB/s).
* ``obs.span(name, trace_args=None, **labels)`` — context manager
  timing a block into a ``<name>.s`` histogram and, while a JAX
  profiler trace runs, into a trace event of the same name whose
  metadata is the labels, ``trace_args`` and the thread's
  ``obs.trace_context(**args)``; usable as the product's timing source
  via ``span.elapsed_s``/``span.duration_s``.
* ``obs.owned_counter(name, **labels)`` — an always-real counter owned
  by one component instance (``TokenCache`` hit/miss counts feed its
  ``stats()`` dict and must keep counting with obs disabled); it is
  *registered* into the global registry only when obs is enabled, with
  replace-on-reregister so snapshots follow the newest instance.
* ``obs.snapshot()`` — export; the spans' events are in the profiler's
  trace (:mod:`repro.obs.trace`).

Disabled mode (``REPRO_OBS=0``): the factories return shared no-op
stubs, resolved once at instrument creation — a disabled counter's
``inc`` is a single no-op method call, and nothing is registered.
``span`` still reads the clock (see :mod:`repro.obs.trace`).  The flag
is read per *factory call* — instruments are created at component
construction time, never per sample — so tests can flip the knob
between components without reimporting.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from repro.core import env
from repro.obs import export as _export
from repro.obs.metrics import (Counter, Gauge, Histogram, Registry,
                               canonical_name)
from repro.obs.trace import NullSpan, Span, trace_context

__all__ = [
    "enabled", "counter", "gauge", "derived_gauge", "histogram", "span",
    "owned_counter", "owned_gauge", "snapshot", "diff", "render",
    "render_diff", "trace_context", "default_registry", "reset",
]


def enabled() -> bool:
    return bool(env.read("REPRO_OBS"))


class _NullCounter:
    kind = "counter"
    name = "<null>"
    __slots__ = ()

    def inc(self, n: int = 1) -> None:
        pass

    @property
    def value(self) -> int:
        return 0


class _NullGauge:
    kind = "gauge"
    name = "<null>"
    __slots__ = ()

    def set(self, value: float) -> None:
        pass

    @property
    def value(self) -> float:
        return 0.0


class _NullHistogram:
    kind = "histogram"
    name = "<null>"
    count = 0
    sum = 0.0
    __slots__ = ()

    def observe(self, value: float) -> None:
        pass

    def percentile(self, q: float) -> float:
        return 0.0


NULL_COUNTER = _NullCounter()
NULL_GAUGE = _NullGauge()
NULL_HISTOGRAM = _NullHistogram()

_registry = Registry()


def default_registry() -> Registry:
    return _registry


def reset() -> None:
    """Fresh registry (tests); instruments already handed out keep
    working but stop appearing in snapshots."""
    global _registry
    _registry = Registry()


def counter(name: str, **labels):
    if not enabled():
        return NULL_COUNTER
    return _registry.counter(name, **labels)


def gauge(name: str, **labels):
    if not enabled():
        return NULL_GAUGE
    return _registry.gauge(name, **labels)


def derived_gauge(name: str, fn: Callable[[], float], **labels):
    if not enabled():
        return NULL_GAUGE
    return _registry.gauge(name, fn=fn, **labels)


def histogram(name: str, **labels):
    if not enabled():
        return NULL_HISTOGRAM
    return _registry.histogram(name, **labels)


def owned_counter(name: str, **labels) -> Counter:
    """A real :class:`Counter` regardless of REPRO_OBS — for component
    counters whose values feed product ``stats()`` dicts.  Registered
    globally (replacing any prior instance's) only when obs is on."""
    key = canonical_name(name, labels)
    inst = Counter(key)
    if enabled():
        _registry.register(inst, replace=True)
    return inst


def owned_gauge(name: str, fn: Callable[[], float], **labels):
    """Per-instance derived gauge: unlike :func:`derived_gauge` (which
    get-or-creates, so an older instance's callable would win), this
    replaces any prior registration — snapshots follow the newest
    component instance."""
    if not enabled():
        return NULL_GAUGE
    key = canonical_name(name, labels)
    inst = Gauge(key, fn=fn)
    _registry.register(inst, replace=True)
    return inst


def span(name: str, trace_args: Optional[Dict[str, Any]] = None,
         **labels):
    """Time a block into ``<name>.s{labels}``; ``trace_args`` go into
    its trace event only (identifiers, sizes), never into the
    histogram's name."""
    if not enabled():
        return NullSpan()
    hist = _registry.histogram(name + ".s", **labels)
    return Span(name, labels, hist, trace_args)


def snapshot() -> Dict[str, Any]:
    return _export.snapshot(_registry)


diff = _export.diff
render = _export.render
render_diff = _export.render_diff
