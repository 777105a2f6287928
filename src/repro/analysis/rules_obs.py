"""REPRO007 — metric-name hygiene for the repro.obs layer.

Three checks keep the metric inventory coherent:

* **No direct instrument construction outside ``repro.obs``.**  Call
  sites must go through the ``obs.counter``/``obs.histogram``/
  ``obs.owned_counter``/``obs.span`` helpers (which resolve the
  REPRO_OBS gate and register into the default registry); constructing
  ``Counter``/``Gauge``/``Histogram``/``Journal``/``Registry``/``Span``
  imported from ``repro.obs.metrics``/``repro.obs.trace`` elsewhere
  creates unregistered instruments that never reach a snapshot.
* **One name, one kind.**  The same literal metric name used with
  conflicting instrument kinds (``obs.counter("x")`` in one module,
  ``obs.histogram("x")`` in another) would raise at runtime only when
  both sites happen to run in one process; statically it is always a
  bug.  A span ``obs.span("x")`` owns the histogram name ``x.s``.
* **No raw ``time.perf_counter`` timing in ``service/`` paths.**  The
  service tier reports latency through ``obs.span`` (trace event + duration
  histogram in one call); a bare perf_counter pair is dark telemetry.
  Waiverable as usual for timing that is genuinely not a metric.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from repro.analysis.core import Finding, ParsedFile, Rule, register

RULE_ID = "REPRO007"

#: classes whose construction belongs inside repro/obs/
_INSTRUMENT_CLASSES = frozenset(
    {"Counter", "Gauge", "Histogram", "Registry", "Journal", "Span"})

#: obs helper -> the instrument kind its literal name argument claims
_HELPER_KINDS = {
    "counter": "counter",
    "owned_counter": "counter",
    "gauge": "gauge",
    "derived_gauge": "gauge",
    "owned_gauge": "gauge",
    "histogram": "histogram",
    "span": "span",
}


def _is_obs_file(path: str) -> bool:
    return "repro/obs/" in path or path.endswith("repro/obs")


def _obs_imports(tree: ast.Module) -> Set[str]:
    """Names bound by ``from repro.obs[.metrics|.trace] import ...``."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.startswith("repro.obs"):
            for alias in node.names:
                names.add(alias.asname or alias.name)
    return names


def _helper_call(call: ast.Call) -> Optional[str]:
    """The obs helper name if `call` is ``obs.<helper>(...)`` or a
    bare ``<helper>(...)`` (from-import style), else None."""
    fn = call.func
    if isinstance(fn, ast.Attribute) and fn.attr in _HELPER_KINDS \
            and isinstance(fn.value, ast.Name) and fn.value.id == "obs":
        return fn.attr
    if isinstance(fn, ast.Name) and fn.id in _HELPER_KINDS \
            and fn.id in ("owned_counter", "owned_gauge", "derived_gauge"):
        # bare short names (counter/span/...) are too collision-prone to
        # claim without the obs. prefix; the owned_*/derived_* spellings
        # are unambiguous
        return fn.id
    return None


@register
class MetricHygieneRule(Rule):
    id = RULE_ID
    title = "obs metrics go through repro.obs helpers with consistent names"

    def run(self, files: Sequence[ParsedFile]) -> List[Finding]:
        findings: List[Finding] = []
        # metric name -> (kind, first path, first line)
        seen: Dict[str, Tuple[str, str, int]] = {}

        for f in files:
            obs_names = _obs_imports(f.tree) if not _is_obs_file(f.path) \
                else set()
            in_service = "/service/" in f.path or f.path.startswith("service/")
            for node in ast.walk(f.tree):
                if not isinstance(node, ast.Call):
                    continue
                self._check_direct_construction(
                    f, node, obs_names, findings)
                self._check_name_kinds(f, node, seen, findings)
                if in_service:
                    self._check_perf_counter(f, node, findings)
        return findings

    def _check_direct_construction(self, f: ParsedFile, call: ast.Call,
                                   obs_names: Set[str],
                                   findings: List[Finding]) -> None:
        if _is_obs_file(f.path):
            return
        fn = call.func
        cls: Optional[str] = None
        if isinstance(fn, ast.Name) and fn.id in _INSTRUMENT_CLASSES \
                and fn.id in obs_names:
            cls = fn.id
        elif isinstance(fn, ast.Attribute) and fn.attr in _INSTRUMENT_CLASSES \
                and isinstance(fn.value, ast.Name) \
                and fn.value.id in ("metrics", "trace") \
                and fn.value.id in obs_names:
            cls = fn.attr
        if cls is not None:
            findings.append(Finding(
                RULE_ID, f.path, call.lineno,
                f"direct {cls} construction outside repro.obs; use the "
                f"obs.counter/gauge/histogram/span/owned_* helpers so the "
                f"instrument is registered and REPRO_OBS-gated"))

    def _check_name_kinds(self, f: ParsedFile, call: ast.Call,
                          seen: Dict[str, Tuple[str, str, int]],
                          findings: List[Finding]) -> None:
        helper = _helper_call(call)
        if helper is None or not call.args:
            return
        arg = call.args[0]
        if not (isinstance(arg, ast.Constant) and isinstance(arg.value, str)):
            return
        kind = _HELPER_KINDS[helper]
        # a span owns its duration histogram's name
        name = arg.value + ".s" if kind == "span" else arg.value
        kind = "histogram" if kind == "span" else kind
        prior = seen.get(name)
        if prior is None:
            seen[name] = (kind, f.path, call.lineno)
        elif prior[0] != kind:
            findings.append(Finding(
                RULE_ID, f.path, call.lineno,
                f"metric name {name!r} used as {kind} here but as "
                f"{prior[0]} at {prior[1]}:{prior[2]}; one name, one kind"))

    def _check_perf_counter(self, f: ParsedFile, call: ast.Call,
                            findings: List[Finding]) -> None:
        fn = call.func
        raw = (isinstance(fn, ast.Attribute) and fn.attr == "perf_counter"
               and isinstance(fn.value, ast.Name) and fn.value.id == "time") \
            or (isinstance(fn, ast.Name) and fn.id == "perf_counter")
        if raw:
            findings.append(Finding(
                RULE_ID, f.path, call.lineno,
                "raw time.perf_counter timing in a service/ path bypasses "
                "obs.span (no histogram, no trace event); wrap the block "
                "in obs.span or waive with a reason"))
