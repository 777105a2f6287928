"""Snapshot exporter: registry -> dict, render, and snapshot diffing.

A snapshot is a plain JSON-serializable dict::

    {"version": 1, "ts": <time.time()>,
     "counters": {name: int}, "gauges": {name: float},
     "histograms": {name: {count,sum,min,max,mean,p50,p90,p99,buckets}}}

Two snapshots of the same process diff into *rates*: counter deltas
divided by the wall-clock gap, histogram count/sum deltas plus the
mean within the window.  That is how the paper's throughput numbers
(MB/s) fall out of two live snapshots instead of a dedicated benchmark
run.
"""

from __future__ import annotations

import time
from typing import Any, Dict

from repro.obs.metrics import Registry

SNAPSHOT_VERSION = 1


def snapshot(registry: Registry) -> Dict[str, Any]:
    snap: Dict[str, Any] = {"version": SNAPSHOT_VERSION, "ts": time.time()}
    snap.update(registry.snapshot())
    return snap


def _fmt(v: float) -> str:
    if isinstance(v, int):
        return str(v)
    if v == 0:
        return "0"
    if abs(v) >= 1000 or abs(v) < 0.001:
        return f"{v:.3e}"
    return f"{v:.4g}"


def render(snap: Dict[str, Any]) -> str:
    """Human-readable one-metric-per-line view of a snapshot."""
    lines = []
    for name in sorted(snap.get("counters", {})):
        lines.append(f"counter   {name} = {snap['counters'][name]}")
    for name in sorted(snap.get("gauges", {})):
        lines.append(f"gauge     {name} = {_fmt(snap['gauges'][name])}")
    for name in sorted(snap.get("histograms", {})):
        h = snap["histograms"][name]
        lines.append(
            f"histogram {name} count={h['count']} mean={_fmt(h['mean'])} "
            f"p50={_fmt(h['p50'])} p90={_fmt(h['p90'])} "
            f"p99={_fmt(h['p99'])} max={_fmt(h['max'])}")
    return "\n".join(lines)


def diff(before: Dict[str, Any], after: Dict[str, Any]) -> Dict[str, Any]:
    """Rates between two snapshots of the same process.

    Counters report ``delta`` and ``rate_per_s``; histograms report the
    sample-count delta, its rate, and the mean value *within the
    window*; gauges report before/after.  Metrics absent from the
    earlier snapshot are treated as starting at zero.
    """
    dt = max(float(after.get("ts", 0.0)) - float(before.get("ts", 0.0)),
             1e-9)
    out: Dict[str, Any] = {"dt_s": dt, "counters": {}, "gauges": {},
                           "histograms": {}}
    for name, val in sorted(after.get("counters", {}).items()):
        delta = val - before.get("counters", {}).get(name, 0)
        out["counters"][name] = {"delta": delta, "rate_per_s": delta / dt}
    for name, val in sorted(after.get("gauges", {}).items()):
        out["gauges"][name] = {
            "before": before.get("gauges", {}).get(name, 0.0),
            "after": val}
    empty = {"count": 0, "sum": 0.0}
    for name, h in sorted(after.get("histograms", {}).items()):
        h0 = before.get("histograms", {}).get(name, empty)
        dcount = h["count"] - h0["count"]
        dsum = h["sum"] - h0["sum"]
        out["histograms"][name] = {
            "count_delta": dcount,
            "rate_per_s": dcount / dt,
            "mean_in_window": (dsum / dcount) if dcount else 0.0,
        }
    return out


def render_diff(d: Dict[str, Any]) -> str:
    lines = [f"window: {d['dt_s']:.3f}s"]
    for name, c in d["counters"].items():
        lines.append(f"counter   {name} +{c['delta']} "
                     f"({_fmt(c['rate_per_s'])}/s)")
    for name, g in d["gauges"].items():
        lines.append(f"gauge     {name} {_fmt(g['before'])} -> "
                     f"{_fmt(g['after'])}")
    for name, h in d["histograms"].items():
        lines.append(f"histogram {name} +{h['count_delta']} samples "
                     f"({_fmt(h['rate_per_s'])}/s, "
                     f"mean {_fmt(h['mean_in_window'])})")
    return "\n".join(lines)
