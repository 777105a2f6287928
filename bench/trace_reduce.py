"""Reduces a JAX profiler trace of a run's window to the numbers it reports.

    python bench/trace_reduce.py <trace dir or .xplane.pb>   # prints the reduction

* the window is the host span ``bench.window`` the harness opens at the
  start of the window and closes at its end;
* device busy time is the union of the intervals in which an operation
  of the device's "XLA Ops" line ran inside the window, averaged over
  the devices, and its idle share is 1 minus busy over the window;
* a kernel's time is the summed duration, inside the window, of the
  device operations whose name matches its pattern (``bench/work``);
* ``device_ops`` are the ten device instructions (names without their
  shapes) that took most device time, and ``idle_gaps`` the ten longest
  gaps between busy intervals of the first device, each named by the
  most specific host event across it (the shortest of those covering at
  least half as much of the gap as the one that covers most) and the
  share of the gap it covers.
"""

from __future__ import annotations

import re
import sys
from pathlib import Path
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10

Interval = Tuple[float, float]


def xplane_file(path: Path) -> Path:
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _union(intervals: List[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(a: float, b: float, w0: float, w1: float) -> float:
    return max(0.0, min(b, w1) - max(a, w0))


def load(path) -> dict:
    """Planes of the trace as plain lists of (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane_file(path)))
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [ev for line in plane.lines
                                   if line.name == OPS_LINE
                                   for ev in _events(line)]
        elif plane.name.startswith("/host:"):
            host += [ev for line in plane.lines for ev in _events(line)]
    return {"devices": devices, "host": host}


def reduce_planes(planes: dict, kernels: Dict[str, str]) -> dict:
    host = planes["host"]
    windows = [(a, b) for name, a, b in host if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = windows[0]
    if not planes["devices"]:
        raise ValueError("no TPU device plane in the trace")
    busy, op_time = [], {}
    kernel_s = {k: 0.0 for k in kernels}
    kernel_n = {k: 0 for k in kernels}
    patterns = {k: re.compile(p) for k, p in kernels.items()}
    first = sorted(planes["devices"])[0]
    first_busy: List[Interval] = []
    for dev, ops in sorted(planes["devices"].items()):
        inside = [(n, max(a, w0), min(b, w1)) for n, a, b in ops
                  if _clip(a, b, w0, w1) > 0]
        merged = _union([(a, b) for _, a, b in inside])
        busy.append(sum(b - a for a, b in merged))
        if dev == first:
            first_busy = merged
        for n, a, b in inside:
            op = n.split(" = ", 1)[0]       # the instruction, not its shapes
            op_time[op] = op_time.get(op, 0.0) + (b - a)
            for k, pat in patterns.items():
                if pat.search(n):
                    kernel_s[k] += (b - a) * 1e-9
                    kernel_n[k] += 1
    edges = [w0] + [x for iv in first_busy for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for a, b in gaps[:TOP]:
        over = [(_clip(ha, hb, a, b), hb - ha, n) for n, ha, hb in host
                if n != WINDOW_SPAN and _clip(ha, hb, a, b) > 0]
        name = "no host event"
        if over:
            best = max(o for o, _, _ in over)
            # the most specific activity: the shortest event that covers at
            # least half as much of the gap as the one that covers most
            ov, _, n = min((x for x in over if x[0] >= best / 2),
                           key=lambda x: x[1])
            name = f"{n} ({ov / (b - a) * 100:.0f}% of the gap)"
        idle.append([name, (b - a) * 1e-9])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "kernel_s": kernel_s,
        "kernel_n": kernel_n,
        "device_ops": [[n, t * 1e-9] for n, t in ops],
        "idle_gaps": idle,
    }


def reduce(path, kernels: Dict[str, str]) -> dict:
    return reduce_planes(load(path), kernels)


if __name__ == "__main__":
    import json

    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import spec

    pats = {p.stem: spec.load_module(p).OP_NAME
            for p in sorted((spec.BENCH / "work").glob("*.py"))}
    print(json.dumps(reduce(sys.argv[1], pats), indent=1))
