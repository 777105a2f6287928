"""Shared arithmetic of the metric readers: latencies and percentiles."""

from __future__ import annotations

import math
from typing import List, Optional


def latency_ms(ctx, rec: dict) -> float:
    """From the time a request was due to its last response byte.  One
    that failed, was refused or never came is given the longest wait the
    run allows (to the close plus the grace), past every limit."""
    if rec["status"] != "ok":
        return (ctx.t1 + ctx.grace_s - rec["due"]) * 1e3
    return (rec["done"] - rec["due"]) * 1e3


def nearest_rank(values: List[float], q: float) -> Optional[float]:
    """The q-quantile (0 < q <= 1) by nearest rank over all values."""
    if not values:
        return None
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def hist_sum(ctx, name: str) -> Optional[float]:
    """Sum of a histogram's samples in the window (None if it had none)."""
    h = ctx.obs["histograms"].get(name)
    if not h or not h["count_delta"]:
        return None
    return h["mean_in_window"] * h["count_delta"]


def hist_mean(ctx, name: str) -> Optional[float]:
    h = ctx.obs["histograms"].get(name)
    if not h or not h["count_delta"]:
        return None
    return h["mean_in_window"]


def counter_delta(ctx, name: str) -> int:
    c = ctx.obs["counters"].get(name)
    return c["delta"] if c else 0
