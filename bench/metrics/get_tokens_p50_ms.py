"""Median latency of the window's ``get_tokens`` requests, from when each
was due to its last response byte (one that failed or never came counts
past every limit)."""

import stats


def read(ctx):
    return stats.nearest_rank([stats.latency_ms(ctx, r) for r in ctx.window
                               if r["op"] == "get_tokens"], 0.5)
