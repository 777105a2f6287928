"""Prompts per group commit of the ingest queue in the window."""

import stats


def read(ctx):
    flushes = stats.counter_delta(ctx, "ingest.flushes")
    return (stats.counter_delta(ctx, "ingest.committed") / flushes
            if flushes else None)
