"""Mean time a planned flush's shard part waited for its writer thread,
from being queued to the writer starting its durable commit, in the
window (the program's ``ingest.writer_queue.s``)."""

import stats


def read(ctx):
    m = stats.hist_mean(ctx, "ingest.writer_queue.s")
    return m * 1e3 if m is not None else None
