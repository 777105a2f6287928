"""Share of the window the ingest queue's one dispatcher thread spent
planning flushes (BPE, the pack launch, the byte stage, handing shard
parts to the writers): the sum of the program's ``ingest.dispatch``
spans over the window's seconds."""

import stats


def read(ctx):
    s = stats.hist_sum(ctx, "ingest.dispatch.s")
    return s / ctx.seconds * 100.0 if s is not None else None
