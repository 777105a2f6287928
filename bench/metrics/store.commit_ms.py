"""Mean time of one shard's durable commit (append, fsyncs, index
publish) in the window."""

import stats


def read(ctx):
    m = stats.hist_mean(ctx, "store.commit.s")
    return m * 1e3 if m is not None else None
