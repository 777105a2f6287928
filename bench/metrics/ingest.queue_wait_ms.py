"""Mean time a submission waited in the ingest queue, from ``submit``
(backpressure included) to the dispatcher taking it into a flush, in
the window (the program's ``ingest.queue.s``)."""

import stats


def read(ctx):
    m = stats.hist_mean(ctx, "ingest.queue.s")
    return m * 1e3 if m is not None else None
