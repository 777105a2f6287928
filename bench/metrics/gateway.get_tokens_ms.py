"""Mean time the gateway took to execute one ``get_tokens`` request in
the window, on its executor thread: the token cache, the store's read
and decode on a miss, and the ids turned into a list (the program's
``gateway.request`` span, ``op=get_tokens``).  The rest of a get's
latency is framing, the event loops and the wait for either."""

import stats


def read(ctx):
    m = stats.hist_mean(ctx, "gateway.request.s{op=get_tokens}")
    return m * 1e3 if m is not None else None
