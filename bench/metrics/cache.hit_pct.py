"""Share of the token cache's lookups in the window that hit (the
program's ``cache.hits`` and ``cache.misses`` counters)."""

import stats


def read(ctx):
    hits = stats.counter_delta(ctx, "cache.hits")
    lookups = hits + stats.counter_delta(ctx, "cache.misses")
    return hits / lookups * 100.0 if lookups else None
