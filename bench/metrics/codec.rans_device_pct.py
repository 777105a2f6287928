"""Share of the records the LZ77 + rANS byte stage entropy-coded on the
device in the window (the program's ``codec.rans.records`` counter, by
path); nothing where the program keeps no such counter."""

import stats


def read(ctx):
    device = stats.counter_delta(ctx, "codec.rans.records{path=device}")
    records = device + stats.counter_delta(ctx,
                                           "codec.rans.records{path=host}")
    return device / records * 100.0 if records else None
