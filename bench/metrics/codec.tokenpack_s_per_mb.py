"""Seconds of the token-pack stage's encode (BPE, the pack launch and any
compile it triggers) per MB of text it took in, over the window."""

import stats

STAGE = "{scheme=fixed,stage=token-pack}"


def read(ctx):
    s = stats.hist_sum(ctx, "codec.encode.s" + STAGE)
    mb = stats.counter_delta(ctx, "codec.encode.bytes_in" + STAGE) / 1e6
    return s / mb if s and mb else None
