"""Seconds of fixed-width packing per MB of text the token-pack stage
took in, over the window: the program's ``codec.pack.encode`` span
around the pack call (the kernel's launch, its read-back and any compile
it triggers), the second part of ``codec.tokenpack_s_per_mb``."""

import stats

STAGE = "{scheme=fixed,stage=token-pack}"


def read(ctx):
    s = stats.hist_sum(ctx, "codec.pack.encode.s")
    mb = stats.counter_delta(ctx, "codec.encode.bytes_in" + STAGE) / 1e6
    return s / mb if s and mb else None
