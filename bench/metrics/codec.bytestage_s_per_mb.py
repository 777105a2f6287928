"""Seconds of the byte stage's encode (zstd, or LZ77 + rANS) per MB of
packed ids it took in, over the window."""

import stats


def read(ctx):
    stage = "{backend=%s,stage=byte-compressor}" % ctx.cell.config["backend"]
    s = stats.hist_sum(ctx, "codec.encode.s" + stage)
    mb = stats.counter_delta(ctx, "codec.encode.bytes_in" + stage) / 1e6
    return s / mb if s and mb else None
