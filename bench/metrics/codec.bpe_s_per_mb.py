"""Seconds of BPE per MB of text the token-pack stage took in, over the
window: the program's ``codec.bpe.encode`` span around the tokenizer's
batch encode, the first part of ``codec.tokenpack_s_per_mb``."""

import stats

STAGE = "{scheme=fixed,stage=token-pack}"


def read(ctx):
    s = stats.hist_sum(ctx, "codec.bpe.encode.s")
    mb = stats.counter_delta(ctx, "codec.encode.bytes_in" + STAGE) / 1e6
    return s / mb if s and mb else None
