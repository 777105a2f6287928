"""Process start to window start: imports, chip start, vocabulary,
gateway start and warm-up, compilation included."""


def read(ctx):
    return ctx.setup_s
