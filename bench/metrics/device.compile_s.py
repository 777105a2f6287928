"""Seconds of XLA backend compiles in the window, summed over every
jitted function: the program's ``device.compile.s{fn=...}`` histograms,
fed by a JAX compile-event listener from the first device routing
decision on.  None where the program has no such histogram."""

PREFIX = "device.compile.s{"


def read(ctx):
    hists = [h for name, h in ctx.obs["histograms"].items()
             if name.startswith(PREFIX)]
    if not hists:
        return None
    return sum(h["mean_in_window"] * h["count_delta"] for h in hists)
