"""Share of its roofline the token-pack kernel reached in the window: the
least time the chip could take for the launches' work (the larger of
operations over peak operations and bytes over peak HBM bandwidth),
over the kernel's summed device time in the trace."""

import spec


def read(ctx):
    if not ctx.trace or not ctx.launches.get("token_pack"):
        return None
    t = ctx.trace["kernel_s"].get("token_pack", 0.0)
    if t <= 0:
        return None
    work = spec.load_module(spec.BENCH / "work" / "token_pack.py")
    ops, nbytes = work.work(ctx.launches["token_pack"])
    least = max(ops / ctx.peaks["flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return least / t * 100.0
