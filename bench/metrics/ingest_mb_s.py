"""UTF-8 bytes of prompts acknowledged durable in the window, per second
of the window (MB = 10**6 bytes, the paper's unit)."""


def read(ctx):
    acked = sum(r["bytes"] for r in ctx.records
                if r["op"] == "put" and r["status"] == "ok"
                and ctx.t0 <= r["done"] < ctx.t1)
    return acked / ctx.seconds / 1e6
