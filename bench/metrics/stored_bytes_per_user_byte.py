"""Growth of the store root's on-disk bytes (every file: shard data,
index, meta) per UTF-8 byte acknowledged.  Taken over every put of the
run, from before the first to after the service drained, so that no
group commit is cut at the window's edges; the traffic is the same
throughout, so this is the window's ratio without the edge noise."""


def read(ctx):
    user = sum(r["bytes"] for r in ctx.records
               if r["op"] == "put" and r["status"] == "ok")
    return (ctx.disk1 - ctx.disk0) / user if user else None
