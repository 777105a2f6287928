"""Closed loop of batched durable puts: an archive backfill job.

Each client keeps one connection and sends ``put_async`` (``wait: true``)
batches of ``batch`` prompts, the next batch when the last is acked,
from the start of the warm-up until the window closes.  Prompts follow
the paper's corpus law.  The pool of bodies is the same for every seed,
made from a fixed stream at the law's fixed set of sizes; a seed deals
it out to the clients in an order of its own, so seeds differ in order,
not in the bytes they put.  A client cycles through its share of the pool, and a first
line naming client and sequence number makes every put distinct from
every earlier one.

The kind has no hooks: it is held to the checks of acknowledged puts and
the faults every kind can show, and adds none of its own.
"""

from __future__ import annotations

import asyncio
import functools
import time
from typing import Dict, List

import numpy as np

import corpus
import wire

STREAM = 1          # corpus stream of the backfill bodies
BODIES = 0          # the seed of the bodies' content, the same for every run

#: a tiny run off the chip, for the benchmark's tests
REHEARSAL = {"pool_per_client": 4, "warmup_s": 1.0, "readback_sample": 6}
#: the names ``check`` returns (it has none), and the faults of its own
CHECKS: List[str] = []
FAULTS: Dict[str, str] = {}


@functools.lru_cache(maxsize=4)
def _pool_of(clients: int, per_client: int, seed: int) -> tuple:
    """Body of each pool position: a fixed body, dealt by the seed."""
    n = clients * per_client
    sizes = corpus.law_sizes(n)
    kinds = corpus.law_kinds(n)
    pair = np.random.default_rng(STREAM).permutation(n)     # fixed pairing
    order = np.random.default_rng([seed, STREAM]).permutation(n)
    return ([int(b) for b in order], sizes, [kinds[pair[b]] for b in range(n)])


def _pool(params: dict, seed: int) -> tuple:
    return _pool_of(params["clients"], params["pool_per_client"], seed)


def _body(pool: tuple, g: int) -> str:
    order, sizes, kinds = pool
    b = order[g]
    return corpus.prompt(BODIES, STREAM, b, kinds[b], sizes[b],
                         b % corpus.SPECIAL_EVERY == 0)


def _text(params: dict, client: int, seq: int, body: str) -> str:
    return f"[backfill {client}.{seq}]\n" + body


def put_chars(params: dict, config: dict, seed: int, ident: list) -> int:
    """The body size put ``ident`` = [client, seq] was drawn with."""
    client, seq = ident
    g = client + params["clients"] * (seq % params["pool_per_client"])
    order, sizes, _ = _pool(params, seed)
    return sizes[order[g]]


def put_text(params: dict, config: dict, seed: int, ident: list) -> str:
    """The text of put ``ident`` = [client, seq], made again."""
    client, seq = ident
    g = client + params["clients"] * (seq % params["pool_per_client"])
    return _text(params, client, seq, _body(_pool(params, seed), g))


def prepare(params: dict, config: dict, seed: int, client: int,
            seconds: float) -> dict:
    pool = _pool(params, seed)
    bodies = [_body(pool, client + params["clients"] * j)
              for j in range(params["pool_per_client"])]
    return {"params": params, "client": client, "bodies": bodies}


async def drive(state: dict, go: dict) -> dict:
    params, client, bodies = state["params"], state["client"], state["bodies"]
    t1 = go["t1"]
    conn = await wire.Connection.open(go["port"])
    records: List[dict] = []
    seq = 0
    await asyncio.sleep(max(0.0, go["t_start"] - time.monotonic()))
    while time.monotonic() < t1:
        seqs = list(range(seq, seq + params["batch"]))
        seq += params["batch"]
        texts = [_text(params, client, s, bodies[s % len(bodies)])
                 for s in seqs]
        sent = time.monotonic()
        try:
            resp, sent, done = await asyncio.wait_for(
                conn.call("put_async", texts=texts, wait=True,
                          timeout=params["timeout_s"]),
                max(0.0, t1 + go["grace_s"] - sent))
        except asyncio.TimeoutError:
            resp, done = None, time.monotonic()
        rec = {"op": "put", "due": sent, "sent": sent, "done": done,
               "ids": [[client, s] for s in seqs],
               "bytes": sum(len(t.encode("utf-8")) for t in texts)}
        if resp is None:
            rec["status"] = "lost"
            records.append(rec)
            break
        rec["status"] = "ok" if resp.get("ok") else resp.get("error", "error")
        if resp.get("ok"):
            rec["keys"] = resp["keys"]
            rec["keys_ok"] = resp["keys"] == [wire.content_key(t)
                                              for t in texts]
        records.append(rec)
    await conn.close()
    return {"records": records,
            "reused": max(0, seq - len(bodies))}
