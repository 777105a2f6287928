"""Open loop of YCSB-B reads and updates over preloaded records.

YCSB's workload B (CoreWorkload: readproportion 0.95, updateproportion
0.05, requestdistribution zipfian, recordcount 1000) as the users of a
prompt store send it:

* ``setup`` preloads ``records`` corpus-law prompts through the
  gateway's put path (``put_async`` with ``wait``, batches of
  ``preload_batch`` over ``clients`` connections); puts once each, one
  at a time, the window's update bodies of at least ``warm_min_chars``
  characters, so that the single-prompt pack launches of the window's
  large updates are compiled in set-up; and reads every record once
  with ``get_tokens``, so that the window starts from a running store's
  token cache and not a cold one;
* from ``t_start`` requests arrive as a Poisson process of
  ``rate_per_s``, dealt round-robin to the ``clients`` generator
  processes, each on one pipelined connection; a request is sent when
  it falls due, whatever is in flight, and is timed from then;
* a request is a ``get_tokens`` of one record (a share ``get_share``),
  or an update: ``put_async`` (``wait: true``) of one fresh corpus-law
  prompt.  A content-addressed store has no update in place: an update
  is a new text under a new key;
* keys follow YCSB's ScrambledZipfianGenerator: a Zipfian rank
  (θ = 0.99) over 10**10 items, FNV-64 hashed onto the records.

Every seed makes the same work in another order: the gaps between
arrivals are the exponential law's quantiles, the Zipfian draws a fixed
stream's, and the updates' count fixed; a seed permutes the gaps, the
updates' positions and the draws.  Bodies are made as the ingest kind
makes them: the corpus law's fixed set of sizes, content from a fixed
stream, the same for every seed.

``check`` compares every ``get_tokens`` answer with the reference's
token ids of its key's text, by digest, once per distinct key
(``get_tokens_bad``), and counts answers for keys that no acknowledged
put wrote (``get_unknown_key``).
"""

from __future__ import annotations

import asyncio
import functools
import sys
import time
from typing import List, Optional

import numpy as np

import corpus
import wire

BODIES = 0          # the seed of the bodies' content, the same for every run
RECORDS = 2         # corpus stream of the preloaded records
UPDATES = 3         # corpus stream of the update bodies
DRAWS = 4           # fixed stream of the Zipfian draws
SCHEDULE = 5        # the seed's stream that orders the work

# YCSB's ScrambledZipfianGenerator: ITEM_COUNT, ZIPFIAN_CONSTANT and ZETAN,
# its precomputed zeta(ITEM_COUNT, 0.99)
ZIPF_ITEMS = 10_000_000_000
ZIPF_THETA = 0.99
ZIPF_ZETAN = 26.46902820178302
FNV_OFFSET = 0xCBF29CE484222325
FNV_PRIME = 1099511628211


def _fnv64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` of each value, as Java's signed long."""
    v = values.astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            h *= np.uint64(FNV_PRIME)
            v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def zipf_draws(n: int, records: int) -> np.ndarray:
    """The first ``n`` record indices of the fixed stream of draws."""
    u = np.random.default_rng(DRAWS).random(n)
    items = ZIPF_ITEMS + 1
    zeta2 = 1.0 + 0.5 ** ZIPF_THETA
    alpha = 1.0 / (1.0 - ZIPF_THETA)
    eta = ((1.0 - (2.0 / items) ** (1.0 - ZIPF_THETA))
           / (1.0 - zeta2 / ZIPF_ZETAN))
    rank = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    uz = u * ZIPF_ZETAN
    rank = np.where(uz < zeta2, 1, rank)
    rank = np.where(uz < 1.0, 0, rank)
    return _fnv64(rank) % records


@functools.lru_cache(maxsize=8)
def _law(n: int, stream: int) -> tuple:
    """Size and content kind of each body of a pool of ``n``: the law's
    fixed set of sizes and its mix, each dealt by a fixed permutation."""
    rng = np.random.default_rng(stream)
    sizes, kinds = corpus.law_sizes(n), corpus.law_kinds(n)
    return ([sizes[i] for i in rng.permutation(n)],
            [kinds[i] for i in rng.permutation(n)])


@functools.lru_cache(maxsize=8192)
def _body(n: int, stream: int, b: int) -> str:
    sizes, kinds = _law(n, stream)
    return corpus.prompt(BODIES, stream, b, kinds[b], sizes[b],
                         b % corpus.SPECIAL_EVERY == 0)


def _schedule(params: dict, seed: int, seconds: float) -> tuple:
    """The run's requests in order of arrival: their offsets from
    ``t_start``, which are updates, and the records the reads draw."""
    span = params["warmup_s"] + seconds
    n = max(1, round(params["rate_per_s"] * span))
    n_put = round(n * (1.0 - params["get_share"]))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= span / gaps.sum()
    rng = np.random.default_rng([seed, SCHEDULE])
    gaps = rng.permutation(gaps)
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    is_put = np.zeros(n, bool)
    is_put[rng.permutation(n)[:n_put]] = True
    draws = rng.permutation(zipf_draws(n - n_put, params["records"]))
    return offsets, is_put, draws, n_put


def _header(tag: str, j: int) -> str:
    return f"[{tag} {j:06d}]\n"


def put_text(params: dict, config: dict, seed: int, ident: list) -> str:
    """The text of put ``ident``, made again: ``["record", i]``, or
    ``["update", j, P]`` / ``["warmup", j, P]`` for body ``j`` of the
    run's ``P`` update bodies."""
    if ident[0] == "record":
        return _body(params["records"], RECORDS, ident[1])
    tag, j, pool = ident
    return _header(tag, j) + _body(pool, UPDATES, j)


def put_chars(params: dict, config: dict, seed: int, ident: list) -> int:
    """The body size put ``ident`` was drawn with."""
    if ident[0] == "record":
        return _law(params["records"], RECORDS)[0][ident[1]]
    return _law(ident[2], UPDATES)[0][ident[1]]


def prepare(params: dict, config: dict, seed: int, client: int,
            seconds: float) -> dict:
    offsets, is_put, draws, n_put = _schedule(params, seed, seconds)
    requests = []
    puts = gets = 0
    for k in range(len(offsets)):
        if is_put[k]:
            arg = ["update", puts, n_put]
            puts += 1
        else:
            arg = int(draws[gets])
            gets += 1
        if k % params["clients"] == client:
            if isinstance(arg, list):
                arg = (arg, put_text(params, config, seed, arg))
            requests.append((float(offsets[k]), arg))
    return {"params": params, "requests": requests}


async def _put(conn: wire.Pipeline, idents: list, texts: List[str],
               timeout_s: float, due: Optional[float],
               deadline: float) -> dict:
    resp, sent, done = await conn.call("put_async", deadline, texts=texts,
                                       wait=True, timeout=timeout_s)
    rec = {"op": "put", "due": sent if due is None else due, "sent": sent,
           "done": done, "ids": idents,
           "bytes": sum(len(t.encode("utf-8")) for t in texts)}
    if resp is None:
        rec["status"] = "lost"
        return rec
    rec["status"] = "ok" if resp.get("ok") else resp.get("error", "error")
    if resp.get("ok"):
        rec["keys"] = resp["keys"]
        rec["keys_ok"] = resp["keys"] == [wire.content_key(t) for t in texts]
    return rec


async def _get(conn: wire.Pipeline, keys: List[str], due: Optional[float],
               deadline: float) -> dict:
    resp, sent, done = await conn.call("get_tokens", deadline, keys=keys)
    rec = {"op": "get_tokens", "due": sent if due is None else due,
           "sent": sent, "done": done, "keys": keys}
    if resp is None:
        rec["status"] = "lost"
        return rec
    rec["status"] = "ok" if resp.get("ok") else resp.get("error", "error")
    if resp.get("ok"):
        rec["digests"] = [wire.ids_digest(t) for t in resp["tokens"]]
    return rec


async def drive(state: dict, go: dict) -> dict:
    params, keys = state["params"], go["keys"]
    t1, deadline = go["t1"], go["t1"] + go["grace_s"]
    conn = await wire.Pipeline.open(go["port"])
    tasks = []
    for offset, arg in state["requests"]:
        due = go["t_start"] + offset
        if due >= t1:
            break
        await asyncio.sleep(max(0.0, due - time.monotonic()))
        if isinstance(arg, int):
            call = _get(conn, [keys[arg]], due, deadline)
        else:
            ident, text = arg
            call = _put(conn, [ident], [text], params["timeout_s"], due,
                        deadline)
        tasks.append(asyncio.ensure_future(call))
    records = list(await asyncio.gather(*tasks))
    await conn.close()
    return {"records": records}


def setup(params: dict, config: dict, seed: int, seconds: float,
          port: int) -> dict:
    return asyncio.run(_setup(params, seed, seconds, port))


async def _setup(params: dict, seed: int, seconds: float, port: int) -> dict:
    n, batch = params["records"], params["preload_batch"]
    timeout_s = params["timeout_s"]
    deadline = time.monotonic() + 10 * timeout_s
    conns = [await wire.Pipeline.open(port) for _ in range(params["clients"])]
    records: List[dict] = []
    starts = iter(range(0, n, batch))

    async def preload(conn: wire.Pipeline) -> None:
        for i0 in starts:
            idents = [["record", i] for i in range(i0, min(n, i0 + batch))]
            texts = [put_text(params, {}, seed, x) for x in idents]
            records.append(await _put(conn, idents, texts, timeout_s, None,
                                      deadline))

    await asyncio.gather(*(preload(c) for c in conns))
    n_put = _schedule(params, seed, seconds)[3]
    sizes = _law(n_put, UPDATES)[0] if n_put else []
    for j in range(n_put):
        if sizes[j] >= params["warm_min_chars"]:
            ident = ["warmup", j, n_put]
            records.append(await _put(
                conns[0], [ident], [put_text(params, {}, seed, ident)],
                timeout_s, None, deadline))
    keys = [wire.content_key(put_text(params, {}, seed, ["record", i]))
            for i in range(n)]
    starts = iter(range(0, n, batch))

    async def read(conn: wire.Pipeline) -> None:
        for i0 in starts:
            records.append(await _get(conn, keys[i0:i0 + batch], None,
                                      deadline))

    await asyncio.gather(*(read(c) for c in conns))
    for c in conns:
        await c.close()
    return {"records": records, "go": {"keys": keys}}


def check(ctx, encoder) -> dict:
    params, config = ctx.cell.traffic, ctx.cell.config
    written = {key: ident for r in ctx.records
               if r["op"] == "put" and r["status"] == "ok"
               for ident, key in zip(r["ids"], r["keys"])}
    want = {}
    answers = bad = unknown = 0
    for r in ctx.records:
        if r["op"] != "get_tokens" or r["status"] != "ok":
            continue
        digests = r["digests"]
        for i, key in enumerate(r["keys"]):
            answers += 1
            if key not in written:
                unknown += 1
                continue
            if key not in want:
                want[key] = wire.ids_digest(encoder.encode(
                    put_text(params, config, ctx.seed, written[key])))
            bad += i >= len(digests) or digests[i] != want[key]
    print(f"[bench] compared: {answers} get_tokens answers of {len(want)} "
          f"keys with the reference's token ids", file=sys.stderr, flush=True)
    return {"get_tokens_bad": bad, "get_unknown_key": unknown}
