#!/usr/bin/env python
"""Serving launcher: LoPace PromptStore admission + slot-batched decode,
optionally fronted by the repro.service tier.

    PYTHONPATH=src python -m repro.launch.serve --requests 8
    PYTHONPATH=src python -m repro.launch.serve --cache-mb 32 --compact \
        --ingest-async

`--cache-mb` admits prompts through the serve-path token cache,
`--ingest-async` builds the corpus store through the async ingest queue,
`--compact` runs a stage-reselecting compaction pass before serving
(`--train-dict` lets it train and adopt per-shard dictionaries), and
`--rebalance N` re-partitions the store across N shards online first.
"""

from __future__ import annotations

import argparse
import tempfile
import time

import jax

from repro.core.device import enable_compile_cache
from repro.data.pipeline import build_store_from_corpus
from repro.launch.statsdump import start_stats_dumper, write_snapshot
from repro.train.serve_loop import BatchServer
from repro.train.train_loop import init_train_state


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--shards", type=int, default=4,
                    help="PromptStore segment count (group-commit batch writes)")
    ap.add_argument("--cache-mb", type=float, default=0.0,
                    help="serve-path token cache budget in MB (0 = no cache)")
    ap.add_argument("--ingest-async", action="store_true",
                    help="ingest the corpus through the async ingest queue "
                         "(per-shard parallel group commits)")
    ap.add_argument("--compact", action="store_true",
                    help="run a stage-reselecting compaction pass over every "
                         "shard before serving")
    ap.add_argument("--train-dict", action="store_true",
                    help="let the compaction pass train per-shard "
                         "dictionaries and adopt them on a strict "
                         "total-bytes win (implies --compact)")
    ap.add_argument("--rebalance", type=int, default=0, metavar="N",
                    help="re-partition the store across N shards online "
                         "before serving (0 = keep the built layout)")
    ap.add_argument("--smoke", action="store_true",
                    help="quick mode: small request/slot/decode budgets, "
                         "async ingest and a token cache on — exercises "
                         "every instrumented path in a few seconds")
    ap.add_argument("--stats-interval", type=float, default=0.0, metavar="N",
                    help="every N seconds print the obs metric rates since "
                         "the previous dump (0 = off)")
    ap.add_argument("--stats-json", metavar="PATH", default=None,
                    help="write the final repro.obs snapshot to PATH as JSON")
    args = ap.parse_args(argv)
    if args.rebalance < 0:
        ap.error(f"--rebalance ({args.rebalance}) must be >= 0")
    if args.stats_interval < 0:
        ap.error(f"--stats-interval ({args.stats_interval}) must be >= 0")
    if args.smoke:
        args.requests = min(args.requests, 4)
        args.slots = min(args.slots, 2)
        args.max_new = min(args.max_new, 8)
        args.ingest_async = True
        if args.cache_mb == 0.0:
            args.cache_mb = 8.0
    # an oversized --max-new would otherwise silently truncate the prompt
    # to an empty or negative slice in BatchServer._fill_slots
    # (prompt_tokens[:max_len - max_new - 1]) — refuse at parse time;
    # max_len - 2 is the largest budget leaving >= 1 prompt token
    if args.max_new > args.max_len - 2:
        ap.error(f"--max-new ({args.max_new}) must be <= --max-len - 2 "
                 f"({args.max_len - 2}): the decode budget has to leave "
                 "room for at least one prompt token")
    return args


def main(argv=None) -> None:
    args = parse_args(argv)
    enable_compile_cache()

    from repro.configs.lopace import CONFIG
    from repro.service import PromptService

    cfg = CONFIG.smoke()
    stats_stop = (start_stats_dumper(args.stats_interval,
                                     json_path=args.stats_json,
                                     prefix="[obs] ")
                  if args.stats_interval else None)
    params, _ = init_train_state(jax.random.PRNGKey(0), cfg)
    with tempfile.TemporaryDirectory() as tmp:
        store = build_store_from_corpus(tmp, n_prompts=max(8, args.requests), seed=4,
                                        n_shards=args.shards,
                                        async_ingest=args.ingest_async)
        st = store.stats()
        print(f"[serve] store: {st['n_prompts']} prompts across "
              f"{st['n_shards']} shards, {st['space_savings_pct']:.1f}% saved"
              + (" (async ingest)" if args.ingest_async else ""))
        service = PromptService(store, cache_bytes=int(args.cache_mb * 2 ** 20),
                                ingest_async=False)
        with service:
            if args.rebalance:
                res = service.rebalance(args.rebalance)
                print(f"[serve] rebalanced {res['n_shards_before']} -> "
                      f"{res['n_shards_after']} shards "
                      f"({res['n_records']} records, {res['wall_s']:.2f}s)")
            if args.compact or args.train_dict:
                for res in service.compact(train_dict=args.train_dict):
                    print(f"[serve] compacted shard {res.shard_id}: "
                          f"{res.bytes_before} -> {res.bytes_after} B"
                          + (f" (re-encoded {res.method}"
                             + (f", dict {res.dict_bytes} B" if res.used_dict
                                else "") + ")" if res.reencoded else ""))
            server = BatchServer(params, cfg, batch_slots=args.slots,
                                 max_len=args.max_len)
            keys = service.keys()[: args.requests]
            if args.smoke and service.cache is not None:
                # warm pass: the admission below then serves from the
                # token cache, the hot-prompt path of a production tier
                service.get_tokens_many(keys)
            # admission goes through the service: cache hits skip the
            # codec decode on repeat keys
            t0 = time.perf_counter()
            reqs = server.submit_text_many(service, keys,
                                           max_new_tokens=args.max_new)
            server.run()
            dt = time.perf_counter() - t0
            toks = sum(len(r.out_tokens) for r in reqs)
            print(f"[serve] {sum(r.done for r in reqs)}/{len(reqs)} requests, "
                  f"{toks} tokens in {dt:.1f}s ({toks/dt:.1f} tok/s)")
            if service.cache is not None:
                cs = service.cache.stats()
                print(f"[serve] token cache: {cs['hits']} hits / "
                      f"{cs['misses']} misses, {cs['bytes']} B cached")
    if stats_stop is not None:
        stats_stop.set()
    if args.stats_json:
        # atomic tmp+rename publish: a scraper tailing the file can never
        # observe a torn JSON document
        write_snapshot(args.stats_json, prefix="[serve] ")


if __name__ == "__main__":
    main()
