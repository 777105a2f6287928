#!/usr/bin/env python
"""Gateway launcher: one process of the multi-process service tier.

    # the writer: owns the store lease, ingest, compaction
    PYTHONPATH=src python -m repro.launch.gateway --store-dir /data/store \
        --role writer --port 7421 --build-corpus 64

    # a standby: blocks on the lease, takes over when the writer dies
    PYTHONPATH=src python -m repro.launch.gateway --store-dir /data/store \
        --role standby --port 7422

    # read replicas: no lease, follow the writer through store.json
    PYTHONPATH=src python -m repro.launch.gateway --store-dir /data/store \
        --role replica --port 7431

Roles map straight onto `core/store.py`'s ownership model: ``writer``
opens read-write with ``lease="try"`` (fails fast if the root is owned),
``standby`` opens with ``lease="wait"`` (the takeover path — the flock
releases the instant the writer dies, even on SIGKILL), and ``replica``
opens ``readonly=True`` plus a poll thread calling ``store.refresh()``
every ``--refresh-s`` seconds so compaction swaps and new ingest become
visible without any writer→replica channel.

``--port-file`` publishes ``{"host", "port", "pid", "role"}`` (atomic
tmp+rename) once the socket is bound — how orchestration and tests
discover an ephemeral ``--port 0``.  SIGTERM drains gracefully.

One process per chip.  A chip belongs to one process at a time, and the
codec reaches JAX on its own: a writer's group commit of
``REPRO_PACK_DEVICE_MIN`` ids or more packs tokens with the Pallas
kernel, and a multi-lane rANS blob may decode there.  So the platform is
part of the role (``--platform``):

* ``writer`` keeps JAX's own choice (``auto``: the accelerator when one
  is attached) — it is the one process of the fleet that holds the chip;
* ``replica`` defaults to ``cpu``: it serves reads on the host paths
  and never opens the chip, however many replicas share the host;
* ``standby`` keeps ``auto`` but touches no JAX backend while it waits:
  the lease wait comes first, and nothing before it compiles or asks
  for a device.  It opens the chip only after taking the lease, i.e.
  after the writer died and the chip was released with its process.

The platform is applied through ``jax.config`` before anything asks JAX
for a backend; the writer and a standby that took over also place the
persistent compile cache (``repro.core.device.enable_compile_cache``).
"""

from __future__ import annotations

import argparse
import json
import os
import threading

from repro.core import env
from repro.core.api import PromptCompressor
from repro.core.durability import publish_durable
from repro.core.store import ShardedPromptStore
from repro.launch.statsdump import start_stats_dumper, write_snapshot
from repro.service import PromptService
from repro.service.gateway import GatewayServer
from repro.tokenizer.vocab import default_tokenizer


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--store-dir", required=True,
                    help="store root shared by writer/standby/replicas")
    ap.add_argument("--role", choices=("writer", "standby", "replica"),
                    default="writer")
    ap.add_argument("--platform", choices=("auto", "cpu"),
                    default=None,
                    help="JAX platform of this process: auto = JAX's own "
                         "choice (default for writer and standby), cpu "
                         "(default for replica: it never opens the chip)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 binds an ephemeral port (see --port-file)")
    ap.add_argument("--port-file", default=None, metavar="PATH",
                    help="publish {host, port, pid, role} JSON at PATH "
                         "once serving (atomic tmp+rename)")
    ap.add_argument("--shards", type=int, default=4,
                    help="shard count when the writer creates a new store")
    ap.add_argument("--method", default="hybrid",
                    help="codec method for --build-corpus ingest")
    ap.add_argument("--build-corpus", type=int, default=0, metavar="N",
                    help="writer only: seed an empty store with N "
                         "synthetic prompts before serving")
    ap.add_argument("--cache-mb", type=float, default=32.0,
                    help="serve-path token cache budget in MB (0 = none)")
    ap.add_argument("--flush-batch", type=int, default=64)
    ap.add_argument("--max-pending", type=int, default=1024,
                    help="ingest queue backpressure bound (texts)")
    ap.add_argument("--compact-interval", type=float, default=0.0,
                    help="background compaction scan interval in seconds "
                         "(0 = no background compactor)")
    ap.add_argument("--scrub-interval", type=float, default=0.0,
                    help="background integrity-scrub interval in seconds "
                         "(0 = no scrubber); failing shards are "
                         "quarantined, reads degrade per key")
    ap.add_argument("--max-inflight", type=int, default=None,
                    help="admission cap (default REPRO_GATEWAY_MAX_INFLIGHT)")
    ap.add_argument("--conn-window", type=int, default=None,
                    help="per-connection window (default "
                         "REPRO_GATEWAY_CONN_WINDOW)")
    ap.add_argument("--drain-s", type=float, default=None,
                    help="SIGTERM drain budget (default "
                         "REPRO_GATEWAY_DRAIN_S)")
    ap.add_argument("--refresh-s", type=float, default=None,
                    help="replica store.json poll interval (default "
                         "REPRO_GATEWAY_REFRESH_S)")
    ap.add_argument("--lease-timeout", type=float, default=None,
                    help="standby: give up waiting for the lease after "
                         "this many seconds (default: wait forever)")
    ap.add_argument("--stats-interval", type=float, default=0.0, metavar="N",
                    help="every N seconds print obs metric rates (and "
                         "republish --stats-json)")
    ap.add_argument("--stats-json", metavar="PATH", default=None,
                    help="write the final obs snapshot to PATH (atomic)")
    args = ap.parse_args(argv)
    if args.shards < 1:
        ap.error(f"--shards ({args.shards}) must be >= 1")
    if args.build_corpus and args.role != "writer":
        ap.error("--build-corpus is writer-only: replicas and standbys "
                 "never mutate the store")
    if args.platform is None:
        args.platform = "cpu" if args.role == "replica" else "auto"
    for name in ("stats_interval", "cache_mb", "compact_interval",
                 "scrub_interval"):
        if getattr(args, name) < 0:
            ap.error(f"--{name.replace('_', '-')} must be >= 0")
    return args


def _open_store(args: argparse.Namespace) -> ShardedPromptStore:
    compressor = PromptCompressor(default_tokenizer(), method=args.method)
    if args.role == "replica":
        return ShardedPromptStore(args.store_dir, compressor, readonly=True)
    if args.role == "standby":
        print(f"[gateway] standby: waiting for the store lease on "
              f"{args.store_dir} ...", flush=True)
        return ShardedPromptStore(
            args.store_dir, compressor, n_shards=args.shards, lease="wait")
    return ShardedPromptStore(
        args.store_dir, compressor, n_shards=args.shards, lease="try")


def _seed_corpus(store: ShardedPromptStore, n: int, method: str) -> None:
    if len(store) >= n:
        return
    from repro.data.corpus import generate_corpus

    prompts = generate_corpus(n_prompts=n, seed=4)
    store.put_many([p.text for p in prompts], method)
    st = store.stats()
    print(f"[gateway] seeded store: {st['n_prompts']} prompts across "
          f"{st['n_shards']} shards, {st['space_savings_pct']:.1f}% saved",
          flush=True)


def _start_replica_refresher(store: ShardedPromptStore,
                             interval_s: float) -> threading.Event:
    stop = threading.Event()

    def loop() -> None:
        while not stop.wait(interval_s):
            try:
                store.refresh()
            except Exception as e:  # keep polling through writer churn
                print(f"[gateway] replica refresh failed (will retry): {e}",
                      flush=True)

    threading.Thread(target=loop, name="replica-refresh",
                     daemon=True).start()
    return stop


def pin_platform(platform: str) -> None:
    """Apply the role's JAX platform before any backend exists
    (``auto`` leaves JAX's own choice alone)."""
    if platform != "auto":
        import jax

        jax.config.update("jax_platforms", platform)


def main(argv=None) -> None:
    args = parse_args(argv)
    pin_platform(args.platform)
    # leasing happens here: writer fails fast if owned, standby blocks
    # until takeover, replica never takes it
    if args.role == "standby" and args.lease_timeout is not None:
        from repro.core.lease import acquire_store_lease

        # bounded wait, then hold the refcounted lease through the
        # store's own acquisition below
        lease = acquire_store_lease(args.store_dir, mode="wait",
                                    timeout_s=args.lease_timeout)
    else:
        lease = None
    try:
        store = _open_store(args)
    except BaseException:
        if lease is not None:
            lease.release()
        raise
    readonly = args.role == "replica"
    if args.role == "standby":
        print("[gateway] standby acquired the lease: taking over as writer",
              flush=True)
    if not readonly:
        from repro.core.device import enable_compile_cache

        enable_compile_cache()
    if args.build_corpus:
        _seed_corpus(store, args.build_corpus, args.method)
    service = PromptService(
        store,
        cache_bytes=int(args.cache_mb * 2 ** 20),
        ingest_async=not readonly,
        flush_batch=args.flush_batch,
        max_pending=args.max_pending,
        compact_interval_s=(args.compact_interval or None
                            if not readonly else None),
        scrub_interval_s=(args.scrub_interval or None
                          if not readonly else None),
    )
    if env.read("REPRO_FAULTS"):
        # deterministic chaos: say so in the log, loudly, so a fault spec
        # leaking into a real deployment is visible at startup
        print(f"[gateway] FAULT INJECTION ARMED: "
              f"REPRO_FAULTS={env.read('REPRO_FAULTS')!r} "
              f"seed={env.read('REPRO_FAULTS_SEED')}", flush=True)
    refresh_s = (env.read("REPRO_GATEWAY_REFRESH_S")
                 if args.refresh_s is None else args.refresh_s)
    refresher = (_start_replica_refresher(store, refresh_s)
                 if readonly else None)
    stats_stop = (start_stats_dumper(args.stats_interval,
                                     json_path=args.stats_json,
                                     prefix="[gateway][obs] ")
                  if args.stats_interval else None)
    server = GatewayServer(service, host=args.host, port=args.port,
                           max_inflight=args.max_inflight,
                           conn_window=args.conn_window,
                           drain_s=args.drain_s, readonly=readonly)

    def ready(srv: GatewayServer) -> None:
        print(f"[gateway] {args.role} serving on {args.host}:{srv.port} "
              f"(store: {len(store)} prompts, {store.n_shards} shards)",
              flush=True)
        if args.port_file:
            publish_durable(args.port_file, (json.dumps({
                "host": args.host, "port": srv.port, "pid": os.getpid(),
                "role": args.role}) + "\n").encode())

    with service:
        try:
            server.run(ready_cb=ready)
        finally:
            if refresher is not None:
                refresher.set()
            if stats_stop is not None:
                stats_stop.set()
    if args.stats_json:
        write_snapshot(args.stats_json, prefix="[gateway] ")
    store.close()
    if lease is not None:
        lease.release()
    print("[gateway] drained, exiting", flush=True)


if __name__ == "__main__":
    main()
