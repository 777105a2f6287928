#!/usr/bin/env python
"""Production training launcher: mesh setup, sharded train step, LoPace
data pipeline, checkpoint/restart, heartbeats, straggler policy.

On this CPU container it runs the real loop on the host mesh; on a TPU
fleet the same entry point shards over the production mesh (the dry-run
proves those shardings compile for every assigned arch).

    PYTHONPATH=src python -m repro.launch.train --arch lopace --steps 100

Trains the reduced smoke config by default; pass ``--full`` (or
``--no-smoke``) for the real one.  Relaunching with the same
``--ckpt-dir`` resumes from the latest checkpoint, including the exact
`TokenPipeline` position.
"""

from __future__ import annotations

import argparse
import tempfile
import time
from pathlib import Path
from typing import List

import jax
import jax.numpy as jnp

from repro.configs.registry import ALIASES, get_config
from repro.core.device import enable_compile_cache
from repro.data.pipeline import PipelineConfig, TokenPipeline, build_store_from_corpus
from repro.dist.checkpoint import (checkpoint_extra, checkpoint_step,
                                   latest_checkpoint, restore_checkpoint,
                                   save_checkpoint)
from repro.dist.fault import FleetMonitor, Heartbeat, RestartPolicy
from repro.train.optimizer import AdamWConfig
from repro.train.train_loop import init_train_state, make_train_step


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lopace")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the reduced smoke config (default on; "
                         "--no-smoke or --full selects the real config)")
    ap.add_argument("--full", action="store_true",
                    help="train the full config (alias for --no-smoke)")
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--ckpt-dir", default=None,
                    help="persistent checkpoint dir (required for resume "
                         "across launches; default: run-scoped temp dir)")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--keep-last", type=int, default=3)
    ap.add_argument("--store-dir", default=None,
                    help="PromptStore location; an already-populated store "
                         "is reopened, not rebuilt (default: temp dir)")
    ap.add_argument("--n-prompts", type=int, default=64,
                    help="corpus size when building a fresh store")
    ap.add_argument("--hb-dir", default=None,
                    help="shared heartbeat dir for fleet monitoring "
                         "(default: run-scoped temp dir)")
    ap.add_argument("--host-id", default="host0")
    args = ap.parse_args(argv)
    args.smoke = args.smoke and not args.full
    return args


_STORE_MARKER = "CORPUS_COMPLETE"
_STORE_BUILDING = "CORPUS_BUILDING"


def _reopen_store(store_dir: Path):
    from repro.core.api import PromptCompressor
    from repro.core.store import ShardedPromptStore
    from repro.tokenizer.vocab import default_tokenizer

    return ShardedPromptStore(
        store_dir, PromptCompressor(default_tokenizer(), method="hybrid"))


def _open_store(store_dir: Path, n_prompts: int):
    marker = store_dir / _STORE_MARKER
    building = store_dir / _STORE_BUILDING
    if marker.exists():  # fully built by a previous launch: reopen
        built = marker.read_text().strip()
        if built != f"n_prompts={n_prompts}":
            print(f"[launch] WARNING: reopening existing store at "
                  f"{store_dir} ({built}); --n-prompts {n_prompts} ignored "
                  f"(delete the dir to rebuild)")
        return _reopen_store(store_dir)
    if any(store_dir.glob("*.bin")):
        if building.exists():
            # OUR build died mid-ingest: training on a truncated corpus
            # would silently change the data — start over
            print(f"[launch] incomplete store at {store_dir}; rebuilding")
            import shutil

            shutil.rmtree(store_dir)
        else:
            # populated by something else (no marker of ours either way):
            # never delete data we didn't write — reopen as-is.  NOTE this
            # also catches partial builds from pre-sentinel launchers; the
            # operator decides, instead of us silently rmtree-ing.
            print(f"[launch] WARNING: reopening store at {store_dir} not "
                  f"built by this launcher; --n-prompts {n_prompts} ignored "
                  "(if this is a suspected partial build, delete the dir "
                  "to rebuild)")
            return _reopen_store(store_dir)
    store_dir.mkdir(parents=True, exist_ok=True)
    building.write_text("")  # sentinel: a *.bin without this is not ours
    store = build_store_from_corpus(store_dir, n_prompts=n_prompts, seed=0)
    marker.write_text(f"n_prompts={n_prompts}\n")
    building.unlink()
    return store


def run(args: argparse.Namespace, scratch: Path) -> List[float]:
    """Train; returns the loss of every step this launch ran."""
    if args.arch == "lopace":
        from repro.configs.lopace import CONFIG as cfg_full
    else:
        cfg_full = get_config(args.arch)
    cfg = cfg_full.smoke() if args.smoke else cfg_full
    print(f"[launch] {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"on {len(jax.devices())} device(s)")

    ckpt_dir = Path(args.ckpt_dir) if args.ckpt_dir else scratch / "ckpt"
    hb_dir = Path(args.hb_dir) if args.hb_dir else scratch / "hb"
    store_dir = Path(args.store_dir) if args.store_dir else scratch / "store"
    hb = Heartbeat(hb_dir, args.host_id)
    monitor = FleetMonitor(hb_dir)
    policy = RestartPolicy()

    store = _open_store(store_dir, args.n_prompts)
    pipe = TokenPipeline(store, PipelineConfig(
        seq_len=args.seq_len, global_batch=args.batch, seed=0))
    opt_cfg = AdamWConfig(lr=3e-4, warmup_steps=20, total_steps=args.steps)
    step_fn = jax.jit(make_train_step(
        cfg, opt_cfg, remat=args.remat, grad_accum=args.grad_accum,
        compress_grads=args.compress_grads), donate_argnums=(0, 1))
    params, opt_state = init_train_state(
        jax.random.PRNGKey(0), cfg, compress_grads=args.compress_grads)

    start = 0
    ck = latest_checkpoint(ckpt_dir)
    if ck:
        state = restore_checkpoint(ck, {"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        pipe.restore(checkpoint_extra(ck)["data"])
        start = checkpoint_step(ck)
        print(f"[launch] resumed from step {start}")

    losses = []
    for step in range(start, args.steps):
        t0 = time.perf_counter()
        batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
        if args.grad_accum > 1:
            batch = pipe.with_accum(batch, args.grad_accum)
        params, opt_state, m = step_fn(params, opt_state, batch)
        losses.append(m["loss"])
        dt = time.perf_counter() - t0
        hb.beat(step, step_time_s=dt)
        if step % 10 == 0:
            # fleet state changes on the dead_after timescale — don't
            # re-read every heartbeat file on every step
            status = monitor.scan()
            decision = policy.decide(status)
            if decision == "abort":
                raise SystemExit("[launch] too many failures; aborting")
            if decision == "restart_elastic":
                # single-host launcher: a real fleet supervisor would
                # re-carve the DP sharding here; we log and keep training
                print(f"[launch] fleet degraded (dead={status.dead}); "
                      f"continuing")
            if status.stragglers:
                print(f"[launch] stragglers: {status.stragglers} "
                      f"(median {status.median_step_time:.2f}s)")
        if (step + 1) % 10 == 0:
            print(f"step {step+1:5d} loss={float(m['loss']):.3f} "
                  f"ce={float(m['ce']):.3f} "
                  f"gnorm={float(m['grad_norm']):.2f} {dt*1e3:.0f}ms")
        if (step + 1) % args.ckpt_every == 0 or step + 1 == args.steps:
            save_checkpoint(ckpt_dir, step + 1,
                            {"params": params, "opt": opt_state},
                            extra={"data": pipe.state()},
                            keep_last=args.keep_last)
    print("[launch] done")
    return [float(x) for x in losses]


def main(argv=None) -> List[float]:
    args = parse_args(argv)
    enable_compile_cache()
    # everything not explicitly pointed at a persistent path lives in one
    # run-scoped scratch dir and is removed on exit (the old mkdtemp
    # fallbacks leaked a store + heartbeat dir per launch)
    with tempfile.TemporaryDirectory(prefix="repro_train_") as scratch:
        return run(args, Path(scratch))


if __name__ == "__main__":
    main()
