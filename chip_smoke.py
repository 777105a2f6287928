#!/usr/bin/env python3
"""Chip smoke: the prompt store and the paper's model, once, on one TPU.

    python3 chip_smoke.py        # from the repository root

One process drives the system's main paths through their normal entry
points, and starts no child process that needs the chip:

0. device   a TPU must be JAX's first device; anything else exits 2
            before any work.  The BPE vocabulary is retrained from the
            committed trainer and seed into a fresh asset directory.
1. store    an in-process writer gateway over a fresh 4-shard store
            ingests the paper's corpus (386 prompts, seed 4) with the
            default ``hybrid`` method through ``put_async`` + ``wait``
            (group commits through the ingest queue, default routing,
            so the token-pack kernel runs), then every key is read back
            with ``get`` and ``get_tokens`` and checked against the text
            and ``tokenizer.encode``.
2. kernels  a fixed sample (smallest, median and largest prompt plus 20
            chosen by seed) goes through a second gateway whose store
            uses the ``repro-lzr`` backend with the LZ77 and rANS device
            paths forced and the byte histogram on the device.  Every
            stored frame must equal the host oracle's frame byte for
            byte and decode under both paths.  Each kernel family is
            shown compiled (``tpu_custom_call`` in its lowered text) with
            its result on the TPU; the dispatch census and compilation
            counts are printed.
3. model    ``repro.launch.train --full`` runs lopace-100m for 4 steps
            from a store through ``TokenPipeline``; the losses must be
            finite and one checkpoint written.

Each phase prints its wall time with JAX's compile time (the wall time
during which some thread compiled) shown apart from run time.  Any
failure raises and exits non-zero.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Stores, checkpoints and the vocabulary go to ``.chip_smoke/`` (git
ignored, removed after a passing run); a summary goes to
``chiprun_out/chip_smoke.json``.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
WORK = ROOT / ".chip_smoke"
SUMMARY = ROOT / "chiprun_out" / "chip_smoke.json"

CORPUS_PROMPTS = 386     # the paper's corpus (§4.1)
CORPUS_SEED = 4          # the corpus the gateway launcher seeds with
SAMPLE_EXTRA = 20        # phase 2: prompts chosen by seed beyond the 3 fixed
PUT_CHUNK = 32           # texts per put_async request
TRAIN_STEPS = 4

# phase 2: the forced device path and the all-host oracle
DEVICE_ENV = {"REPRO_LZ_MODE": "device", "REPRO_RANS_MODE": "device",
              "REPRO_HIST_DEVICE_MIN": "1"}
ORACLE_ENV = {"REPRO_LZ_MODE": "vector", "REPRO_RANS_MODE": "numpy",
              "REPRO_HIST_DEVICE_MIN": str(1 << 62),
              "REPRO_PACK_DEVICE_MIN": str(1 << 62)}


class CompileClock:
    """Records JAX's compile events (tracing, lowering, backend compile)
    from any thread.  A phase's compile time is the wall time covered by
    at least one compile, so parallel compiles are not counted twice and
    run time is the rest of the phase's wall time."""

    def __init__(self) -> None:
        import jax.monitoring

        self._lock = threading.Lock()
        self._spans: list = []           # (start, end, is_backend_compile)
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            end = time.perf_counter()
            with self._lock:
                self._spans.append(
                    (end - secs, end, name.endswith("backend_compile_duration")))

    def _covered(self, t0: float, t1: float) -> tuple:
        with self._lock:
            spans = sorted((max(a, t0), min(b, t1), c)
                           for a, b, c in self._spans if b > t0 and a < t1)
        covered, reach = 0.0, t0
        for a, b, _ in spans:
            if b > reach:
                covered += b - max(a, reach)
                reach = b
        return covered, sum(c for _, _, c in spans)

    @contextlib.contextmanager
    def phase(self, label: str, timings: dict):
        t0 = time.perf_counter()
        yield
        t1 = time.perf_counter()
        comp, n = self._covered(t0, t1)
        wall = t1 - t0
        timings[label] = {"wall_s": wall, "compile_s": comp,
                          "run_s": wall - comp, "compiles": n}
        print(f"[chip_smoke] phase {label}: wall {wall} s, compile {comp} s "
              f"({n} compiles), run {wall - comp} s", flush=True)


@contextlib.contextmanager
def env_set(values: dict):
    saved = {k: os.environ.get(k) for k in values}
    os.environ.update(values)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def phase0_device() -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        print(f"[chip_smoke] no TPU found: JAX's first device is "
              f"{d.platform!r} ({d.device_kind}); this smoke runs only on "
              f"a TPU", file=sys.stderr, flush=True)
        sys.exit(2)
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    print(f"[chip_smoke] device: {d.device_kind} x {len(devices)} "
          f"(jax {jax.__version__})", flush=True)
    return info


def dispatch_census() -> dict:
    from repro import obs

    return {k: v for k, v in obs.snapshot()["counters"].items()
            if k.startswith("device.dispatch")}


@contextlib.contextmanager
def served(store):
    """A started PromptService with an in-process gateway and a client."""
    from repro.service import PromptService
    from repro.service.gateway import GatewayClient, start_in_thread

    with PromptService(store) as service, start_in_thread(service) as gw, \
            GatewayClient("127.0.0.1", gw.port, timeout=900) as client:
        yield client


def put_all(client, texts, method: str):
    tickets = [client.put_async(texts[i:i + PUT_CHUNK], method=method)
               for i in range(0, len(texts), PUT_CHUNK)]
    keys = []
    for t in tickets:
        keys += client.wait(t["ticket"], timeout=900)
    return keys


def read_back(client, tok, keys, texts) -> None:
    for i in range(0, len(keys), PUT_CHUNK):
        chunk = keys[i:i + PUT_CHUNK]
        want = texts[i:i + PUT_CHUNK]
        if client.get_many(chunk) != want:
            raise AssertionError(f"get: records {i}.. differ from the text")
        got = client.call("get_tokens", keys=chunk)["tokens"]
        for j, (ids, text) in enumerate(zip(got, want)):
            if ids != tok.encode(text):
                raise AssertionError(
                    f"get_tokens: record {i + j} differs from encode")


def phase1_store(tok, texts) -> dict:
    from repro.core.api import PromptCompressor
    from repro.core.store import ShardedPromptStore

    store = ShardedPromptStore(WORK / "store",
                               PromptCompressor(tok, method="hybrid"),
                               n_shards=4)
    with served(store) as client:
        keys = put_all(client, texts, "hybrid")
        read_back(client, tok, keys, texts)
    store.close()
    census = dispatch_census()
    packed_on_device = census.get(
        "device.dispatch{knob=repro_pack_device_min,path=device}", 0)
    if not packed_on_device:
        raise AssertionError(
            f"no group commit took the token-pack kernel: {census}")
    print(f"[chip_smoke] store: {len(keys)} prompts, "
          f"{sum(map(len, texts))} chars, read back byte-identical with "
          f"matching token ids; token-pack kernel taken by "
          f"{packed_on_device} group commits at default routing", flush=True)
    return {"prompts": len(keys), "pack_device_commits": packed_on_device}


def sample_indices(texts) -> list:
    import numpy as np

    order = np.argsort([len(t) for t in texts], kind="stable")
    fixed = [int(order[0]), int(order[len(order) // 2]), int(order[-1])]
    rest = np.setdiff1d(np.arange(len(texts)), fixed)
    extra = np.random.default_rng(CORPUS_SEED).choice(
        rest, SAMPLE_EXTRA, replace=False)
    return fixed + sorted(int(i) for i in extra)


def stored_frames(store) -> dict:
    frames = {}
    for sid in range(store.n_shards):
        recs = store.shard_records(sid)
        for rec, blob in zip(recs, store.read_records(sid, recs)):
            frames[rec["key"]] = blob
    return frames


def kernel_proofs(tok, text) -> dict:
    """Lower and run each kernel family's jitted stage on the largest
    sample: the lowered text must hold the Mosaic kernel and the
    results must sit on the TPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import lz77, packing, rans_np
    from repro.kernels.histogram import ops as hist_ops
    from repro.kernels.lz_match import ops as lz_ops
    from repro.kernels.rans_lanes import ops as rans_ops
    from repro.kernels.token_pack import ops as pack_ops

    ids = np.asarray(tok.encode(text), np.int32)
    packed = packing.pack_tokens(ids, "fixed")
    with env_set(DEVICE_ENV):
        lz = lz77.lz_compress(packed)
    sym = np.frombuffer(lz, np.uint8)
    pb = rans_np.PROB_BITS_DEFAULT
    freqs = rans_np.normalize_freqs(np.bincount(sym, minlength=256), pb)
    lanes = rans_np._auto_lanes(sym.size)
    words, states = rans_np.rans_encode_interleaved(sym, freqs, lanes, pb)
    p = lz_ops.size_bucket(len(packed), lz_ops._PAD_MIN)
    buf = np.zeros(p, np.uint8)
    buf[:len(packed)] = np.frombuffer(packed, np.uint8)
    f32 = jnp.asarray(freqs, jnp.uint32)
    stages = {
        "token_pack": (pack_ops._pack_padded, (jnp.asarray(ids), 2, False)),
        "histogram": (hist_ops._token_histogram,
                      (jnp.asarray(sym, jnp.int32), 256, False)),
        "lz_match": (lz_ops._candidate_stage,
                     (jnp.asarray(buf), jnp.int32(len(packed)),
                      jnp.int32(0), p, False)),
        "rans_encode": (rans_ops._encode_stage,
                        (jnp.asarray(sym), f32, lanes, pb, False)),
        "rans_decode": (rans_ops._decode_stage,
                        (jnp.asarray(words, jnp.uint16),
                         jnp.asarray(states, jnp.uint32), f32, int(sym.size),
                         lanes, pb, False)),
    }
    out = {}
    for name, (fn, args) in stages.items():
        if "tpu_custom_call" not in fn.lower(*args).as_text():
            raise AssertionError(f"{name}: no Mosaic kernel in the lowering")
        result = jax.block_until_ready(fn(*args))
        platforms = sorted({d.platform for leaf in jax.tree.leaves(result)
                            for d in leaf.devices()})
        if platforms != ["tpu"]:
            raise AssertionError(f"{name}: result on {platforms}")
        if name == "rans_decode" and not np.array_equal(
                np.asarray(result[0]), sym):
            raise AssertionError("rans_decode: symbols differ")
        out[name] = {"compiled_shapes": fn._cache_size()}
        print(f"[chip_smoke] kernel {name}: tpu_custom_call lowered, result "
              f"on {platforms}, {fn._cache_size()} compiled shapes", flush=True)
    return out


def phase2_kernels(tok, texts) -> dict:
    from repro.core.api import PromptCompressor
    from repro.core.store import ShardedPromptStore

    idx = sample_indices(texts)
    sample = [texts[i] for i in idx]
    oracle = PromptCompressor(tok, method="hybrid", backend="repro-lzr")
    with env_set(ORACLE_ENV):
        want = oracle.compress_batch(sample)
    before = dispatch_census()
    store = ShardedPromptStore(
        WORK / "store-lzr",
        PromptCompressor(tok, method="hybrid", backend="repro-lzr"),
        n_shards=4)
    with env_set(DEVICE_ENV), served(store) as client:
        keys = put_all(client, sample, "hybrid")
        read_back(client, tok, keys, sample)
    delta = {k: v - before.get(k, 0) for k, v in dispatch_census().items()
             if v != before.get(k, 0)}
    frames = stored_frames(store)
    store.close()
    for i, (key, frame) in enumerate(zip(keys, want)):
        if frames[key] != frame:
            raise AssertionError(
                f"sample {idx[i]}: device frame differs from the oracle's")
    with env_set(ORACLE_ENV):
        if oracle.decompress_batch([frames[k] for k in keys]) != sample:
            raise AssertionError("oracle decode of device frames differs")
    print(f"[chip_smoke] kernels: {len(sample)} sampled prompts "
          f"({min(map(len, sample))}..{max(map(len, sample))} chars), every "
          f"frame byte-identical to the host oracle and decoded under both",
          flush=True)
    print(f"[chip_smoke] device.dispatch census (phase 2): {delta}",
          flush=True)
    proofs = kernel_proofs(tok, max(sample, key=len))
    return {"sample": idx, "dispatch": delta, "kernels": proofs}


def phase3_model() -> dict:
    from repro.dist.checkpoint import checkpoint_step, latest_checkpoint
    from repro.launch import train

    ckpt = WORK / "ckpt"
    losses = train.main([
        "--full", "--steps", str(TRAIN_STEPS), "--n-prompts", "64",
        "--store-dir", str(WORK / "train-store"),
        "--ckpt-dir", str(ckpt), "--hb-dir", str(WORK / "hb")])
    if len(losses) != TRAIN_STEPS or not all(map(math.isfinite, losses)):
        raise AssertionError(f"losses not finite: {losses}")
    last = latest_checkpoint(ckpt)
    if last is None or checkpoint_step(last) != TRAIN_STEPS:
        raise AssertionError(f"no checkpoint at step {TRAIN_STEPS}: {last}")
    print(f"[chip_smoke] model: lopace-100m, {TRAIN_STEPS} steps, losses "
          f"{losses}, checkpoint {last.name}", flush=True)
    return {"losses": losses, "checkpoint": last.name}


def main() -> int:
    info = phase0_device()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"[chip_smoke] no repro package under {ROOT / 'src'}",
              file=sys.stderr, flush=True)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    for k in [k for k in os.environ if k.startswith("REPRO_")]:
        del os.environ[k]            # phase 1 runs at default routing
    shutil.rmtree(WORK, ignore_errors=True)
    os.environ["REPRO_ASSET_DIR"] = str(WORK / "assets")

    from repro.core.device import enable_compile_cache

    cache = enable_compile_cache()
    clock = CompileClock()
    timings: dict = {}
    report = {"device": info, "compile_cache": cache, "timings": timings}
    print(f"[chip_smoke] compile cache: {cache}", flush=True)

    with clock.phase("0 vocabulary+corpus", timings):
        from repro.data.corpus import generate_corpus
        from repro.tokenizer.vocab import default_tokenizer

        tok = default_tokenizer()
        texts = [p.text for p in generate_corpus(n_prompts=CORPUS_PROMPTS,
                                                 seed=CORPUS_SEED)]
    with clock.phase("1 store", timings):
        report["store"] = phase1_store(tok, texts)
    with clock.phase("2 kernels", timings):
        report["kernels"] = phase2_kernels(tok, texts)
    with clock.phase("3 model", timings):
        report["model"] = phase3_model()

    SUMMARY.parent.mkdir(parents=True, exist_ok=True)
    SUMMARY.write_text(json.dumps(report, indent=1, default=str))
    shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
