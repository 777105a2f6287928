"""Finds the batch size from which the rANS stage's device route beats the
host coder, by a sweep on the chip.

    python3 bench/rans_crossover.py [--records 192] [--seed 1] [--reps 5]
                                    [--out chiprun_out/rans_crossover.json]
                                    [--no-chip]

Builds the byte stage's real inputs as the store does (corpus-law
prompts, the program's BPE, fixed-width packing, LZ77 on the host) and
times ``rans_np.rans_compress_batch`` on batches of 1 to 64 of those
records: on the NumPy coder (``REPRO_RANS_MODE=numpy``) and on the
device route (``=device``), each the median of ``--reps`` calls, the
device's after a warm call, so no compile is timed.  Every device blob
must equal the host's, or the sweep fails.  Prints one line per batch (records, LZ77
bytes in all, host and device seconds, and the device route's seconds
inside ``kernel.rans_lanes``: launches and read-backs) and, last, the
smallest batch total from which the device route was faster in every
larger batch swept: the reading behind ``rans_np._DEVICE_MIN_BYTES``.
``--no-chip`` rehearses the control flow on the CPU (kernels in
interpret mode) at 12 records and prints no times.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

BATCH_RECORDS = (1, 2, 4, 8, 16, 32, 64)
BATCHES_PER_SIZE = 3


def lz_records(n: int, seed: int):
    """LZ77 outputs of ``n`` corpus-law prompts, packed as the store
    packs them."""
    import numpy as np

    from repro.core import packing
    from repro.core.lz77 import lz_compress
    from repro.data.corpus import generate_corpus
    from repro.tokenizer.vocab import default_tokenizer

    tok = default_tokenizer()
    texts = [p.text for p in generate_corpus(n_prompts=n, seed=seed)]
    return [lz_compress(packing.pack_tokens(
        np.asarray(ids, np.uint32), "fixed"))
        for ids in tok.encode_batch(texts)]


def _timed(fn, reps: int):
    secs = []
    for _ in range(reps):
        t = time.perf_counter()
        out = fn()
        secs.append(time.perf_counter() - t)
    return statistics.median(secs), out


def _launch_s() -> float:
    from repro import obs

    h = obs.snapshot()["histograms"].get("kernel.rans_lanes.s")
    return h["sum"] if h else 0.0


def sweep(records, reps: int, seed: int) -> list:
    import random

    from repro.core.rans_np import rans_compress_batch

    rng = random.Random(seed)
    rows = []
    for k in BATCH_RECORDS:
        for _ in range(BATCHES_PER_SIZE if k < len(records) else 1):
            batch = rng.sample(records, min(k, len(records)))
            os.environ["REPRO_RANS_MODE"] = "numpy"
            host_s, want = _timed(lambda: rans_compress_batch(batch), reps)
            os.environ["REPRO_RANS_MODE"] = "device"
            rans_compress_batch(batch)      # warm: compiles a new shape
            before = _launch_s()
            dev_s, got = _timed(lambda: rans_compress_batch(batch), reps)
            if got != want:
                raise AssertionError(
                    f"{k} records: a device blob differs from the host's")
            rows.append({"records": len(batch),
                         "lz_bytes": sum(map(len, batch)),
                         "host_s": host_s, "device_s": dev_s,
                         "launch_s": (_launch_s() - before) / reps})
    return sorted(rows, key=lambda r: r["lz_bytes"])


def crossover(rows: list):
    """Smallest total from which the device route won every larger
    batch swept (None if it never did)."""
    best = None
    for r in reversed(rows):
        if r["device_s"] >= r["host_s"]:
            break
        best = r["lz_bytes"]
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--records", type=int, default=192)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out")
    ap.add_argument("--no-chip", action="store_true")
    args = ap.parse_args(argv)
    import jax

    backend = jax.default_backend()
    if backend == "cpu" and not args.no_chip:
        print("[rans_crossover] no TPU found", file=sys.stderr)
        return 3
    records = lz_records(12 if args.no_chip else args.records, args.seed)
    rows = sweep(records, 1 if args.no_chip else args.reps, args.seed)
    if args.no_chip:
        print(f"[rans_crossover] rehearsal on the CPU: {len(rows)} batches, "
              f"every device blob equal to the host's; no times printed")
        return 0
    for r in rows:
        print(json.dumps(r))
    result = {"device": jax.devices()[0].device_kind, "rows": rows,
              "crossover_lz_bytes": crossover(rows)}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps({"crossover_lz_bytes": result["crossover_lz_bytes"],
                      "device": result["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
