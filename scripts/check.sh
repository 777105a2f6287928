#!/usr/bin/env bash
# Quick verification loop: the not-slow test tier plus an explicit run of
# the golden-frame tests that pin on-disk byte layouts (v1 token payload,
# v2 dict header).  Full tier-1 remains `PYTHONPATH=src python -m pytest
# -x -q` (see ROADMAP.md); `pytest -m crash` selects the crash-injection
# suite alone.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Invariant gate first: the static analyzer (lock order, durability,
# frozen wire formats, kernel hygiene, env registry, pool re-entrancy)
# fails in seconds, before any test tier spends minutes.
python -m repro.analysis src --baseline analysis-baseline.json

python -m pytest -q -m "not slow"
python -m pytest -q tests/test_codec.py tests/test_dict_codec.py -k golden

# Perf smoke: the vectorized repro-lzr compress path must beat the scalar
# baseline by a conservative floor on a ~1 MB sample — this is the guard
# against silently falling back to the scalar path (e.g. a routing or
# env-knob regression).  The floor (1.8x) sits far below the measured
# speedup (~4-6x on this corpus) so machine-load noise cannot trip it.
python - <<'PYEOF'
import os, time
from repro.data.corpus import generate_corpus
from repro.core.zstd_backend import compress_bytes

blob = "\n".join(p.text for p in generate_corpus(32, seed=0)).encode()[:1 << 20]

def best(reps=3):
    b = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        compress_bytes(blob, backend="repro-lzr")
        b = min(b, time.perf_counter() - t0)
    return b

os.environ.update(REPRO_LZ_MODE="scalar", REPRO_RANS_LANES="1")
t_scalar = best()
os.environ.pop("REPRO_LZ_MODE"); os.environ.pop("REPRO_RANS_LANES")
t_vec = best()
speedup = t_scalar / t_vec
print(f"perf smoke: repro-lzr compress scalar {t_scalar*1e3:.0f}ms "
      f"vec {t_vec*1e3:.0f}ms speedup {speedup:.1f}x (floor 1.8x)")
assert speedup >= 1.8, (
    f"vectorized repro-lzr compress only {speedup:.2f}x over scalar — "
    "did the hot path silently fall back to the scalar loop?")
PYEOF

# Obs smoke: with REPRO_OBS=0 the instrumented codec hot path must sit
# within 3% of the raw compress baseline — the guard against metric
# bookkeeping leaking outside the enabled() gate (see scripts/obs_smoke.py).
python scripts/obs_smoke.py

# Device-kernel smoke: both codec kernels (LZ77 match finder, lane-parallel
# rANS) run in interpret mode and must be byte-identical to the scalar-
# rooted oracles — the guard against a kernel or dispatch change silently
# breaking wire-format parity on hosts with no accelerator attached.
python - <<'PYEOF'
import numpy as np
from repro.core.lz77 import _lz_compress_device, _lz_compress_np
from repro.core.rans_np import normalize_freqs, rans_encode_interleaved
from repro.kernels.rans_lanes import (rans_decode_interleaved_device,
                                      rans_encode_interleaved_device)
from repro.data.corpus import generate_corpus

blob = "\n".join(p.text for p in generate_corpus(8, seed=1)).encode()[:1 << 16]
assert _lz_compress_device(blob) == _lz_compress_np(blob), \
    "device LZ77 match finder diverged from the NumPy parse"
sym = np.frombuffer(blob, np.uint8)
freqs = normalize_freqs(np.bincount(sym, minlength=256))
w_r, x_r = rans_encode_interleaved(sym, freqs, 256)
w_d, x_d = rans_encode_interleaved_device(sym, freqs, 256, 12, interpret=True)
assert np.array_equal(w_r, w_d) and np.array_equal(x_r, x_d), \
    "device rANS encoder diverged from the NumPy interleaved coder"
assert bytes(rans_decode_interleaved_device(
    w_d, x_d, sym.size, freqs, 256, 12, interpret=True)) == blob, \
    "device rANS decoder failed to round-trip"
print("kernel smoke: LZ77 + rANS device paths byte-identical (interpret mode)")
PYEOF

# Gateway smoke: spawn a real writer gateway subprocess,
# drive it with concurrent socket clients, and require nonzero request-
# latency percentiles in the obs snapshot, a graceful SIGTERM drain
# (exit 0), and an atomically published --stats-json that parses.
python scripts/gateway_smoke.py

# Chaos smoke (~30s, fixed seed): writer + standby + replica fleet under
# a seeded fault schedule — one SIGKILL takeover, one injected fsync
# fault, one injected shard corruption.  Asserts zero acked-write loss,
# quarantine + degraded reads (never store-wide failure), and the
# fault/retry/quarantine counters in the obs snapshots.  `make chaos`
# runs the full harness across seeds 0-4.
python scripts/chaos.py --smoke --seed 0
