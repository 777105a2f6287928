"""Device-codec kernel benchmarks, on a chip only.

    PYTHONPATH=src:. python benchmarks/kernel_throughput.py   # on a TPU

* parity gate: the compiled device codec kernels against their host
  oracles — the LZ77 match finder must reproduce ``_lz_compress_np``'s
  stream, the lane-parallel rANS coder the interleaved blob, the
  histogram NumPy's counts.  A mismatch emits a ``FAIL`` row.  (The same
  parity in interpret mode on a CPU is tests/test_kernel_codec.py.)
* sweeps: device vs host throughput per kernel, the ``DEFAULT_BLOCK_N``
  block-size sweep of the token-pack kernel, and the first payload size
  at which the device wins — the data the ``REPRO_*_DEVICE_MIN``
  defaults should be set from.

A host without an accelerator raises instead of writing CPU timings
under device names.  Every record names its device.  Writes
``benchmarks/BENCH_kernel_codec.json``.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from benchmarks.common import corpus, csv_row

_OUT = Path(__file__).resolve().parent / "BENCH_kernel_codec.json"

REPS = 3
BLOCK_SWEEP = (512, 1024, 2048, 4096, 8192)   # pack kernel block_n candidates
_PARITY_BYTES = 1 << 16   # gate payload (one record-sized buffer)


def _best(fn, reps=REPS):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _payload(n: int) -> bytes:
    blob = "\n".join(p.text for p in corpus(32)).encode("utf-8")
    reps = -(-n // len(blob))
    return (blob * reps)[:n]


def _parity_rows(doc: dict) -> list:
    """Byte-parity of every compiled device codec stage (the lossless
    gate)."""
    from repro.core.entropy import byte_histogram
    from repro.core.lz77 import _lz_compress_device, _lz_compress_np
    from repro.core.rans_np import (normalize_freqs,
                                    rans_decode_interleaved,
                                    rans_encode_interleaved)
    from repro.kernels.rans_lanes import (rans_decode_interleaved_device,
                                          rans_encode_interleaved_device)

    rows = []
    payload = _payload(_PARITY_BYTES)
    sym = np.frombuffer(payload, np.uint8)
    freqs = normalize_freqs(np.bincount(sym, minlength=256))

    lz_ok = _lz_compress_device(payload) == _lz_compress_np(payload)
    rans_ok = True
    for lanes in (16, 256, 1024):
        w_r, x_r = rans_encode_interleaved(sym, freqs, lanes)
        w_d, x_d = rans_encode_interleaved_device(sym, freqs, lanes, 12)
        dec = rans_decode_interleaved_device(w_d, x_d, sym.size, freqs,
                                             lanes, 12)
        rans_ok &= (np.array_equal(w_r, w_d) and np.array_equal(x_r, x_d)
                    and bytes(dec) == payload
                    and rans_decode_interleaved(
                        w_d, x_d, sym.size, freqs, lanes).tobytes() == payload)
    hist_ok = np.array_equal(np.asarray(byte_histogram(payload, use_device=True)),
                             byte_histogram(payload, use_device=False))
    doc["parity"] = {"lz": lz_ok, "rans": rans_ok, "hist": hist_ok}
    for name, ok in doc["parity"].items():
        rows.append(csv_row(f"kernel_{name}_parity", 0,
                            "ok" if ok else "FAIL:byte_mismatch"))
    return rows


def _block_sweep_rows(doc: dict) -> list:
    """DEFAULT_BLOCK_N sweep for the token-pack byte-split kernel:
    correctness, then time, per candidate block."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.token_pack.kernel import pack_tokens_kernel
    from repro.kernels.token_pack.ref import pack_ref

    rows = []
    rng = np.random.default_rng(7)
    ids = rng.integers(0, 1 << 20, 1 << 16).astype(np.int32)
    sweep = {}
    for block_n in BLOCK_SWEEP:
        idsp = ids[: (ids.size // block_n) * block_n]
        x = jnp.asarray(idsp)
        correct = np.array_equal(
            np.asarray(pack_tokens_kernel(x, width=4, block_n=block_n)),
            np.asarray(pack_ref(x, 4)))
        if not correct:
            rows.append(csv_row(f"kernel_pack_block{block_n}", 0,
                                "FAIL:byte_mismatch"))
            continue
        fn = jax.jit(lambda a, b=block_n: pack_tokens_kernel(
            a, width=4, block_n=b))
        fn(x).block_until_ready()
        t = _best(lambda: fn(x).block_until_ready())
        mbps = idsp.nbytes / 1e6 / t
        sweep[block_n] = mbps
        rows.append(csv_row(f"kernel_pack_block{block_n}", 1e6 * t,
                            f"{mbps:.0f}MB/s ok"))
    doc["pack_block_sweep_mbps"] = sweep
    if sweep:
        doc["pack_block_best"] = max(sweep, key=sweep.get)
    return rows


def _device_sweep_rows(doc: dict) -> list:
    """Device-vs-host throughput + crossover hints for the LZ and rANS
    stages."""
    rows = []
    from repro.core.entropy import byte_histogram
    from repro.core.lz77 import _lz_compress_device, _lz_compress_np
    from repro.core.rans_np import normalize_freqs, rans_encode_interleaved
    from repro.kernels.rans_lanes import rans_encode_interleaved_device

    crossovers = {}
    for name, host_fn, dev_fn in (
        ("lz_match",
         lambda p: _lz_compress_np(p),
         lambda p: _lz_compress_device(p)),
        ("rans_lanes",
         lambda p: rans_encode_interleaved(
             np.frombuffer(p, np.uint8),
             normalize_freqs(np.bincount(np.frombuffer(p, np.uint8),
                                         minlength=256)), 256),
         lambda p: rans_encode_interleaved_device(
             np.frombuffer(p, np.uint8),
             normalize_freqs(np.bincount(np.frombuffer(p, np.uint8),
                                         minlength=256)), 256, 12)),
        ("histogram",
         lambda p: byte_histogram(p, use_device=False),
         lambda p: byte_histogram(p, use_device=True)),
    ):
        cross = None
        for size in (1 << 14, 1 << 16, 1 << 18, 1 << 20, 1 << 22):
            p = _payload(size)
            dev_fn(p)   # warm the jit cache before timing
            t_h = _best(lambda: host_fn(p))
            t_d = _best(lambda: dev_fn(p))
            mb = size / 1e6
            rows.append(csv_row(
                f"kernel_{name}_{size}", 1e6 * t_d,
                f"host={mb/t_h:.1f}MB/s device={mb/t_d:.1f}MB/s "
                f"speedup={t_h/t_d:.2f}x"))
            if cross is None and t_d < t_h:
                cross = size
        crossovers[name] = cross
    doc["measured_crossover_bytes"] = crossovers
    return rows


def run() -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise RuntimeError(
            "kernel_throughput measures the device kernels and needs an "
            "accelerator; JAX found only the CPU")
    doc = {"device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(devices)},
           "reps": REPS, "block_sweep": list(BLOCK_SWEEP)}
    rows = _parity_rows(doc)
    rows += _block_sweep_rows(doc)
    rows += _device_sweep_rows(doc)
    try:
        _OUT.write_text(json.dumps(doc, indent=1) + "\n")
    except OSError:
        pass  # benchmarks dir read-only: keep the csv rows
    return rows


if __name__ == "__main__":
    for row in run():
        print(row)
