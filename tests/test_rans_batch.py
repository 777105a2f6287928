"""The group commit as the unit of rANS device work.

``rans_np.rans_compress_batch`` encodes every record of a batch in at
most two lane-kernel launches (single-lane records as columns of one,
multi-lane records' lanes side by side in the other), each lane up to
its own step end.  Every blob must be byte-identical to
``rans_compress_bytes`` of the same record, and launches must compile
per size bucket, not per flush.  Forced device mode runs the kernel in
interpret mode on CPU.
"""

from __future__ import annotations

import os
import random

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from repro import obs
from repro.core import device
from repro.core.codec import ByteCompressorCodec
from repro.core.rans_np import (_auto_lanes, normalize_freqs,
                                rans_compress_batch, rans_compress_bytes,
                                rans_decompress_bytes)
from repro.core.zstd_backend import compress_bytes
from repro.data import corpus
from repro.kernels.rans_lanes import (encode_lanes_ref,
                                      rans_encode_records_device)
from repro.kernels.rans_lanes import ops as rans_ops

_PB = 12
_RNG = random.Random(19)
_WORDS = [bytes(_RNG.choices(b"etaoin shrdlu\n", k=_RNG.randint(2, 9)))
          for _ in range(64)]


def _text(n: int) -> bytes:
    """``n`` bytes over a 14-letter alphabet (a sparse table)."""
    out = b"".join(_RNG.choice(_WORDS) for _ in range(n // 4 + 1))
    return out[:n]


def _dense(n: int) -> bytes:
    """``n`` bytes over all 256 values (a dense table)."""
    return _RNG.randbytes(n)


# each case is one batch; sizes chosen for the layouts they reach
BATCHES = {
    # auto lanes 1: the single-lane layout, as columns of one launch
    "single-lane": [_text(n) for n in (1, 2, 100, 1023, 3000, 4095)],
    # auto lanes 16, 32, 64, 128, 256, 512 and 1024, side by side
    "lanes-16-1024": [_text(n) for n in (4096, 8192, 20000, 40000, 70000,
                                         140000, 270000)],
    # n % lanes != 0: the tail row on lanes < rem only
    "partial-tail": [_text(n) for n in (4097, 4101, 6007, 9001, 16385)],
    "sparse-dense": [_text(5000), _dense(5000), _text(700), _dense(700),
                     _dense(33000)],
    # both stay on the NumPy coder, between records that do not
    "single-symbol-empty": [_text(5000), b"", b"\x07" * 6000, _text(200),
                            b"\x00", _dense(4500)],
    # what took the per-record device path before: a batch of one
    "one-above-64k": [_text(70000)],
    "mixed": [_text(n) for n in (50, 4095, 4096, 12345, 33333, 1000, 7)]
             + [_dense(9000), b"", b"Z" * 4100],
}


def _numpy_blobs(datas, monkeypatch):
    monkeypatch.setenv("REPRO_RANS_MODE", "numpy")
    return [rans_compress_bytes(d) for d in datas]


def _records(name: str):
    c = obs.snapshot()["counters"]
    return c.get(f"codec.rans.records{{path={name}}}", 0)


@pytest.mark.parametrize("case", sorted(BATCHES))
def test_rans_batch_blobs_match_per_record(case, monkeypatch):
    """Forced onto the device, a batch gives each record the blob
    ``rans_compress_bytes`` gives it, which decodes; empty and
    single-symbol records are counted on the host path."""
    datas = BATCHES[case]
    want = _numpy_blobs(datas, monkeypatch)
    monkeypatch.setenv("REPRO_RANS_MODE", "device")
    dev0, host0 = _records("device"), _records("host")
    got = rans_compress_batch(datas)
    assert got == want
    for data, blob in zip(datas, got):
        assert rans_decompress_bytes(blob) == data
    on_host = sum(len(set(d)) <= 1 for d in datas)
    if obs.enabled():
        assert _records("device") - dev0 == len(datas) - on_host
        assert _records("host") - host0 == on_host


def test_rans_batch_env_lanes(monkeypatch):
    """``REPRO_RANS_LANES`` picks every record's lane count in the batch
    as it does for ``rans_compress_bytes``: one lane for large records,
    eight for small ones."""
    datas = [_text(n) for n in (100, 5000, 20000)] + [_dense(3000)]
    for lanes in ("1", "8"):
        monkeypatch.setenv("REPRO_RANS_LANES", lanes)
        want = _numpy_blobs(datas, monkeypatch)
        monkeypatch.setenv("REPRO_RANS_MODE", "device")
        assert rans_compress_batch(datas) == want


def test_lzr_byte_stage_batch_matches_per_record(monkeypatch):
    """The ``repro-lzr`` byte stage: LZ77 on the pool, then the batch's
    rANS on the device, gives the bytes of ``compress_bytes`` per
    record."""
    payloads = [_text(n) * 3 for n in (40, 2000, 9000, 30000)] + [b""]
    monkeypatch.setenv("REPRO_RANS_MODE", "numpy")
    want = [compress_bytes(p, backend="repro-lzr") for p in payloads]
    monkeypatch.setenv("REPRO_RANS_MODE", "device")
    assert ByteCompressorCodec(backend="repro-lzr").encode_batch(
        payloads) == want


def test_rans_batch_routes_on_batch_total(monkeypatch):
    """The crossover applies to the batch's total bytes: records each
    below it go to the device together once their sum clears it, and
    stay on the host when it does not (the host is never routed to the
    chip implicitly: the backend must be attached)."""
    datas = [_text(3000) for _ in range(4)]          # 12,000 bytes in all
    want = _numpy_blobs(datas, monkeypatch)
    monkeypatch.setenv("REPRO_RANS_MODE", "auto")
    monkeypatch.setattr(device, "backend_available", lambda: True)
    for floor, path in ((12000, "device"), (12001, "host")):
        monkeypatch.setenv("REPRO_RANS_DEVICE_MIN", str(floor))
        before = _records(path)
        assert rans_compress_batch(datas) == want
        if obs.enabled():
            assert _records(path) - before == len(datas)


def _freqs_for(sym: np.ndarray) -> np.ndarray:
    return normalize_freqs(np.bincount(sym, minlength=256), _PB)


def test_rans_lanes_step_ends_match_separate_launches():
    """Lanes with different step ends (records of other lengths, lane
    counts and tails in one launch) give each record the stream and
    states of its own launch and of the NumPy coder."""
    specs = [(5000, 16), (4101, 16), (17, 32), (9000, 64), (300, 4),
             (12345, 128)]
    records = []
    for n, lanes in specs:
        sym = np.frombuffer(_text(n), np.uint8)
        records.append((sym, _freqs_for(sym), lanes))
    together = rans_encode_records_device(records, _PB, interpret=True)
    for rec, (words, states) in zip(records, together):
        alone_w, alone_s = rans_encode_records_device([rec], _PB,
                                                      interpret=True)[0]
        ref_w, ref_s = encode_lanes_ref(rec[0], rec[1], rec[2], _PB)
        assert np.array_equal(words, alone_w)
        assert np.array_equal(states, alone_s)
        assert np.array_equal(words, ref_w)
        assert np.array_equal(states, ref_s)


def test_rans_records_refuse_single_symbol_alphabet():
    """f == 2**prob_bits overflows the kernel's u32 state bound."""
    sym = np.zeros(5000, np.uint8)
    with pytest.raises(ValueError):
        rans_encode_records_device([(sym, _freqs_for(sym), 16)], _PB,
                                   interpret=True)


# -- launch shapes -----------------------------------------------------------

# LZ77 output per text character of the ingest corpus (median record
# 4,980 B of LZ77 output for a median prompt of 20,803 characters)
_LZ_PER_CHAR = 0.24


def _flush_sizes(seed: int, records: int = 64):
    """LZ77 output sizes of one group commit of corpus-law prompts."""
    rng = np.random.default_rng(seed)
    chars = np.clip(rng.lognormal(corpus._MU, corpus._SIGMA, records),
                    corpus._MIN_CHARS, corpus._MAX_CHARS)
    return [int(c * _LZ_PER_CHAR) for c in chars]


def _shapes(sizes):
    shapes = set()
    for single in (True, False):
        group = [(n, _auto_lanes(n)) for n in sizes
                 if (_auto_lanes(n) == 1) == single]
        if group:
            shapes.add(rans_ops.launch_shape(
                max(-(-n // lanes) for n, lanes in group),
                sum(lanes for _, lanes in group), len(group), single))
    return shapes


def test_rans_flush_launch_shapes_are_few():
    """200 group commits of 64 corpus-law records map to the two launch
    shapes of the lane floors, whatever their sizes."""
    shapes = set().union(*(_shapes(_flush_sizes(s)) for s in range(200)))
    assert shapes == {(4096, 128, 64), (512, 2048, 64)}


def test_rans_batch_compiles_per_bucket_not_per_flush(monkeypatch):
    """Two flushes of different corpus-law sizes compile the encode
    stage for their launch shapes once; a repeated flush and the
    second flush compile nothing more, and every blob still equals
    ``rans_compress_bytes``."""
    flushes = [[_text(n) for n in _flush_sizes(s)] for s in (1, 2)]
    monkeypatch.setenv("REPRO_RANS_MODE", "device")
    before = rans_ops._encode_stage._cache_size()
    rans_compress_batch(flushes[0])
    first = rans_ops._encode_stage._cache_size()
    assert first - before <= len(_shapes(_flush_sizes(1)))
    got = [rans_compress_batch(flushes[0]), rans_compress_batch(flushes[1])]
    assert rans_ops._encode_stage._cache_size() == first
    for datas, blobs in zip(flushes, got):
        assert blobs == _numpy_blobs(datas, monkeypatch)
