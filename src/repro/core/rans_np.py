"""Reference rANS entropy coder (numpy/python) + the vectorized
interleaved N-lane coder that serves the codec hot path.

This is the entropy-coding stage of the paper's model of Zstd
(``FSE(LZ77(...))`` — FSE is the table-driven cousin of rANS) implemented
from scratch.  It serves three roles:

1. oracle for the JAX/TPU interleaved coder in ``repro.core.rans``,
2. entropy stage of the from-scratch ``repro-lzr`` backend
   (LZ77 -> rANS ~= the paper's LZ77 -> FSE description of Zstd),
3. order-0 coder over *token ids* for the token-stream storage mode.

Classic 32-bit-state rANS with 16-bit renormalization; python ints make
the scalar arithmetic exact, numpy handles tables.  Streaming convention:
encoder walks the symbols in reverse and appends 16-bit words; the
serialized stream stores those words reversed so the decoder reads
forward.

The interleaved coder runs N independent rANS states in lockstep over a
round-robin symbol split (symbol ``i`` belongs to lane ``i % N``): every
step is a handful of vectorized uint64 ops over the N states, and because
a 32-bit state with 16-bit renorm emits **at most one** word per symbol
(``x_max = f << (32-pb) >= 2^16`` for ``pb <= 16``), renormalization is a
single mask.  All lanes share one word stream: the encoder emits each
step's words in descending-lane order so the (forward-reading) decoder
can consume them in ascending-lane order.  Lane 1 of the interleaved
coder reproduces the scalar stream bit-for-bit (asserted in tests).

Blob format: the header's `asize` field distinguishes a dense 256-entry
frequency table (asize == 256, the original layout) from the sparse
(symbol, freq)-pair table for small/low-alphabet inputs (asize 1..255).
Single-lane blobs keep the original layout byte-for-byte.  Multi-lane
blobs set bit 7 of the ``prob_bits`` header byte (legacy writers only
ever produced 1..16 there) and insert one lane-count byte —
``log2(lanes)`` — after it; the tail then carries ``lanes`` u32 states
followed by one shared word stream.  Readers predating the flag cannot
parse multi-lane blobs; this reader accepts every layout.
"""

from __future__ import annotations

import struct
from typing import Optional, Tuple

import numpy as np

from repro.core import env

PROB_BITS_DEFAULT = 12
_STATE_LOW = 1 << 16  # renormalization lower bound

# interleaved-coder defaults: payloads below _LANES_MIN_BYTES stay on the
# single-lane scalar path (fixed numpy overhead + 4 header bytes per lane
# dominate tiny blobs); above it the lane count scales with payload size
# so per-step vector width amortizes numpy dispatch
_LANES_MIN_BYTES = 4096
_LANES_MAX = 1024

# auto-mode crossover for the device lane-parallel kernels: below this the
# upload + per-call dispatch beats the lockstep win.  An estimate, not yet
# measured on a chip (benchmarks/kernel_throughput.py); override with
# REPRO_RANS_DEVICE_MIN
_DEVICE_MIN_BYTES = 1 << 16


def _use_device_rans(n: int) -> bool:
    """REPRO_RANS_MODE routing: ``numpy`` forces the host coder,
    ``device`` forces the Pallas lane kernels (interpret mode on CPU —
    tests/parity smokes), ``auto`` (default) takes the device only when a
    non-CPU backend is attached and the payload clears the crossover.
    Every decision, forced or not, lands in the ``device.dispatch``
    census."""
    from repro.core import device as _device

    force = {"device": True, "auto": None}.get(env.read("REPRO_RANS_MODE"),
                                               False)
    return _device.use_device(n, "REPRO_RANS_DEVICE_MIN", _DEVICE_MIN_BYTES,
                              force=force)


def _env_lanes() -> Optional[int]:
    """``REPRO_RANS_LANES``, sanitized by the env registry's parser (the
    explicit ``lanes=`` argument keeps strict validation; the env knob
    warns and clamps — see repro.core.env)."""
    return env.read("REPRO_RANS_LANES")


def normalize_freqs(counts: np.ndarray, prob_bits: int = PROB_BITS_DEFAULT) -> np.ndarray:
    """Scale a histogram to sum to 2**prob_bits with every observed symbol
    keeping frequency >= 1 (largest-remainder apportionment)."""
    counts = np.asarray(counts, dtype=np.float64)
    total = counts.sum()
    target = 1 << prob_bits
    if total <= 0:
        raise ValueError("empty histogram")
    present = counts > 0
    n_present = int(present.sum())
    if n_present > target:
        raise ValueError(f"alphabet has {n_present} symbols > table size {target}")
    raw = counts * (target / total)
    freqs = np.floor(raw).astype(np.int64)
    freqs[present & (freqs == 0)] = 1
    diff = target - int(freqs.sum())
    if diff > 0:  # hand out leftovers by largest remainder
        rema = raw - np.floor(raw)
        rema[~present] = -1.0
        order = np.argsort(-rema, kind="stable")
        freqs[order[:diff]] += 1
    elif diff < 0:  # take back from the largest entries (keep >= 1)
        order = np.argsort(-freqs, kind="stable")
        k = 0
        while diff < 0:
            idx = order[k % len(order)]
            if freqs[idx] > 1:
                freqs[idx] -= 1
                diff += 1
            k += 1
    assert freqs.sum() == target
    return freqs.astype(np.uint32)


def rans_encode(
    symbols: np.ndarray, freqs: np.ndarray, prob_bits: int = PROB_BITS_DEFAULT
) -> Tuple[np.ndarray, int]:
    """Encode `symbols` under `freqs`; returns (emitted u16 words, state)."""
    cum = np.concatenate(([0], np.cumsum(freqs.astype(np.int64))))
    x = _STATE_LOW
    words = []
    shift = 16 + 16 - prob_bits  # x_max = freq << shift keeps x < 2**32
    for s in symbols[::-1]:
        s = int(s)
        f = int(freqs[s])
        if f == 0:
            raise ValueError(f"symbol {s} has zero frequency")
        x_max = f << shift
        while x >= x_max:
            words.append(x & 0xFFFF)
            x >>= 16
        x = ((x // f) << prob_bits) + (x % f) + int(cum[s])
    return np.array(words, dtype=np.uint16), x


def rans_decode(
    words: np.ndarray, state: int, n: int, freqs: np.ndarray,
    prob_bits: int = PROB_BITS_DEFAULT,
) -> np.ndarray:
    """Inverse of `rans_encode`. `words` in emission order."""
    cum = np.concatenate(([0], np.cumsum(freqs.astype(np.int64))))
    # slot -> symbol lookup
    slot2sym = np.repeat(
        np.arange(len(freqs), dtype=np.int64), freqs.astype(np.int64)
    )
    mask = (1 << prob_bits) - 1
    x = int(state)
    pos = len(words) - 1  # consume in reverse emission order
    out = np.empty(n, dtype=np.int64)
    for i in range(n):
        slot = x & mask
        s = int(slot2sym[slot])
        out[i] = s
        x = int(freqs[s]) * (x >> prob_bits) + slot - int(cum[s])
        while x < _STATE_LOW:
            if pos < 0:
                raise ValueError("rANS stream underflow")
            x = (x << 16) | int(words[pos])
            pos -= 1
    return out


# ---------------------------------------------------------------------------
# Vectorized interleaved N-lane coder
# ---------------------------------------------------------------------------


def rans_encode_interleaved(
    symbols: np.ndarray, freqs: np.ndarray, lanes: int,
    prob_bits: int = PROB_BITS_DEFAULT,
) -> Tuple[np.ndarray, np.ndarray]:
    """Encode `symbols` over N interleaved lanes (lane = index % lanes).

    Returns (words u16 in forward/decode order, final states [lanes] u32).
    All arithmetic is uint64 so the single-symbol-alphabet edge
    (f == 2**prob_bits, x_max == 2**32) needs no special case.
    """
    n = symbols.size
    cum = np.concatenate(
        (np.zeros(1, np.uint64), np.cumsum(freqs, dtype=np.uint64)))
    fs = freqs.astype(np.uint64)[symbols]
    cs = cum[symbols]
    xm = fs << np.uint64(32 - prob_bits)
    T = n // lanes          # full steps
    rem = n - T * lanes     # partial tail step (lanes 0..rem-1)
    x = np.full(lanes, _STATE_LOW, np.uint64)
    pb = np.uint64(prob_bits)
    u16 = np.uint64(0xFFFF)
    sixteen = np.uint64(16)
    chunks = []
    if rem:  # encoder runs back-to-front: tail step first
        xa = x[:rem]
        emit = xa >= xm[T * lanes :]
        w = (xa[emit] & u16).astype(np.uint16)
        if w.size:
            chunks.append(w[::-1])
        xa = xa >> (emit.astype(np.uint64) * sixteen)
        q, r = np.divmod(xa, fs[T * lanes :])
        x[:rem] = (q << pb) + r + cs[T * lanes :]
    fg = fs[: T * lanes].reshape(T, lanes)
    cg = cs[: T * lanes].reshape(T, lanes)
    xg = xm[: T * lanes].reshape(T, lanes)
    for t in range(T - 1, -1, -1):
        emit = x >= xg[t]
        w = (x[emit] & u16).astype(np.uint16)
        if w.size:
            chunks.append(w[::-1])
        x = x >> (emit.astype(np.uint64) * sixteen)
        q, r = np.divmod(x, fg[t])
        x = (q << pb) + r + cg[t]
    if chunks:
        words = np.concatenate(chunks)[::-1]
    else:
        words = np.zeros(0, np.uint16)
    return words, x.astype(np.uint32)


def rans_decode_interleaved(
    words: np.ndarray, states: np.ndarray, n: int, freqs: np.ndarray,
    lanes: int, prob_bits: int = PROB_BITS_DEFAULT,
) -> np.ndarray:
    """Inverse of `rans_encode_interleaved`; returns uint8 symbols [n]."""
    cum = np.concatenate(
        (np.zeros(1, np.uint64), np.cumsum(freqs, dtype=np.uint64)))
    freqs64 = freqs.astype(np.uint64)
    slot2sym = np.repeat(np.arange(freqs.size, dtype=np.uint8),
                         freqs.astype(np.int64))
    mask = np.uint64((1 << prob_bits) - 1)
    pb = np.uint64(prob_bits)
    low = np.uint64(_STATE_LOW)
    sixteen = np.uint64(16)
    T = n // lanes
    rem = n - T * lanes
    x = states.astype(np.uint64)
    out = np.empty(T * lanes + (lanes if rem else 0), np.uint8)
    wl = words.astype(np.uint64)
    wpos = 0
    for t in range(T):
        slot = x & mask
        s = slot2sym[slot.astype(np.int64)]
        out[t * lanes : (t + 1) * lanes] = s
        x = freqs64[s] * (x >> pb) + (slot - cum[s])
        need = x < low
        k = int(np.count_nonzero(need))
        if k:
            if wpos + k > wl.size:
                raise ValueError("rANS stream underflow")
            x[need] = (x[need] << sixteen) | wl[wpos : wpos + k]
            wpos += k
    if rem:
        xa = x[:rem]
        slot = xa & mask
        out[T * lanes : T * lanes + rem] = slot2sym[slot.astype(np.int64)]
    return out[:n]


def _auto_lanes(n: int) -> int:
    """Power-of-two lane count targeting ~512 lockstep steps: the
    per-step cost is numpy dispatch (width-independent), so wider is
    faster until the 4-byte-per-lane state header matters — at n/512
    lanes the header stays ~2% of a typically-compressed payload.
    Auto range is 16..1024 (n >= 4096 implies (n>>9).bit_length() >= 4);
    smaller explicit lane counts remain valid via the `lanes` argument."""
    if n < _LANES_MIN_BYTES:
        return 1
    return min(1 << (n >> 9).bit_length(), _LANES_MAX)


# ---------------------------------------------------------------------------
# Self-contained byte-stream format
# ---------------------------------------------------------------------------
#
# single-lane (original layout, unchanged byte-for-byte):
#   u32le n_symbols | u8 prob_bits | u16le alphabet_size
#   freqs: alphabet_size x u16le   | u32le state | u32le n_words | words u16le
#   (words stored reversed so decode reads forward)
# interleaved (bit 7 of the prob_bits byte set; legacy writers never set it):
#   u32le n_symbols | u8 prob_bits|0x80 | u8 log2(lanes) | u16le alphabet_size
#   freqs table (same sparse/dense convention) | lanes x u32le states
#   u32le n_words | words u16le (forward order)


def _freq_table(symbols: np.ndarray, prob_bits: int) -> Tuple[np.ndarray, bytes, int]:
    from repro.core.entropy import byte_histogram

    counts = byte_histogram(symbols)  # np.bincount on CPU, Pallas on device
    freqs = normalize_freqs(counts, prob_bits)
    # `asize` field: 256 = dense 256-entry table; 1..255 = sparse table of
    # (symbol u8, freq u2) pairs.  Sparse wins on small or low-alphabet
    # inputs, where a 512-byte dense table would dominate the blob
    # (3 bytes/symbol vs 2 bytes/slot -> sparse iff k < 171).
    nonzero = np.flatnonzero(freqs)
    if nonzero.size < 171:
        table = (nonzero.astype("<u1").tobytes()
                 + freqs[nonzero].astype("<u2").tobytes())
        return freqs, table, nonzero.size
    return freqs, freqs.astype("<u2").tobytes(), 256


def rans_compress_bytes(data: bytes, prob_bits: int = PROB_BITS_DEFAULT,
                        lanes: Optional[int] = None) -> bytes:
    """Entropy-code `data`.  ``lanes=None`` auto-routes: the scalar
    single-lane path (original blob layout) for small payloads, the
    vectorized interleaved coder above ``_LANES_MIN_BYTES``.  Forcing
    ``lanes=1`` always yields the original layout byte-for-byte."""
    symbols = np.frombuffer(data, dtype=np.uint8)
    if symbols.size == 0:
        return struct.pack("<IBH", 0, prob_bits, 0)
    if lanes is None:
        lanes = _env_lanes()
        if lanes is None:
            lanes = _auto_lanes(symbols.size)
    if lanes & (lanes - 1) or not 1 <= lanes <= _LANES_MAX:
        raise ValueError(f"lanes must be a power of two in 1..{_LANES_MAX}")
    freqs, table, asize = _freq_table(symbols, prob_bits)
    if lanes == 1:
        words, state = rans_encode(symbols, freqs, prob_bits)
        header = struct.pack("<IBH", symbols.size, prob_bits, asize)
        tail = (struct.pack("<II", state, words.size)
                + words[::-1].astype("<u2").tobytes())
        return header + table + tail
    # the single-symbol alphabet (f == 2**prob_bits) overflows the device
    # kernel's uint32 x_max; only the NumPy uint64 lanes handle it
    if asize > 1 and _use_device_rans(symbols.size):
        from repro.kernels.rans_lanes import rans_encode_interleaved_device

        words, states = rans_encode_interleaved_device(
            symbols, freqs, lanes, prob_bits)
    else:
        words, states = rans_encode_interleaved(
            symbols, freqs, lanes, prob_bits)
    header = struct.pack("<IBBH", symbols.size, prob_bits | 0x80,
                         lanes.bit_length() - 1, asize)
    return (header + table + states.astype("<u4").tobytes()
            + struct.pack("<I", words.size) + words.astype("<u2").tobytes())


def _read_freq_table(blob: bytes, asize: int, off: int) -> Tuple[np.ndarray, int]:
    if asize < 256:  # sparse (symbol, freq) pairs
        syms = np.frombuffer(blob, dtype="<u1", count=asize, offset=off)
        off += asize
        vals = np.frombuffer(blob, dtype="<u2", count=asize, offset=off)
        off += 2 * asize
        freqs = np.zeros(256, dtype=np.uint32)
        freqs[syms] = vals
        return freqs, off
    freqs = np.frombuffer(blob, dtype="<u2", count=asize, offset=off).astype(np.uint32)
    return freqs, off + 2 * asize


def _parse_interleaved(blob: bytes):
    """Header/table/state/word fields of a multi-lane blob."""
    n, pbb, lane_exp, asize = struct.unpack_from("<IBBH", blob, 0)
    lanes = 1 << lane_exp
    freqs, off = _read_freq_table(blob, asize, 8)
    states = np.frombuffer(blob, dtype="<u4", count=lanes, offset=off)
    off += 4 * lanes
    (n_words,) = struct.unpack_from("<I", blob, off)
    off += 4
    words = np.frombuffer(blob, dtype="<u2", count=n_words, offset=off)
    return n, pbb & 0x7F, lanes, asize, freqs, states, words


def rans_decompress_bytes(blob: bytes) -> bytes:
    n, prob_bits, = struct.unpack_from("<IB", blob, 0)
    if n == 0:
        return b""
    if prob_bits & 0x80:  # interleaved layout
        n, pb, lanes, asize, freqs, states, words = _parse_interleaved(blob)
        if asize > 1 and _use_device_rans(n):
            from repro.kernels.rans_lanes import \
                rans_decode_interleaved_device

            out = rans_decode_interleaved_device(
                words, states, n, freqs, lanes, pb)
        else:
            out = rans_decode_interleaved(words, states, n, freqs, lanes, pb)
        return out.tobytes()
    n, prob_bits, asize = struct.unpack_from("<IBH", blob, 0)
    freqs, off = _read_freq_table(blob, asize, 7)
    state, n_words = struct.unpack_from("<II", blob, off)
    off += 8
    words = np.frombuffer(blob, dtype="<u2", count=n_words, offset=off)[::-1]
    out = rans_decode(words, state, n, freqs, prob_bits)
    return out.astype(np.uint8).tobytes()


def rans_decompress_to_device(blob: bytes):
    """Decode a blob into a **device-resident** uint8 array (a jnp array)
    — the serve path's decompress-to-tokens hands this straight to the
    token-unpack stage without a host byte round trip.  Layouts the lane
    kernel doesn't cover (single-lane, empty, single-symbol alphabet)
    decode on the host and upload."""
    import jax.numpy as jnp

    n, prob_bits, = struct.unpack_from("<IB", blob, 0)
    if n and prob_bits & 0x80:
        n, pb, lanes, asize, freqs, states, words = _parse_interleaved(blob)
        if asize > 1:
            from repro.kernels.rans_lanes import \
                rans_decode_interleaved_device

            return rans_decode_interleaved_device(
                words, states, n, freqs, lanes, pb, to_host=False)
    return jnp.asarray(
        np.frombuffer(rans_decompress_bytes(blob), np.uint8))
