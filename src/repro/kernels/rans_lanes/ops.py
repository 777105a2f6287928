"""Public wrappers: lane-parallel rANS encode/decode on device.

These drive the existing multi-lane blob layout of
``repro.core.rans_np`` — same round-robin lane split, same shared word
stream, same header — so blobs produced by either implementation decode
under the other byte-for-byte (asserted across the parity corpus in
tests/test_kernel_codec.py).

Split of labor:

* the jitted stage functions run the frequency-table gathers, the
  partial tail step (rANS encodes it first / decodes it last — one
  vector op), padding, and the Pallas lockstep kernel on device;
* the host side only compacts the dense [T, lanes] word/mask pair into
  the serialized stream (encode) and runs the underflow check (decode).
  Decode can skip the host entirely: ``to_host=False`` returns the
  symbol array still resident in device memory — the serve path's
  decompress-to-tokens feeds on this.

The dispatch layer (``rans_np.rans_compress_bytes``) never routes the
single-symbol alphabet here: ``f == 2**prob_bits`` makes
``x_max == 2**32``, which needs the NumPy coder's uint64 lanes.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_default
from repro.kernels.rans_lanes.kernel import (DEFAULT_BLOCK_T, WORD_ROW,
                                             decode_window_rows,
                                             rans_decode_lanes_kernel,
                                             rans_encode_lanes_kernel)


@partial(jax.jit, static_argnames=("lanes", "prob_bits", "interpret"))
def _encode_stage(symbols: jnp.ndarray, freqs: jnp.ndarray, lanes: int,
                  prob_bits: int, interpret: bool):
    n = symbols.shape[0]
    T = n // lanes
    rem = n - T * lanes
    f32 = freqs.astype(jnp.uint32)
    cum = jnp.cumsum(f32, dtype=jnp.uint32) - f32      # exclusive prefix
    sym = symbols.astype(jnp.int32)
    fs_all = f32[sym]
    cs_all = cum[sym]
    shift = jnp.uint32(32 - prob_bits)
    pb = jnp.uint32(prob_bits)
    x0 = jnp.full((lanes,), 1 << 16, jnp.uint32)
    tail_w = jnp.zeros((lanes,), jnp.uint32)
    tail_em = jnp.zeros((lanes,), jnp.int32)
    if rem:   # tail step runs first on the encode side
        ft = fs_all[T * lanes:]
        ct = cs_all[T * lanes:]
        xa = x0[:rem]
        em = xa >= (ft << shift)
        tail_w = tail_w.at[:rem].set(xa & jnp.uint32(0xFFFF))
        tail_em = tail_em.at[:rem].set(em.astype(jnp.int32))
        xa = jnp.where(em, xa >> jnp.uint32(16), xa)
        xa = ((xa // ft) << pb) + (xa % ft) + ct
        x0 = x0.at[:rem].set(xa)
    bt = DEFAULT_BLOCK_T
    tp = max(-(-T // bt) * bt, bt)
    fs = jnp.pad(fs_all[: T * lanes].reshape(T, lanes),
                 ((0, tp - T), (0, 0)), constant_values=1)
    cs = jnp.pad(cs_all[: T * lanes].reshape(T, lanes),
                 ((0, tp - T), (0, 0)))
    words, emit, states = rans_encode_lanes_kernel(
        fs, cs, x0[None, :], total_t=T, prob_bits=prob_bits, block_t=bt,
        interpret=interpret)
    return words, emit, states[0], tail_w, tail_em


def rans_encode_interleaved_device(
        symbols: np.ndarray, freqs: np.ndarray, lanes: int,
        prob_bits: int, interpret: Optional[bool] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Device counterpart of ``rans_np.rans_encode_interleaved``: returns
    (words u16 in forward/decode order, final states [lanes] u32),
    bit-identical to the NumPy coder."""
    interpret = interpret_default(interpret)
    n = int(symbols.size)
    words_d, emit_d, states_d, tail_w, tail_em = _encode_stage(
        jnp.asarray(symbols, jnp.uint8), jnp.asarray(freqs, jnp.uint32),
        int(lanes), int(prob_bits), interpret)
    # forward stream = dense words masked in row-major (step asc, lane
    # asc) order; the tail step's words (emitted first) come last
    emit = np.asarray(emit_d, dtype=bool)
    fwd = np.asarray(words_d)[emit].astype(np.uint16)
    rem = n - (n // lanes) * lanes
    if rem:
        te = np.asarray(tail_em, dtype=bool)
        fwd = np.concatenate([fwd, np.asarray(tail_w)[te].astype(np.uint16)])
    return fwd, np.asarray(states_d, np.uint32)


@partial(jax.jit, static_argnames=("n", "lanes", "prob_bits", "interpret"))
def _decode_stage(words: jnp.ndarray, states: jnp.ndarray,
                  freqs: jnp.ndarray, n: int, lanes: int, prob_bits: int,
                  interpret: bool):
    T = n // lanes
    rem = n - T * lanes
    f32 = freqs.astype(jnp.int32)
    cum = jnp.cumsum(f32) - f32                       # exclusive prefix
    lp = -(-lanes // WORD_ROW) * WORD_ROW
    rows = -(-words.shape[0] // WORD_ROW) + decode_window_rows(lp)
    rows = -(-rows // 8) * 8
    wpad = jnp.pad(words.astype(jnp.uint32),
                   (0, rows * WORD_ROW - words.shape[0]))
    st = jnp.pad(states.astype(jnp.uint32), (0, lp - lanes))
    sym, states_f, wcnt = rans_decode_lanes_kernel(
        wpad.reshape(rows, WORD_ROW), st[None, :], cum[:, None], total_t=T,
        prob_bits=prob_bits, lanes=lanes, interpret=interpret)
    flat = sym[:, :lanes].reshape(-1)[: T * lanes]
    if rem:   # tail symbols: slot lookup only, no renorm (mirrors NumPy)
        slot = (states_f[0, :rem] & jnp.uint32((1 << prob_bits) - 1)
                ).astype(jnp.int32)
        tail = jnp.sum(cum[None, :] <= slot[:, None], axis=1) - 1
        flat = jnp.concatenate([flat, tail])
    return flat.astype(jnp.uint8), wcnt


def rans_decode_interleaved_device(
        words: np.ndarray, states: np.ndarray, n: int, freqs: np.ndarray,
        lanes: int, prob_bits: int, interpret: Optional[bool] = None,
        to_host: bool = True):
    """Device counterpart of ``rans_np.rans_decode_interleaved``.

    ``to_host=False`` returns the uint8 symbol array still resident on
    the device (a jnp array) — the serve path hands it straight to the
    token-unpack stage without a host byte round trip."""
    interpret = interpret_default(interpret)
    out, wcnt = _decode_stage(
        jnp.asarray(words, jnp.uint16), jnp.asarray(states, jnp.uint32),
        jnp.asarray(freqs, jnp.uint32), int(n), int(lanes),
        int(prob_bits), interpret)
    if int(wcnt[0]) > int(words.size):
        raise ValueError("rANS stream underflow")
    return np.asarray(out) if to_host else out
