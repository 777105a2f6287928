"""Public wrappers: lane-parallel rANS encode/decode on device.

These drive the existing multi-lane blob layout of
``repro.core.rans_np`` — same round-robin lane split, same shared word
stream, same header — so blobs produced by either implementation decode
under the other byte-for-byte (asserted across the parity corpus in
tests/test_kernel_codec.py).

Split of labor:

* encode takes many records at once (``rans_encode_records_device``):
  the host lays their symbols out as one u8 grid, each record's lanes
  a block of columns with its partial tail step as one more row on the
  lanes it covers, pads the grid to its ``launch_shape`` and uploads
  it with one frequency table per record; the jitted stage gathers
  each cell's (freq, cumfreq) on the device and runs the Pallas
  lockstep kernel, every lane up to its own step end; the host reads
  back one u32 per cell (word and emit flag) and compacts each
  record's block into its stream.  A single record is a batch of one;
* decode's jitted stage runs the frequency-table prefix, padding, the
  Pallas kernel and the partial tail step (decoded last) on device;
  the host runs the underflow check.
  Decode can skip the host entirely: ``to_host=False`` returns the
  symbol array still resident in device memory — the serve path's
  decompress-to-tokens feeds on this.

The dispatch layer (``rans_np.rans_compress_bytes``) never routes the
single-symbol alphabet here: ``f == 2**prob_bits`` makes
``x_max == 2**32``, which needs the NumPy coder's uint64 lanes.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import interpret_default, size_bucket
from repro.kernels.rans_lanes.kernel import (DEFAULT_BLOCK_T, WORD_ROW,
                                             decode_window_rows,
                                             rans_decode_lanes_kernel,
                                             rans_encode_lanes_kernel)


#: launch-shape floors per lane layout.  Auto lane counts give a
#: multi-lane record 256-512 steps (its tail row included) and a
#: single-lane one fewer than 4,096, and a group commit of the corpus
#: law holds about 1,100 lanes of at most 64 records: so every such
#: flush launches one [512, 2048] and one [4096, 128] grid with tables
#: for 64 records, and larger groups step up by ``size_bucket``
_MULTI_ROWS, _MULTI_LANES = 512, 2048
_SINGLE_ROWS, _SINGLE_LANES = 4096, 128
_RECORDS = 64
_BLOCK_CELLS = 1 << 18      # cells of one step block held in VMEM


def launch_shape(rows: int, lanes: int, records: int,
                 single: bool) -> Tuple[int, int, int]:
    """(steps, lanes, table rows) a launch of ``records`` records needing
    ``rows`` steps and ``lanes`` lanes in all is padded to, each axis by
    ``size_bucket``, so that the kernel compiles per bucket, not per
    flush."""
    row_floor, lane_floor = ((_SINGLE_ROWS, _SINGLE_LANES) if single
                             else (_MULTI_ROWS, _MULTI_LANES))
    return (size_bucket(rows, row_floor), size_bucket(lanes, lane_floor),
            size_bucket(records, _RECORDS))


@partial(jax.jit, static_argnames=("prob_bits", "interpret"))
def _encode_stage(grid: jnp.ndarray, rec: jnp.ndarray, ends: jnp.ndarray,
                  freqs: jnp.ndarray, prob_bits: int, interpret: bool):
    """grid: [Tp, Lp] u8 symbols (record-major lane blocks, step ``t`` of
    a lane in row ``t``); rec / ends: [1, Lp] i32, each lane's record
    and step end; freqs: [Rp, 256] u32, one table per record.  The
    tables' gathers run here, on the device."""
    f = freqs.astype(jnp.uint32)
    cum = jnp.cumsum(f, axis=1, dtype=jnp.uint32) - f   # exclusive prefix
    idx = rec * 256 + grid.astype(jnp.int32)
    fs = jnp.maximum(f.reshape(-1)[idx], jnp.uint32(1))  # pad cells: f = 1
    cs = cum.reshape(-1)[idx]
    lp = grid.shape[1]
    block_t = min(DEFAULT_BLOCK_T,
                  1 << ((_BLOCK_CELLS // lp).bit_length() - 1))
    return rans_encode_lanes_kernel(
        fs | (cs << jnp.uint32(16)), ends, prob_bits=prob_bits,
        block_t=block_t, interpret=interpret)


def _lay_out(group, single: bool):
    """Host inputs of one launch over ``group``, (symbols, freqs, lanes)
    triples all single-lane or all multi-lane: the symbol grid, each
    lane's record and step end, the tables, padded to their
    ``launch_shape``."""
    steps = [divmod(sym.size, lanes) for sym, _, lanes in group]
    rows = max(t + (rem > 0) for t, rem in steps)
    cols = sum(lanes for _, _, lanes in group)
    tp, lp, rp = launch_shape(rows, cols, len(group), single)
    grid = np.zeros((tp, lp), np.uint8)
    rec = np.zeros((1, lp), np.int32)
    ends = np.zeros((1, lp), np.int32)        # padding lanes never step
    tables = np.zeros((rp, 256), np.uint32)
    off = 0
    for k, ((sym, freqs, lanes), (t, rem)) in enumerate(zip(group, steps)):
        grid[:t, off:off + lanes] = sym[: t * lanes].reshape(t, lanes)
        ends[0, off:off + lanes] = t
        if rem:   # the partial tail: one more row on lanes < rem
            grid[t, off:off + rem] = sym[t * lanes:]
            ends[0, off:off + rem] = t + 1
        rec[0, off:off + lanes] = k
        tables[k, : freqs.size] = freqs
        off += lanes
    return grid, rec, ends, tables


def _encode_group(group, single: bool, prob_bits: int,
                  interpret: bool) -> List[Tuple[np.ndarray, np.ndarray]]:
    """One launch over ``group`` (as ``_lay_out`` takes it)."""
    grid, rec, ends, tables = _lay_out(group, single)
    # one launch and its read-back; the trace event carries the group's
    # real sizes (the launch is their ``launch_shape``)
    with obs.span("kernel.rans_lanes",
                  trace_args={"records": len(group),
                              "lanes": sum(g[2] for g in group),
                              "steps": int(ends.max())}):
        cells_d, states_d = _encode_stage(
            jnp.asarray(grid), jnp.asarray(rec), jnp.asarray(ends),
            jnp.asarray(tables), prob_bits, interpret)
        cells = np.asarray(cells_d)
        states = np.asarray(states_d)[0]
    out = []
    off = 0
    for sym, _, lanes in group:
        block = cells[: -(-sym.size // lanes), off:off + lanes]
        # forward stream: flagged words in row-major (step, lane) order
        words = block[(block >> 16).astype(bool)].astype(np.uint16)
        out.append((words, states[off:off + lanes].copy()))
        off += lanes
    return out


def rans_encode_records_device(
        records: Sequence[Tuple[np.ndarray, np.ndarray, int]],
        prob_bits: int, interpret: Optional[bool] = None,
) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Device counterpart of ``rans_np.rans_encode_interleaved`` over many
    records at once.  ``records`` holds (symbols u8, freqs [256], lanes)
    triples, every frequency below ``2**prob_bits``; returns, per record,
    (words u16 in forward/decode order, final states [lanes] u32),
    bit-identical to the NumPy coder (and, for one lane, to the scalar
    ``rans_encode`` stream stored reversed).

    Single-lane records are the columns of one launch, multi-lane
    records' lanes lie side by side in another: at most two launches,
    whatever the number of records."""
    interpret = interpret_default(interpret)
    for sym, freqs, lanes in records:
        if int(np.max(freqs)) >= 1 << prob_bits:
            raise ValueError("a single-symbol alphabet needs the NumPy "
                             "coder's uint64 lanes")
    out: list = [None] * len(records)
    for single in (True, False):
        members = [j for j, r in enumerate(records)
                   if (r[2] == 1) == single]
        if members:
            coded = _encode_group([records[j] for j in members], single,
                                  prob_bits, interpret)
            for j, result in zip(members, coded):
                out[j] = result
    return out


def rans_encode_interleaved_device(
        symbols: np.ndarray, freqs: np.ndarray, lanes: int,
        prob_bits: int, interpret: Optional[bool] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Device counterpart of ``rans_np.rans_encode_interleaved``: returns
    (words u16 in forward/decode order, final states [lanes] u32),
    bit-identical to the NumPy coder.  A batch of one record."""
    return rans_encode_records_device(
        [(np.asarray(symbols, np.uint8), np.asarray(freqs), int(lanes))],
        prob_bits, interpret)[0]


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
