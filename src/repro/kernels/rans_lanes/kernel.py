"""Pallas TPU kernels: lane-parallel interleaved rANS encode/decode.

The interleaved N-lane coder (``repro.core.rans_np``) was laid out for
exactly this port: N independent 32-bit rANS states advance in lockstep
over a round-robin symbol split, every step is a handful of elementwise
uint32 ops over the N states, and 16-bit renormalization emits **at most
one** word per lane per step — so the data-dependent part of the stream
reduces to a dense [T, lanes] word/mask pair that the host compacts into
the shared word stream (encode) or a prefix-sum word-consumption schedule
(decode).

Both kernels keep the *step* axis sequential (rANS states chain through
every symbol) and vectorize across lanes, mirroring the NumPy lockstep
loop one-to-one so the produced stream is bit-identical.  Lane states
are ``[1, lanes]`` rows; per-step operands are read as rows straight
from the refs (Mosaic has no gather from a loaded value).

* encode walks step blocks in **reverse** grid order (rANS encodes
  back-to-front), carrying the lane states in an output ref whose block
  index_map is constant — the classic Pallas sequential-reduction
  pattern the histogram kernel uses.  Each lane has its own step end,
  so the lanes of many records (each with its own length, lane count
  and partial tail row) encode side by side in one launch;
* decode walks forward, carrying the lane states in the same way and
  the word cursor in SMEM.  Everything that the NumPy coder does with
  a 1-D gather is recast as dense compares and small matmuls, which is
  what the TPU's vector and matrix units execute natively:

  - symbol lookup: ``slot`` is compared against the 256-entry
    exclusive cumulative-frequency column; the count of entries
    ``<= slot`` is the symbol, and the largest such entry / the
    smallest larger one are ``cum[s]`` / ``cum[s + 1]``, so the
    frequency needs no table of its own;
  - word-consumption schedule: the needy lanes' exclusive prefix count
    is one ``[1, L] x [L, L]`` matmul against a strictly-upper 0/1
    matrix (exact: 0/1 operands, f32 accumulation of at most L ones);
  - renorm words: the rows holding ``[cursor, cursor + L)`` are read
    as one dynamic sublane window of the ``[W/128, 128]`` word ref, and
    each lane picks its word with a one-hot matmul over the window's
    columns followed by a row select.  Words are split into their two
    bytes so the bf16 operands are exact.

  Lanes are padded to a multiple of 128 for the matmuls; padded lanes
  never consume a word and their symbols are dropped by the wrapper.

All state arithmetic is uint32: a 32-bit state with 16-bit renorm stays
below 2**32, and ``x_max = f << (32 - prob_bits)`` fits iff every
frequency is below ``2**prob_bits`` — the single-symbol-alphabet edge
(f == 2**prob_bits) is routed to the NumPy uint64 path by the dispatch
layer, never to this kernel.

Step blocks are padded to ``block_t`` multiples; rows at or past a
lane's end (encode) or past ``total_t`` (decode) are masked out of the
state evolution and emit nothing, so padding never touches the stream.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_T = 256   # lockstep steps per grid block
WORD_ROW = 128          # words per row of the decoder's word ref


def _encode_kernel(ends_ref, fc_ref, out_ref, state_ref, *, block_t: int,
                   prob_bits: int):
    i = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        state_ref[...] = jnp.full(state_ref.shape, 1 << 16, jnp.uint32)

    base = (nb - 1 - i) * block_t            # reverse block order
    ends = ends_ref[...]                     # [1, lanes] i32
    shift = jnp.uint32(32 - prob_bits)
    pb = jnp.uint32(prob_bits)
    lo16 = jnp.uint32(0xFFFF)
    sixteen = jnp.uint32(16)

    def row(t, x):                           # x: [1, lanes] u32
        r = block_t - 1 - t                  # reverse rows within the block
        valid = base + r < ends
        fc = fc_ref[pl.ds(r, 1), :]
        f = fc & lo16
        c = fc >> sixteen
        em = (x >= (f << shift)) & valid
        out_ref[pl.ds(r, 1), :] = (x & lo16) | (em.astype(jnp.uint32)
                                                << sixteen)
        x2 = jnp.where(em, x >> sixteen, x)
        xn = ((x2 // f) << pb) + (x2 % f) + c
        return jnp.where(valid, xn, x)

    state_ref[...] = jax.lax.fori_loop(0, block_t, row, state_ref[...])


def rans_encode_lanes_kernel(fc: jnp.ndarray, ends: jnp.ndarray, *,
                             prob_bits: int,
                             block_t: int = DEFAULT_BLOCK_T,
                             interpret: bool = False):
    """fc: [Tp, lanes] u32 per-step ``freq | cumfreq << 16`` (both below
    ``2**prob_bits``, freq >= 1 everywhere, padding included), Tp a
    block_t multiple; ends: [1, lanes] i32, the rows each lane steps
    through (lane ``j`` encodes rows ``ends[j] - 1`` down to 0; 0 for a
    padding lane).

    Returns (out [Tp, lanes] u32: the word a cell may emit in bits 0-15
    and its emit flag in bit 16; states [1, lanes] u32).  Forward stream
    of one lane block = the words of the flagged cells in row-major
    order; rows at or past a lane's end never emit.
    """
    tp, lanes = fc.shape
    if tp % block_t:
        raise ValueError("pad T to a block multiple upstream")
    nb = tp // block_t
    kernel = functools.partial(_encode_kernel, block_t=block_t,
                               prob_bits=prob_bits)
    rows = pl.BlockSpec((block_t, lanes), lambda i, nb=nb: (nb - 1 - i, 0))
    state = pl.BlockSpec((1, lanes), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[state, rows],
        out_specs=[rows, state],
        out_shape=[
            jax.ShapeDtypeStruct((tp, lanes), jnp.uint32),
            jax.ShapeDtypeStruct((1, lanes), jnp.uint32),
        ],
        interpret=interpret,
        name="rans_lanes_encode",
    )(ends, fc)


def decode_window_rows(lanes_padded: int) -> int:
    """Rows of the word window one decode step reads: enough for a
    cursor anywhere in its first row plus ``lanes_padded`` words, in
    whole 8-row tiles."""
    need = lanes_padded // WORD_ROW + 1
    return -(-need // 8) * 8


def _decode_kernel(words_ref, st_ref, cum_ref, sym_ref, state_ref, wcnt_ref,
                   wpos_ref, *, block_t: int, total_t: int, prob_bits: int,
                   lanes: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        state_ref[...] = st_ref[...]
        wpos_ref[0] = 0

    lp = st_ref.shape[1]
    win_rows = decode_window_rows(lp)
    max_row = words_ref.shape[0] - win_rows
    total = 1 << prob_bits
    slot_mask = jnp.uint32(total - 1)
    pb = jnp.uint32(prob_bits)
    low = jnp.uint32(1 << 16)
    sixteen = jnp.uint32(16)
    base = i * block_t
    cum = cum_ref[...]                                   # [256, 1] i32
    live = jax.lax.broadcasted_iota(jnp.int32, (1, lp), 1) < lanes
    upper = (jax.lax.broadcasted_iota(jnp.int32, (lp, lp), 0)
             < jax.lax.broadcasted_iota(jnp.int32, (lp, lp), 1)
             ).astype(jnp.bfloat16)                     # strict upper 0/1
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (WORD_ROW, lp), 0)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (win_rows, lp), 0)

    def row(r, x):                                       # x: [1, lp] u32
        valid = base + r < total_t
        slot = (x & slot_mask).astype(jnp.int32)
        le = cum <= slot                                 # [256, lp]
        s = jnp.sum(le.astype(jnp.int32), axis=0, keepdims=True) - 1
        c_lo = jnp.max(jnp.where(le, cum, 0), axis=0, keepdims=True)
        c_hi = jnp.min(jnp.where(le, total, cum), axis=0, keepdims=True)
        sym_ref[pl.ds(r, 1), :] = s
        xn = ((c_hi - c_lo).astype(jnp.uint32) * (x >> pb)
              + (slot - c_lo).astype(jnp.uint32))
        need = (xn < low) & live & valid
        excl = jnp.dot(need.astype(jnp.bfloat16), upper,
                       preferred_element_type=jnp.float32).astype(jnp.int32)
        wpos = wpos_ref[0]
        r0 = jnp.minimum(wpos // WORD_ROW, max_row)
        idx = wpos - r0 * WORD_ROW + excl                # [1, lp] window pos
        win = words_ref[pl.ds(r0, win_rows), :].astype(jnp.int32)
        onehot = (col_ids == (idx & (WORD_ROW - 1))).astype(jnp.bfloat16)
        lo = jnp.dot((win & 0xFF).astype(jnp.float32).astype(jnp.bfloat16),
                     onehot, preferred_element_type=jnp.float32)
        hi = jnp.dot((win >> 8).astype(jnp.float32).astype(jnp.bfloat16),
                     onehot, preferred_element_type=jnp.float32)
        pick = row_ids == (idx // WORD_ROW)              # [win_rows, lp]
        w = jnp.sum(jnp.where(pick, lo + 256.0 * hi, 0.0), axis=0,
                    keepdims=True).astype(jnp.int32).astype(jnp.uint32)
        wpos_ref[0] = wpos + jnp.sum(need.astype(jnp.int32))
        xn = jnp.where(need, (xn << sixteen) | w, xn)
        return jnp.where(valid, xn, x)

    state_ref[...] = jax.lax.fori_loop(0, block_t, row, state_ref[...])
    wcnt_ref[0] = wpos_ref[0]


def rans_decode_lanes_kernel(words: jnp.ndarray, states: jnp.ndarray,
                             cum: jnp.ndarray, *, total_t: int,
                             prob_bits: int, lanes: int,
                             block_t: int = DEFAULT_BLOCK_T,
                             interpret: bool = False):
    """words: [Wr, 128] u32 forward stream (zero-padded, at least
    ``decode_window_rows`` rows past the last word), states: [1, Lp] u32
    (Lp a multiple of 128; lanes >= ``lanes`` are padding), cum: [256, 1]
    i32 exclusive cumulative frequencies.

    Returns (symbols [Tp, Lp] i32 — row-major flatten of the first
    ``lanes`` columns IS the round-robin interleave order, states [1, Lp]
    u32 after the full steps, words_consumed [1] i32).  The host runs the
    partial tail step (slot lookup only, no renorm) on the returned
    states.
    """
    tp = -(-total_t // block_t) * block_t if total_t else block_t
    lp = states.shape[1]
    if lp % WORD_ROW:
        raise ValueError("pad lanes to a multiple of 128 upstream")
    if words.shape[0] < decode_window_rows(lp):
        raise ValueError("pad the word rows past the decode window upstream")
    nb = tp // block_t
    kernel = functools.partial(_decode_kernel, block_t=block_t,
                               total_t=total_t, prob_bits=prob_bits,
                               lanes=lanes)
    state = pl.BlockSpec((1, lp), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(words.shape, lambda i: (0, 0)),
            state,
            pl.BlockSpec((256, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, lp), lambda i: (i, 0)),
            state,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, lp), jnp.int32),
            jax.ShapeDtypeStruct((1, lp), jnp.uint32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(words, states, cum)
