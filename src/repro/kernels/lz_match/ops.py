"""Public wrapper: LZ77 match-candidate stage on device.

``lz_candidates_device(buf, plen)`` produces the exact candidate
contract of ``repro.core.lz77._candidates_np`` — ``(ok, cand, mlen)``
over the ``len(buf) - 3`` positions holding a full 4-gram — so the
host-side greedy selection + sequence emit (``_select_emit``, shared
with the NumPy path) turns it into a byte-identical compressed stream.

Stage layout inside the one jitted function:

* gram/hash build — Pallas elementwise kernel over four shifted byte
  planes;
* head-table candidate scatter — ``lax.fori_loop`` over
  ``_SCAN_BLOCK``-byte blocks with an XLA ``scatter-max``: each block
  reads candidates *before* writing its own positions (a position never
  proposes itself), and since positions only grow, scatter-max over the
  block history equals the NumPy path's last-write-wins overwrite;
* short-period run candidates (periods 1-4) as shifted compares;
* dense batched 8-gram XOR extension — Pallas kernel over
  ``_EXT_ROUNDS`` gram planes gathered from the same u32 array
  (``v[g], v[g+4]``).

Equivalence note: the NumPy path marks some positions *lazy* (negative
``mlen``) that the dense device extension resolves exactly — its
run-dominance early-break keeps survivor sets dynamic, which a fixed
device schedule has no reason to copy.  That is output-invariant: lazy
markers resolve to the same exact length at selection time, so the
device lazy set being a subset of the NumPy lazy set still yields
identical bytes.  ``ok`` and ``cand`` match the NumPy stage exactly.

Payload bytes are padded to 1/8-octave size buckets (min 16 KiB) so
recompiles stay logarithmic in payload size; padded positions are
masked out of ``ok`` and scattered only after every real read in their
block.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_default, size_bucket
from repro.kernels.lz_match.kernel import (gram_hash_kernel,
                                           match_extend_kernel)

_MIN_MATCH = 4
_WINDOW = 0xFFFF
_HASH_BITS = 20
_SCAN_BLOCK = 1024
_EXT_ROUNDS = 3
_PAD_MIN = 16384   # must be a multiple of _SCAN_BLOCK and the kernel block


@partial(jax.jit, static_argnames=("p", "interpret"))
def _candidate_stage(b: jnp.ndarray, n: jnp.ndarray, plen: jnp.ndarray,
                     p: int, interpret: bool):
    zero = jnp.zeros(3, jnp.uint8)
    b1 = jnp.concatenate([b[1:], zero[:1]])
    b2 = jnp.concatenate([b[2:], zero[:2]])
    b3 = jnp.concatenate([b[3:], zero[:3]])
    v, h = gram_hash_kernel(b, b1, b2, b3, hash_bits=_HASH_BITS,
                            interpret=interpret)
    idx = jnp.arange(p, dtype=jnp.int32)
    nv = (n - 3).astype(jnp.int32)

    # head-table scatter, block by block (reads before writes per block;
    # positions past nv land in trailing blocks, after every real read)
    def blk(k, carry):
        head, cand = carry
        a = k * _SCAN_BLOCK
        hb = jax.lax.dynamic_slice(h, (a,), (_SCAN_BLOCK,))
        ib = a + jnp.arange(_SCAN_BLOCK, dtype=jnp.int32)
        cand = jax.lax.dynamic_update_slice(cand, head[hb], (a,))
        return head.at[hb].max(ib), cand

    head0 = jnp.full(1 << _HASH_BITS, -1, jnp.int32)
    _, cand = jax.lax.fori_loop(0, p // _SCAN_BLOCK, blk,
                                (head0, jnp.zeros(p, jnp.int32)))

    # short-period runs are invisible to the block scatter — catch them
    # directly; d=4 covers periods 1/2/4, then d=3 (nearer candidates
    # overwrite, matching the NumPy application order)
    for d in (4, 3):
        vs = jnp.concatenate([jnp.zeros(d, jnp.uint32), v[:-d]])
        eq = (v == vs) & (idx >= d)
        cand = jnp.where(eq, idx - d, cand)

    ok = ((cand >= 0) & (idx - cand <= _WINDOW)
          & (v[jnp.maximum(cand, 0)] == v)
          & (idx >= plen.astype(jnp.int32)) & (idx < nv))

    # dense 8-gram XOR extension planes: round r compares the grams at
    # l = MIN_MATCH + 8r via two u32 halves gathered from v
    n8 = (n - 7).astype(jnp.int32)
    dlo, dhi, inb = [], [], []
    top = p - 1
    for r in range(_EXT_ROUNDS):
        l = _MIN_MATCH + 8 * r
        g = idx + l
        gc = cand + l
        dlo.append(v[jnp.clip(g, 0, top)] ^ v[jnp.clip(gc, 0, top)])
        dhi.append(v[jnp.clip(g + 4, 0, top)] ^ v[jnp.clip(gc + 4, 0, top)])
        inb.append((g < n8).astype(jnp.int32))
    mlen = match_extend_kernel(
        jnp.stack(dlo), jnp.stack(dhi), jnp.stack(inb),
        ok.astype(jnp.int32), min_match=_MIN_MATCH, interpret=interpret)
    return ok, cand, mlen


def lz_candidates_device(
        buf: bytes, plen: int, interpret: Optional[bool] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Device counterpart of ``lz77._candidates_np``: (ok bool[nv],
    cand intp[nv], mlen int64[nv]) for the full window+payload buffer."""
    interpret = interpret_default(interpret)
    n = len(buf)
    nv = n - 3
    if nv <= 0:
        return (np.zeros(max(nv, 0), bool), np.zeros(max(nv, 0), np.intp),
                np.zeros(max(nv, 0), np.int64))
    p = size_bucket(n, _PAD_MIN)
    padded = np.zeros(p, np.uint8)
    padded[:n] = np.frombuffer(buf, np.uint8)
    ok, cand, mlen = _candidate_stage(
        jnp.asarray(padded), jnp.int32(n), jnp.int32(plen), p, interpret)
    return (np.asarray(ok[:nv]), np.asarray(cand[:nv]).astype(np.intp),
            np.asarray(mlen[:nv]).astype(np.int64))
