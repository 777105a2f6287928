"""Pallas TPU kernels (validated on CPU via interpret=True against the
pure-jnp oracles in each kernel's ref.py):

  flash_attention/  blockwise online-softmax attention (causal/GQA/window/
                    softcap) — the perf-critical layer of every arch
  token_pack/       LoPace fixed-width + delta-zigzag byte packing
  histogram/        token-frequency one-hot-matmul reduction (rANS tables)
  lz_match/         LZ77 gram hashing + match extension
  rans_lanes/       lane-parallel interleaved rANS encode/decode
"""

from __future__ import annotations

from typing import Optional


def interpret_default(interpret: Optional[bool] = None) -> bool:
    """The ``interpret`` flag every public kernel wrapper passes to
    ``pallas_call``: an explicit value wins; ``None`` means compiled,
    unless JAX's backend is the CPU (which has no Pallas compiler)."""
    if interpret is None:
        import jax

        return jax.default_backend() == "cpu"
    return bool(interpret)


def size_bucket(n: int, floor: int) -> int:
    """Padded length for an ``n``-element kernel input: the next multiple
    of an eighth of the enclosing power of two, at least ``floor``.  Pad
    waste stays under 12.5% and the distinct shapes, hence compilations,
    under eight per octave.  ``floor`` must be a power of two."""
    q = max(floor, 1 << max(int(n).bit_length() - 3, 0))
    return max(-(-n // q) * q, floor)
