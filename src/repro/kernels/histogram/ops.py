"""Public wrapper: device histogram feeding rANS table normalization."""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default, size_bucket
from repro.kernels.histogram.kernel import histogram_kernel

_HIST_PAD_MIN = 1024   # a kernel block (DEFAULT_BLOCK_N)


def token_histogram(ids: jnp.ndarray, vocab_size: int,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """ids: [N] any int dtype -> counts [vocab_size] int32.
    Pads N and vocab to kernel block multiples (pad ids are -1 = no bucket)."""
    return _token_histogram(ids, vocab_size, interpret_default(interpret))


@partial(jax.jit, static_argnames=("vocab_size", "interpret"))
def _token_histogram(ids: jnp.ndarray, vocab_size: int,
                     interpret: bool) -> jnp.ndarray:
    n = ids.shape[0]
    block_n = min(1024, max(n, 8))
    pad_n = (-n) % block_n
    idsp = jnp.pad(ids.astype(jnp.int32), (0, pad_n), constant_values=-1)
    block_v = min(2048, vocab_size)
    pad_v = (-vocab_size) % block_v
    out = histogram_kernel(idsp, vocab_size + pad_v, block_n=block_n,
                           block_v=block_v, interpret=interpret)
    return out[:vocab_size]


def byte_histogram_device(data, interpret: Optional[bool] = None):
    """256-bucket byte histogram on the accelerator — the rANS frequency
    table builder for device-resident entropy coding.  Accepts bytes or a
    uint8 ndarray; returns numpy int64 counts [256] (the shape
    ``normalize_freqs`` consumes)."""
    import numpy as np

    arr = np.frombuffer(data, np.uint8) if isinstance(data, (bytes, bytearray, memoryview)) \
        else np.asarray(data, np.uint8)
    if arr.size == 0:
        return np.zeros(256, np.int64)
    # pad to a size bucket with -1 (no bucket) so payload lengths share
    # compilations
    ids = np.full(size_bucket(arr.size, _HIST_PAD_MIN), -1, np.int32)
    ids[:arr.size] = arr
    counts = token_histogram(jnp.asarray(ids), 256, interpret=interpret)
    return np.asarray(counts, dtype=np.int64)
