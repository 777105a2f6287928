"""Shannon-entropy accounting (paper §3.6): theoretical limits and the
compression-efficiency metric η = CR_actual / CR_theoretical — plus the
byte-histogram primitive the rANS frequency tables are built from.

``byte_histogram`` is the one entry point: vectorized ``np.bincount`` on
CPU hosts, the Pallas one-hot-matmul histogram kernel
(``repro.kernels.histogram``) when a non-CPU backend is attached — the
same auto-routing convention the token-pack stage uses.  The rANS coders
(``repro.core.rans_np`` / ``repro.core.rans``) and the bytes fast path of
``shannon_entropy`` all feed from it, so frequency counting is vectorized
everywhere on the codec hot path.
"""

from __future__ import annotations

import math
from collections import Counter
from typing import Optional, Union

import numpy as np

Data = Union[str, bytes]

# device-histogram crossover (bytes): even with an accelerator attached,
# small payloads pay more in upload + dispatch than the one-hot matmul
# saves.  An estimate, not yet measured on a chip (the sweep is
# benchmarks/kernel_throughput.py); override with REPRO_HIST_DEVICE_MIN
_DEVICE_MIN_BYTES = 1 << 15


def byte_histogram(data, use_device: Optional[bool] = None) -> np.ndarray:
    """256-bucket histogram of a byte payload (bytes or uint8 ndarray).

    ``use_device=None`` auto-routes through the shared policy in
    ``repro.core.device``: the Pallas histogram kernel only when a
    non-CPU backend is attached *and* the payload clears the
    ``REPRO_HIST_DEVICE_MIN`` crossover; ``np.bincount`` otherwise.
    Both paths are exact (kernel parity is asserted in
    tests/test_kernels.py)."""
    arr = (np.frombuffer(data, np.uint8)
           if isinstance(data, (bytes, bytearray, memoryview))
           else np.asarray(data, np.uint8))
    from repro.core import device as _device

    if _device.use_device(arr.size, "REPRO_HIST_DEVICE_MIN",
                          _DEVICE_MIN_BYTES, force=use_device) and arr.size:
        from repro.kernels.histogram import byte_histogram_device

        return byte_histogram_device(arr)
    return np.bincount(arr, minlength=256).astype(np.int64)


def shannon_entropy(data: Data) -> float:
    """H(X) in bits/symbol over character (str) or byte (bytes) frequencies
    (Eq. 23).  Bytes take the vectorized histogram path."""
    if len(data) == 0:
        return 0.0
    if isinstance(data, (bytes, bytearray, memoryview)):
        counts = byte_histogram(data)
        p = counts[counts > 0] / float(len(data))
        return float(-(p * np.log2(p)).sum())
    counts = Counter(data)
    n = len(data)
    return -sum((c / n) * math.log2(c / n) for c in counts.values())


def theoretical_min_bytes(data: Data) -> float:
    """S_min = H(X) * |T| / 8 (Eq. 24)."""
    return shannon_entropy(data) * len(data) / 8.0


def theoretical_cr(data: Data) -> float:
    """CR_theoretical = 8 / H(X) (Eq. 25). Infinite for constant input."""
    h = shannon_entropy(data)
    return math.inf if h == 0.0 else 8.0 / h


def efficiency(data: Data, compressed_size: int) -> float:
    """η (Eq. 26). NOTE: an LZ coder exploits *sequence* structure that an
    order-0 character model cannot see, so η > 1 is possible and expected
    for repetitive text; the paper's 60–80 % band refers to low-redundancy
    content."""
    if compressed_size <= 0:
        raise ValueError("compressed_size must be positive")
    cr_actual = len(data) if isinstance(data, bytes) else len(data.encode("utf-8"))
    cr_actual = cr_actual / compressed_size
    cr_theory = theoretical_cr(data)
    return 0.0 if math.isinf(cr_theory) else cr_actual / cr_theory


def bits_per_char(text: str, compressed_size: int) -> float:
    """BPC (Eq. 33)."""
    if len(text) == 0:
        return 0.0
    return compressed_size * 8.0 / len(text)
