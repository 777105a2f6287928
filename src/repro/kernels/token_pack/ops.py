"""Public wrapper: batch token packing on device.

`pack_tokens_device(ids)` reproduces LoPace's fixed-width packing decision
(Eq. 7: uint16 iff max(ids) <= 65535) and returns (format_byte, bytes) —
bit-identical to repro.core.packing.pack_fixed, validated in tests.

Every launch is zero-padded on the host to ``size_bucket(n, 2048)`` ids,
so the jitted kernels compile per size bucket (at most eight shapes per
octave per width), not per exact id count; the pad is sliced away.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import interpret_default, size_bucket
from repro.kernels.token_pack.kernel import delta_zigzag_kernel, pack_tokens_kernel

_BLOCK = 2048


def _bucket_buffer(n: int) -> np.ndarray:
    """Zero-filled int32 launch buffer for ``n`` ids, ``size_bucket`` long
    (always a whole number of kernel blocks)."""
    return np.zeros(size_bucket(n, _BLOCK), np.int32)


@partial(jax.jit, static_argnames=("width", "interpret"))
def _pack_padded(ids: jnp.ndarray, width: int, interpret: bool) -> jnp.ndarray:
    n = ids.shape[0]
    pad = (-n) % min(_BLOCK, max(n, 1))
    idsp = jnp.pad(ids, (0, pad))
    return pack_tokens_kernel(idsp.astype(jnp.int32), width=width,
                              block_n=min(_BLOCK, idsp.shape[0]),
                              interpret=interpret)


def pack_tokens_device(ids, interpret: Optional[bool] = None
                       ) -> Tuple[int, bytes]:
    """Returns (format_byte, packed_bytes) per paper Algorithm 1 lines 2-8."""
    ids = np.asarray(ids, dtype=np.uint32)
    if ids.size == 0:
        return 0x00, b""
    width = 2 if int(ids.max()) <= 0xFFFF else 4
    buf = _bucket_buffer(ids.size)
    buf[: ids.size] = ids
    out = _pack_padded(jnp.asarray(buf), width, interpret_default(interpret))
    return (0x00 if width == 2 else 0x01), np.asarray(out)[: ids.size].tobytes()


def pack_fixed_batch_device(ids_list, interpret: Optional[bool] = None
                            ) -> List[bytes]:
    """Batch fixed-width packing: the vectorized device path of the codec layer.

    Streams are grouped by packing width (Eq. 7 decides per stream), each
    group is concatenated into one [N] id vector, zero-padded to its size
    bucket, streamed through the Pallas byte-split kernel in a single
    launch, and the [N, k] byte plane is sliced back per stream.
    Bit-identical to ``repro.core.packing.pack_fixed`` applied per stream
    (format byte included), which the kernel parity tests assert.
    """
    interpret = interpret_default(interpret)
    arrs = [np.asarray(ids, dtype=np.uint32) for ids in ids_list]
    out: List[bytes] = [b""] * len(arrs)
    groups: dict = {2: [], 4: []}
    for i, a in enumerate(arrs):
        if a.size == 0:
            out[i] = bytes([0x00])  # empty stream: u16 header, no body
            continue
        groups[2 if int(a.max()) <= 0xFFFF else 4].append(i)
    for width, members in groups.items():
        if not members:
            continue
        fmt = 0x00 if width == 2 else 0x01
        n = sum(arrs[i].size for i in members)
        buf = _bucket_buffer(n)
        np.concatenate([arrs[i] for i in members], out=buf[:n],
                       casting="unsafe")
        # one launch per width group, read back to the host; its trace
        # event carries the group's real id count and the padded length
        with obs.span("kernel.token_pack",
                      trace_args={"ids": n, "padded": buf.size,
                                  "width": width}):
            plane = np.asarray(
                _pack_padded(jnp.asarray(buf), width, interpret))[:n]
        offsets = np.cumsum([0] + [arrs[i].size for i in members])
        for j, i in enumerate(members):
            out[i] = bytes([fmt]) + plane[offsets[j]:offsets[j + 1]].tobytes()
    return out


def unpack_fixed_device(payload) -> jnp.ndarray:
    """Inverse of the fixed-width packers, landing the ids **on device**:
    a self-describing payload (format byte + LE body) -> uint32 jnp
    array.  Accepts host bytes or a device-resident uint8 array (e.g.
    straight from ``rans_decompress_to_device``), so the serve path's
    decompress-to-tokens never bounces the body through host memory.

    Only the fixed formats (0x00 u16 / 0x01 u32) are byte-combinable on
    device; varint payloads raise and the caller falls back to the host
    unpacker."""
    if isinstance(payload, (bytes, bytearray, memoryview)):
        payload = jnp.asarray(np.frombuffer(payload, np.uint8))
    fmt = int(payload[0])
    body = payload[1:].astype(jnp.uint32)
    if fmt == 0x00:
        return body[0::2] | (body[1::2] << jnp.uint32(8))
    if fmt == 0x01:
        return (body[0::4] | (body[1::4] << jnp.uint32(8))
                | (body[2::4] << jnp.uint32(16))
                | (body[3::4] << jnp.uint32(24)))
    raise ValueError(f"format {fmt:#x} has no device unpacker")


def delta_zigzag_device(ids: jnp.ndarray,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """[N] ids -> [N,4] zigzag-delta bytes (feeder for the rANS stage)."""
    host = np.asarray(ids)
    n = host.shape[0]
    buf = _bucket_buffer(n)
    buf[:n] = host
    return _delta_zigzag(jnp.asarray(buf), interpret_default(interpret))[:n]


@partial(jax.jit, static_argnames=("interpret",))
def _delta_zigzag(ids: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    """``ids``: an int32 launch buffer from ``_bucket_buffer``."""
    prev = jnp.concatenate([jnp.zeros(1, ids.dtype), ids[:-1]])
    return delta_zigzag_kernel(ids, prev, width=4, block_n=_BLOCK,
                               interpret=interpret)
