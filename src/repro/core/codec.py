"""Layered codec pipelines: the paper's three methods as composable stages.

The paper's hybrid method (§3.4, Algorithm 1) *is* a two-stage pipeline —
pack the token ids, then byte-compress the packed stream — and CompactPrompt
/ LLMLingua-style systems generalize exactly this shape: a chain of
bijective stages, each mapping a batch of byte payloads to a batch of byte
payloads.  This module makes that structure explicit:

    Codec              protocol: encode_batch / decode_batch over payloads
    TokenPackCodec     text bytes  <-> packed token ids (τ then P)
    ByteCompressorCodec payload    <-> C_backend(payload)  (any BACKENDS entry)
    PipelineCodec      ordered stage composition (decode runs in reverse)

and re-expresses the paper's methods as pipelines:

    zstd   = [ByteCompressorCodec]
    token  = [TokenPackCodec]
    hybrid = [TokenPackCodec, ByteCompressorCodec]

Byte-exactness contract: for every method, the pipeline's single-element
encode output is bit-identical to the paper-exact functions in
``repro.core.api`` (``compress_zstd`` / ``compress_token`` /
``compress_hybrid``), and batched encode is bit-identical to sequential
encode.  Both identities are asserted by tests/test_codec.py, so benchmark
byte sizes are unchanged by this layering.

Device routing: the fixed-width pack stage is pure byte movement, so on an
accelerator the batch path concatenates streams and runs the Pallas
byte-split kernel in one launch per width group
(``repro.kernels.token_pack.pack_fixed_batch_device``); on CPU hosts the
pure-NumPy ``packing.pack_fixed`` path is used per stream.  The
``repro-lzr`` byte stage runs LZ77 per record on the codec pool and then
the batch's rANS stage as one unit (``rans_np.rans_compress_batch``):
on an accelerator, and when the batch's LZ77 output clears the rANS
crossover, every record's lanes are encoded in one launch per lane
layout.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Protocol, Sequence, runtime_checkable

from repro.core import env
from repro import obs

import numpy as np

from repro.core import packing
from repro.core.lz77 import lz_compress
from repro.core.rans_np import rans_compress_batch
from repro.core.zstd_backend import (BACKENDS, DEFAULT_LEVEL, DICT_BACKENDS,
                                     compress_bytes, compress_bytes_dict,
                                     decompress_bytes, decompress_bytes_dict)
from repro.tokenizer.bpe import BPETokenizer

# ---------------------------------------------------------------------------
# Shared codec thread pool
# ---------------------------------------------------------------------------
#
# One process-wide pool fans per-record byte compression out across cores:
# `PromptCompressor.compress_batch`, `ShardedPromptStore.plan_batch` and the
# ingest dispatcher all reach it through the byte-stage codecs below, so a
# group commit's latency is bounded by its slowest record, not the sum.
# The win is real where the leaf releases the GIL (zlib/bz2/lzma and the
# zstd C library do; the from-scratch backends only during their NumPy
# spans — see ARCHITECTURE.md "Vectorized codec path" for measurements).
# Sizing: REPRO_CODEC_THREADS always wins (0/1 disables); the default is
# min(4, cpu_count) on hosts with >2 CPUs and DISABLED on <=2-CPU boxes,
# where measurement shows even the GIL-releasing C codecs lose to the
# handoff+contention cost (2 vCPUs are typically hyperthread siblings).
# Leaf tasks never submit back into the pool, so a bounded worker count
# cannot deadlock.

_POOL: Optional[ThreadPoolExecutor] = None
_POOL_SIZE = 0
_POOL_LOCK = threading.Lock()
_PAR_MIN_BATCH = 4          # payloads per batch before the pool pays off
_PAR_MIN_BYTES = 1 << 16    # total bytes before the pool pays off


def codec_pool_size() -> int:
    size = env.read("REPRO_CODEC_THREADS")
    if size is not None:
        return size
    cpus = os.cpu_count() or 1
    return min(4, cpus) if cpus > 2 else 0


def _codec_pool() -> Optional[ThreadPoolExecutor]:
    global _POOL, _POOL_SIZE
    size = codec_pool_size()
    if size <= 1:
        return None
    with _POOL_LOCK:
        if _POOL is None or _POOL_SIZE != size:
            if _POOL is not None:
                _POOL.shutdown(wait=False)
            _POOL = ThreadPoolExecutor(max_workers=size,
                                       thread_name_prefix="codec")
            _POOL_SIZE = size
        return _POOL


def _parallel_map(fn: Callable[[bytes], bytes],
                  payloads: Sequence[bytes]) -> List[bytes]:
    """Order-preserving map over payloads, fanned across the shared pool
    when the batch is big enough to amortize the handoff."""
    if (len(payloads) >= _PAR_MIN_BATCH
            and sum(map(len, payloads)) >= _PAR_MIN_BYTES):
        pool = _codec_pool()
        if pool is not None:
            return list(pool.map(fn, payloads))
    return [fn(p) for p in payloads]


# ---------------------------------------------------------------------------
# Codec observability
# ---------------------------------------------------------------------------
#
# Every stage/pipeline owns a `_CodecObs` created once in __init__ —
# the REPRO_OBS gate is resolved there, so the per-batch cost with obs
# disabled is one perf_counter read and one no-op method call (byte
# totals are only summed by the enabled twin; the token-pack stage's two
# sub-spans read the clock only).  Pipelines additionally
# export the paper's Table metrics as derived gauges: live compression
# ratio and encode/decode MB/s (MB = 10**6 bytes, the paper's unit) per
# method, computed from the running byte/second totals at snapshot time.


class _CodecObs:
    __slots__ = ("enc_s", "dec_s", "enc_in", "enc_out", "dec_in", "dec_out")

    def __init__(self, **labels) -> None:
        self.enc_s = obs.histogram("codec.encode.s", **labels)
        self.dec_s = obs.histogram("codec.decode.s", **labels)
        self.enc_in = obs.counter("codec.encode.bytes_in", **labels)
        self.enc_out = obs.counter("codec.encode.bytes_out", **labels)
        self.dec_in = obs.counter("codec.decode.bytes_in", **labels)
        self.dec_out = obs.counter("codec.decode.bytes_out", **labels)

    def encode(self, dt: float, payloads: Sequence[bytes],
               out: Sequence[bytes]) -> None:
        self.enc_s.observe(dt)
        self.enc_in.inc(sum(map(len, payloads)))
        self.enc_out.inc(sum(map(len, out)))

    def decode(self, dt: float, payloads: Sequence[bytes],
               out: Sequence[bytes]) -> None:
        self.dec_s.observe(dt)
        self.dec_in.inc(sum(map(len, payloads)))
        self.dec_out.inc(sum(map(len, out)))


class _NullCodecObs:
    __slots__ = ()

    def encode(self, dt, payloads, out) -> None:
        pass

    def decode(self, dt, payloads, out) -> None:
        pass


_NULL_CODEC_OBS = _NullCodecObs()


def _codec_obs(**labels):
    return _CodecObs(**labels) if obs.enabled() else _NULL_CODEC_OBS


def _pipeline_obs(method: str):
    """Method-level obs plus the derived ratio/throughput gauges."""
    o = _codec_obs(method=method)
    if isinstance(o, _CodecObs):
        obs.derived_gauge(
            "codec.compression_ratio",
            lambda: o.enc_in.value / o.enc_out.value, method=method)
        obs.derived_gauge(
            "codec.encode_mb_s",
            lambda: (o.enc_in.value / 1e6) / o.enc_s.sum, method=method)
        obs.derived_gauge(
            "codec.decode_mb_s",
            lambda: (o.dec_out.value / 1e6) / o.dec_s.sum, method=method)
    return o


@runtime_checkable
class Codec(Protocol):
    """A bijective batch transform over byte payloads."""

    name: str

    def encode_batch(self, payloads: Sequence[bytes]) -> List[bytes]: ...

    def decode_batch(self, payloads: Sequence[bytes]) -> List[bytes]: ...


# ---------------------------------------------------------------------------
# Stage codecs
# ---------------------------------------------------------------------------


# device-packing crossover (total ids across the batch): one kernel
# launch per width group still has to beat per-stream NumPy casts.  An
# estimate, not a chip measurement; override with REPRO_PACK_DEVICE_MIN
_PACK_DEVICE_MIN_IDS = 1 << 14


class TokenPackCodec:
    """τ then P: UTF-8 text bytes <-> self-describing packed token stream.

    ``use_device=None`` auto-routes: Pallas kernel batch path on
    accelerators, per-stream NumPy on CPU.  Both paths are bit-identical
    (kernel parity tests in tests/test_kernels.py).
    """

    name = "token-pack"

    def __init__(self, tokenizer: BPETokenizer, scheme: str = "fixed",
                 use_device: Optional[bool] = None) -> None:
        if tokenizer is None:
            raise ValueError("TokenPackCodec requires a tokenizer")
        if scheme not in packing.PACKERS:
            raise ValueError(f"unknown packing scheme {scheme!r}")
        self.tokenizer = tokenizer
        self.scheme = scheme
        self.use_device = use_device
        self._obs = _codec_obs(stage=self.name, scheme=scheme)

    # -- token-level entry points (used by the token-stream storage mode) --

    def encode_ids_batch(self, ids_list: Sequence[np.ndarray]) -> List[bytes]:
        if self.scheme == "fixed":
            from repro.core import device as _device

            total = sum(np.asarray(ids).size for ids in ids_list)
            if _device.use_device(total, "REPRO_PACK_DEVICE_MIN",
                                  _PACK_DEVICE_MIN_IDS,
                                  force=self.use_device):
                from repro.kernels.token_pack import pack_fixed_batch_device

                return pack_fixed_batch_device(ids_list)
        return [packing.pack_tokens(ids, self.scheme) for ids in ids_list]

    def decode_ids_batch(self, payloads: Sequence[bytes],
                         to_device: bool = False) -> List[np.ndarray]:
        """Packed payloads -> token-id arrays.  ``to_device=True`` lands
        each array in device memory (jnp uint32) instead of host NumPy —
        the serve path's decompress-to-tokens feeds model input staging
        without a host round trip.  Fixed-width payloads byte-combine on
        device; varint formats decode on host and upload."""
        if to_device:
            import jax.numpy as jnp

            from repro.kernels.token_pack import unpack_fixed_device

            out = []
            for p in payloads:
                fmt = p[0] if len(p) else packing.FMT_U16
                if fmt in packing._FIXED:
                    out.append(unpack_fixed_device(p))
                else:
                    out.append(jnp.asarray(packing.unpack_tokens(p)))
            return out
        return [packing.unpack_tokens(p) for p in payloads]

    # -- Codec protocol ----------------------------------------------------

    def encode_batch(self, payloads: Sequence[bytes]) -> List[bytes]:
        t0 = time.perf_counter()
        with obs.span("codec.bpe.encode"):
            ids_list = self.tokenizer.encode_batch(
                [p.decode("utf-8") for p in payloads])
        with obs.span("codec.pack.encode"):
            out = self.encode_ids_batch(
                [np.asarray(ids, np.uint32) for ids in ids_list])
        self._obs.encode(time.perf_counter() - t0, payloads, out)
        return out

    def decode_batch(self, payloads: Sequence[bytes]) -> List[bytes]:
        t0 = time.perf_counter()
        out = [self.tokenizer.decode_bytes(ids) for ids in self.decode_ids_batch(payloads)]
        self._obs.decode(time.perf_counter() - t0, payloads, out)
        return out


class ByteCompressorCodec:
    """C_backend stage over any registered byte backend (zstd by default)."""

    name = "byte-compressor"

    def __init__(self, level: int = DEFAULT_LEVEL, backend: str = "zstd") -> None:
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; have {sorted(BACKENDS)}")
        self.level = level
        self.backend = backend
        self._obs = _codec_obs(stage=self.name, backend=backend)

    def encode_batch(self, payloads: Sequence[bytes]) -> List[bytes]:
        t0 = time.perf_counter()
        if self.backend == "repro-lzr":
            # LZ77 per record on the pool, then the batch's rANS stage as
            # one unit (one device launch per lane layout when routed to
            # the chip); the same bytes as compress_bytes per record.
            # repro-analysis: disable=REPRO006 lz_compress is the leaf compress_bytes ran here before; the analyzer's path reaches decode_batch only through a dict's .get in the scalar LZ77 loop, matched by name
            out = rans_compress_batch(_parallel_map(lz_compress, payloads))
        else:
            out = _parallel_map(
                lambda p: compress_bytes(p, level=self.level,
                                         backend=self.backend),
                payloads)
        self._obs.encode(time.perf_counter() - t0, payloads, out)
        return out

    def decode_batch(self, payloads: Sequence[bytes]) -> List[bytes]:
        t0 = time.perf_counter()
        out = _parallel_map(
            lambda p: decompress_bytes(p, backend=self.backend), payloads)
        self._obs.decode(time.perf_counter() - t0, payloads, out)
        return out


class DictCodec:
    """Dictionary-seeded byte-compressor stage (paper §8.4.2 #2).

    Same position in a pipeline as :class:`ByteCompressorCodec`, but the
    backend is primed with a trained dictionary, recovering cross-record
    redundancy that per-record compression cannot see.  Encode and decode
    must hold the identical dictionary bytes — the frame layer
    (``repro.core.api``) threads a fingerprint through v2 frame headers
    and the store persists the blob as a per-shard-generation sidecar.
    """

    name = "dict-compressor"

    def __init__(self, dictionary: bytes, level: int = DEFAULT_LEVEL,
                 backend: str = "zstd") -> None:
        if backend not in DICT_BACKENDS:
            raise ValueError(
                f"backend {backend!r} has no dictionary mode; "
                f"have {sorted(DICT_BACKENDS)}")
        if not dictionary:
            raise ValueError("DictCodec requires a non-empty dictionary")
        self.dictionary = bytes(dictionary)
        self.level = level
        self.backend = backend
        self._obs = _codec_obs(stage=self.name, backend=backend)

    def encode_batch(self, payloads: Sequence[bytes]) -> List[bytes]:
        t0 = time.perf_counter()
        out = _parallel_map(
            lambda p: compress_bytes_dict(p, self.dictionary, level=self.level,
                                          backend=self.backend), payloads)
        self._obs.encode(time.perf_counter() - t0, payloads, out)
        return out

    def decode_batch(self, payloads: Sequence[bytes]) -> List[bytes]:
        t0 = time.perf_counter()
        out = _parallel_map(
            lambda p: decompress_bytes_dict(p, self.dictionary,
                                            backend=self.backend), payloads)
        self._obs.decode(time.perf_counter() - t0, payloads, out)
        return out


class PipelineCodec:
    """Ordered composition of stages; decode applies the inverses in reverse."""

    def __init__(self, stages: Sequence[Codec], name: str = "pipeline") -> None:
        if not stages:
            raise ValueError("pipeline needs at least one stage")
        self.stages = list(stages)
        self.name = name
        self._obs = _pipeline_obs(name)

    def encode_batch(self, payloads: Sequence[bytes]) -> List[bytes]:
        t0 = time.perf_counter()
        out = list(payloads)
        for stage in self.stages:
            out = stage.encode_batch(out)
        self._obs.encode(time.perf_counter() - t0, payloads, out)
        return out

    def decode_batch(self, payloads: Sequence[bytes]) -> List[bytes]:
        t0 = time.perf_counter()
        out = list(payloads)
        for stage in reversed(self.stages):
            out = stage.decode_batch(out)
        self._obs.decode(time.perf_counter() - t0, payloads, out)
        return out


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

CODEC_REGISTRY: Dict[str, Callable[..., Codec]] = {}


def register_codec(name: str, factory: Callable[..., Codec]) -> None:
    if name in CODEC_REGISTRY:
        raise ValueError(f"codec {name!r} already registered")
    CODEC_REGISTRY[name] = factory


def get_codec(name: str, **kwargs) -> Codec:
    try:
        factory = CODEC_REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown codec {name!r}; have {sorted(CODEC_REGISTRY)}") from None
    return factory(**kwargs)


register_codec("token-pack", TokenPackCodec)
register_codec("byte-compressor", ByteCompressorCodec)
register_codec("dict-compressor", DictCodec)


def method_pipeline(
    method: str,
    tokenizer: Optional[BPETokenizer] = None,
    level: int = DEFAULT_LEVEL,
    backend: str = "zstd",
    scheme: str = "fixed",
    use_device: Optional[bool] = None,
    dictionary: Optional[bytes] = None,
) -> PipelineCodec:
    """The paper's three methods as stage pipelines (§3.2-§3.4).

    With ``dictionary``, the byte-compressor stage is swapped for a
    :class:`DictCodec` primed with it; ``token`` has no byte stage, so a
    dictionary there is an error."""
    if dictionary:
        byte_stage: Codec = DictCodec(dictionary, level, backend)
    else:
        byte_stage = ByteCompressorCodec(level, backend)
    if method == "zstd":
        stages: List[Codec] = [byte_stage]
    elif method == "token":
        if dictionary:
            raise ValueError(
                "method 'token' has no byte-compressor stage to apply a "
                "dictionary to")
        stages = [TokenPackCodec(tokenizer, scheme, use_device)]
    elif method == "hybrid":
        stages = [TokenPackCodec(tokenizer, scheme, use_device), byte_stage]
    else:
        raise ValueError(f"unknown method {method!r}")
    return PipelineCodec(stages, name=method)
