"""The single registry for ``REPRO_*`` environment knobs.

Every environment variable the runtime reads is *declared* here with a
typed parser, a default, and a one-line description — and every read
goes through :func:`read`, which looks the variable up fresh on each
call (benchmarks and tests re-tune without reimporting).  The static
invariant checker (``repro.analysis`` rule REPRO005) enforces the other
half of the contract: no module outside this one may touch
``os.environ`` for a ``REPRO_*`` name, so the table below is always the
complete inventory of runtime knobs.

Parser semantics are part of each knob's contract (several predate this
registry and keep their historical fallback behavior exactly):

* a parser may *raise* ``ValueError`` — :func:`read` then falls back to
  the default silently (the device-crossover knobs work this way);
* a parser may *absorb* garbage itself when the historical behavior was
  not "fall back to default" — ``REPRO_CODEC_THREADS`` maps garbage to
  0 (pool disabled), ``REPRO_RANS_LANES`` warns and clamps.

Unset or empty values never reach a parser; they yield the default
(the per-call ``default=`` override wins over the declared one, which
is how call sites keep ownership of measured tuning constants).
"""

from __future__ import annotations

import os
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


@dataclass(frozen=True)
class EnvVar:
    """One declared knob: its parser, declared default, and doc line."""

    name: str
    parse: Callable[[str], Any]
    default: Any
    help: str


_REGISTRY: Dict[str, EnvVar] = {}


def declare(name: str, parse: Callable[[str], Any], default: Any,
            help: str) -> EnvVar:
    if not name.startswith("REPRO_"):
        raise ValueError(f"env registry only holds REPRO_* names, got {name!r}")
    if name in _REGISTRY:
        raise ValueError(f"env var {name!r} already declared")
    var = EnvVar(name, parse, default, help)
    _REGISTRY[name] = var
    return var


_UNSET = object()


def read(name: str, default: Any = _UNSET) -> Any:
    """Parsed value of `name` (declared names only; raises RuntimeError
    for undeclared ones — the point of the registry is that there is no
    ad-hoc read path).  ``default=`` overrides the declared default for
    knobs whose fallback is a call-site measurement."""
    spec = _REGISTRY.get(name)
    if spec is None:
        raise RuntimeError(
            f"undeclared environment variable {name!r}; declare it in "
            f"repro.core.env (known: {sorted(_REGISTRY)})")
    fallback = spec.default if default is _UNSET else default
    raw = os.environ.get(name, "")
    if raw == "":
        return fallback
    try:
        return spec.parse(raw)
    except ValueError:
        return fallback


def registry() -> Dict[str, EnvVar]:
    """Snapshot of every declared knob (docs, tests, ``--help`` dumps)."""
    return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# Parsers
# ---------------------------------------------------------------------------


def _parse_str(raw: str) -> str:
    return raw


def _parse_int_min0(raw: str) -> int:
    """Non-negative int; garbage raises (read() falls back to default)."""
    return max(int(raw), 0)


def _parse_flag(raw: str) -> bool:
    return raw.strip().lower() in ("1", "true", "yes", "on")


def _parse_codec_threads(raw: str) -> int:
    """Historical contract: garbage disables the pool (0), it does not
    fall back to auto sizing — an operator who set the knob at all asked
    for explicit control."""
    try:
        return max(int(raw), 0)
    except ValueError:
        return 0


def _choice(options: tuple, fallback: str) -> Callable[[str], str]:
    def parse(raw: str) -> str:
        return raw if raw in options else fallback

    return parse


def _parse_lanes(raw: str) -> Optional[int]:
    """``REPRO_RANS_LANES``, sanitized.  Env input never raises — the
    explicit ``lanes=`` argument keeps strict validation: ``0`` means
    auto (mirrors ``REPRO_CODEC_THREADS=0``); garbage and negatives fall
    back to auto with a warning; values above the lane maximum or
    non-powers-of-two clamp down with a warning."""
    from repro.core.rans_np import _LANES_MAX

    try:
        val = int(raw)
    except ValueError:
        warnings.warn(
            f"REPRO_RANS_LANES={raw!r} is not an integer; using auto lanes",
            RuntimeWarning, stacklevel=4)
        return None
    if val == 0:
        return None
    if val < 0:
        warnings.warn(
            f"REPRO_RANS_LANES={val} is negative; using auto lanes",
            RuntimeWarning, stacklevel=4)
        return None
    if val > _LANES_MAX:
        warnings.warn(
            f"REPRO_RANS_LANES={val} exceeds the maximum; "
            f"clamping to {_LANES_MAX}", RuntimeWarning, stacklevel=4)
        return _LANES_MAX
    if val & (val - 1):
        p2 = 1 << (val.bit_length() - 1)
        warnings.warn(
            f"REPRO_RANS_LANES={val} is not a power of two; "
            f"clamping to {p2}", RuntimeWarning, stacklevel=4)
        return p2
    return val


# ---------------------------------------------------------------------------
# The knob inventory
# ---------------------------------------------------------------------------

declare("REPRO_ASSET_DIR", _parse_str, None,
        "directory holding trained tokenizer assets (default: the "
        "package's tokenizer/assets)")
declare("REPRO_CODEC_THREADS", _parse_codec_threads, None,
        "shared codec pool size; 0/1 disables, unset = auto "
        "(min(4, cpus) on >2-CPU hosts)")
declare("REPRO_LZ_MODE", _choice(("scalar", "vector", "device", "auto"),
                                 "auto"), "auto",
        "LZ77 path: scalar reference loop, NumPy vector parse, Pallas "
        "device match finder, or size-routed auto")
declare("REPRO_RANS_MODE", _choice(("auto", "device"), "numpy"), "auto",
        "rANS path: numpy forces the host coder, device forces the "
        "Pallas lane kernels, auto routes on backend + payload size")
declare("REPRO_RANS_LANES", _parse_lanes, None,
        "interleaved rANS lane count (power of two); 0/unset = auto")
declare("REPRO_LZ_DEVICE_MIN", _parse_int_min0, None,
        "payload bytes before the LZ77 device match finder pays off")
declare("REPRO_RANS_DEVICE_MIN", _parse_int_min0, None,
        "payload bytes before the device rANS lane kernels pay off")
declare("REPRO_PACK_DEVICE_MIN", _parse_int_min0, None,
        "batch token count before the device pack kernel pays off")
declare("REPRO_HIST_DEVICE_MIN", _parse_int_min0, None,
        "payload bytes before the device histogram kernel pays off")
declare("REPRO_LOCK_SANITIZER", _parse_flag, False,
        "1/true enables the runtime lock-order sanitizer "
        "(repro.core.locks); on for concurrency/crash test markers")
declare("REPRO_ANALYSIS_FROZEN_MANIFEST", _parse_str, None,
        "override path of the frozen wire-format hash manifest "
        "(repro.analysis rule REPRO003; tests point it at fixtures)")
declare("REPRO_OBS", _parse_flag, True,
        "0/false disables the repro.obs metrics/tracing layer; "
        "instrument sites resolve to shared no-op stubs at creation")


def _parse_int_min1(raw: str) -> int:
    """Positive int; garbage raises (read() falls back to default)."""
    val = int(raw)
    if val < 1:
        raise ValueError(f"expected >= 1, got {val}")
    return val


def _parse_float_min0(raw: str) -> float:
    """Non-negative float; garbage raises (read() falls back)."""
    val = float(raw)
    if val < 0:
        raise ValueError(f"expected >= 0, got {val}")
    return val


declare("REPRO_GATEWAY_MAX_INFLIGHT", _parse_int_min1, 64,
        "gateway admission control: max requests executing at once "
        "across all connections; excess requests are rejected with "
        "error=admission_reject, never buffered")
declare("REPRO_GATEWAY_CONN_WINDOW", _parse_int_min1, 8,
        "gateway per-connection in-flight window; a client pipelining "
        "past it is stalled by TCP backpressure (the reader loop stops "
        "consuming), propagating the ingest queue's max_pending")
declare("REPRO_GATEWAY_FRAME_MAX", _parse_int_min1, 16 << 20,
        "max accepted gateway frame payload (bytes); larger frames "
        "close the connection with error=frame_too_large")
declare("REPRO_GATEWAY_DRAIN_S", _parse_float_min0, 5.0,
        "graceful-drain budget on SIGTERM: seconds the gateway waits "
        "for in-flight requests before forcing shutdown")
declare("REPRO_GATEWAY_REFRESH_S", _parse_float_min0, 0.5,
        "read-replica poll interval: how often a replica gateway "
        "re-checks store.json / shard indexes for writer publishes")
declare("REPRO_FAULTS", _parse_str, "",
        "fault-injection spec 'pattern=schedule,action[;...]' armed at "
        "the named failpoint sites (repro.core.failpoints.SITES); "
        "empty = nothing injected")
declare("REPRO_FAULTS_SEED", _parse_int_min0, 0,
        "seed for the per-rule RNG behind probabilistic (p:) fault "
        "schedules; same seed + same hit order = same fault sequence")
declare("REPRO_GATEWAY_RETRIES", _parse_int_min0, 4,
        "GatewayClient retry budget per call(): total attempts for "
        "retryable failures (connection loss, admission_reject, "
        "timeout); 0 disables retries")
declare("REPRO_GATEWAY_RETRY_BASE_S", _parse_float_min0, 0.05,
        "GatewayClient backoff base: sleep base*2^attempt plus "
        "seeded jitter between retries, capped at 2s")
