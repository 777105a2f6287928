"""The chip: the look for it, its description, its compile events.

`CompileClock` is a copy of the one in the program's ``chip_smoke.py``:
it records JAX's compile events from every thread, so compiles can be
counted in set-up and in the window alike.
"""

from __future__ import annotations

import threading
import time
from typing import List, Tuple


class NoAccelerator(RuntimeError):
    pass


def require_tpu(chips: int) -> dict:
    """Put a computation on the first device; refuse anything but a TPU
    with at least ``chips`` devices."""
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise NoAccelerator(f"JAX's first device is {d.platform!r} "
                            f"({d.device_kind}); this benchmark runs only "
                            f"on a TPU")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devices)}")
    x = jax.device_put(jnp.arange(1024, dtype=jnp.int32), d)
    if int((x * 2).sum()) != 1024 * 1023:
        raise NoAccelerator("a first computation on the TPU came out wrong")
    return describe(chips)


def describe(chips: int) -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax

    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for dev in jax.devices()[:chips]]
    return int(max(peaks)) if peaks else 0


class CompileClock:
    """Records JAX's compile events (tracing, lowering, backend compile)
    from any thread."""

    def __init__(self) -> None:
        import jax.monitoring

        self._lock = threading.Lock()
        self._spans: List[Tuple[float, float, bool]] = []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            end = time.monotonic()
            with self._lock:
                self._spans.append(
                    (end - secs, end, name.endswith("backend_compile_duration")))

    def window(self, t0: float, t1: float) -> dict:
        """Backend compiles that ended in [t0, t1), and the wall time in
        that span covered by at least one compile event."""
        with self._lock:
            spans = sorted((max(a, t0), min(b, t1), c)
                           for a, b, c in self._spans if b > t0 and a < t1)
        covered, reach = 0.0, t0
        for a, b, _ in spans:
            if b > reach:
                covered += b - max(a, reach)
                reach = b
        with self._lock:
            n = sum(1 for _, b, c in self._spans if c and t0 <= b < t1)
        return {"backend_compiles": n, "compile_s": covered}
