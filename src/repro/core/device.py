"""Central device-dispatch policy for the codec tier.

Every codec stage with a Pallas kernel behind it (histogram, token
packing, LZ77 match finding, lane-parallel rANS) asks the same two
questions before leaving the host:

1. is a non-CPU JAX backend actually attached?  On CPU hosts the
   interpret-mode kernels lose to vectorized NumPy by orders of
   magnitude, so the device path is never taken implicitly there;
2. is the payload big enough to amortize the host->device->host round
   trip?  Tiny payloads pay more in dispatch + transfer than the kernel
   saves — each call site carries a crossover, overridable by an env
   knob.  The defaults are estimates, not chip measurements.

Keeping the answers here (instead of one private helper per module, as
the histogram and token-pack stages originally grew) means the routing
policy is uniform and testable in one place.

A device path that is taken runs the compiled kernel (see
``repro.kernels.interpret_default``); an error there propagates — it is
never turned into a host fallback.

The module also places JAX's persistent compilation cache
(:func:`enable_compile_cache`), which every entry point that compiles
for the chip calls before its first compile.  From the first routing
decision that takes a device path on, it times every backend compile of
the process into ``device.compile.s{fn=<jitted function>}``.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Optional

from repro import obs
from repro.core import env

#: the compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: fixed and inside the checkout (git-ignored), because the directory is
#: part of the cache key — a path that moves never hits
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"

#: JAX's event for one XLA backend compile (a persistent-cache hit skips it)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compile_watch_lock = threading.Lock()
_compile_watched = False


def backend_available() -> bool:
    """True iff JAX has a non-CPU backend attached."""
    import jax

    return jax.default_backend() != "cpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache goes to :data:`DEFAULT_COMPILE_CACHE`.
    Call before the first compile: JAX fixes the cache at that point."""
    import jax

    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


def crossover(env_var: str, default: int) -> int:
    """Payload-size floor (bytes/elements) for taking a device path.

    Reads ``env_var`` fresh on every call so benchmarks and tests can
    re-tune without reimporting; invalid values fall back to the
    call site's default rather than raising (the registry's int parser
    raises and ``env.read`` absorbs it into the default).
    """
    return env.read(env_var, default)


def _record_compile(event: str, secs: float, fun_name: str = "unknown",
                    **_) -> None:
    if event == BACKEND_COMPILE_EVENT:
        obs.histogram("device.compile.s", fn=fun_name).observe(secs)


def _watch_compiles() -> None:
    """Register the compile listener with JAX, once per process (JAX
    keeps its listeners process-wide).  The ``fn`` label is bounded by
    the number of jitted functions."""
    global _compile_watched
    with _compile_watch_lock:
        if _compile_watched:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_record_compile)
        _compile_watched = True


def use_device(size: int, env_var: str, default_min: int,
               force: Optional[bool] = None) -> bool:
    """The standard routing decision: explicit ``force`` wins, otherwise
    a non-CPU backend must be attached and ``size`` must clear the
    crossover."""
    if force is not None:
        decision = force
    else:
        decision = (backend_available()
                    and size >= crossover(env_var, default_min))
    if decision and not _compile_watched:
        _watch_compiles()
    # routing census: how often each kernel family actually leaves the
    # host (obs.counter is a no-op stub when REPRO_OBS=0)
    obs.counter("device.dispatch", knob=env_var.lower(),
                path="device" if decision else "host").inc()
    return decision
