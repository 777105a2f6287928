"""Runtime lock-order sanitizer: ranked lock constructors for the store.

The store's deadlock-freedom argument is a total order on its lock
classes (ARCHITECTURE.md "Static analysis & invariants"): a thread may
only acquire a lock whose rank is **>=** the highest rank it already
holds.  Equal ranks are allowed because the rebalancer legitimately
takes *all* compact locks, then *all* shard locks (each class in index
order, and only under the rebalance lock, so two such sweeps never
interleave).

    rebalance(0) < compact(10) < shard(20) < index(30) < meta(40)
                                                       < obs(100)

:func:`make_lock` / :func:`make_rlock` are drop-in constructor
replacements for ``threading.Lock()`` / ``threading.RLock()``.  With
``REPRO_LOCK_SANITIZER`` unset (production) they return the plain
threading primitive — zero overhead, nothing wrapped.  With the flag set
(the ``concurrency`` and ``crash`` pytest markers turn it on via
conftest) they return a :class:`_SanitizedLock` that keeps a per-thread
stack of held locks and raises :class:`LockOrderViolation` — with both
acquisition sites in the message — the moment any thread acquires
against the order, whether or not the opposing thread is running.  The
flag is read at *creation* time: a store built inside a sanitized test
stays sanitized for its lifetime.

The static half of this invariant is ``repro.analysis`` rule REPRO001,
which checks the acquisition *graph* over the same rank table without
running anything; this module catches what static analysis cannot see
(acquisitions through callbacks, test monkeypatching, future code the
graph walker under-approximates).
"""

from __future__ import annotations

import sys
import threading
from typing import Dict, List, Union

from repro.core import env

RANKS: Dict[str, int] = {
    "rebalance": 0,
    "compact": 10,
    "shard": 20,
    "index": 30,
    "meta": 40,
    # Near-leaf: the failpoint rule registry (repro.core.failpoints).
    # fire() runs inside arbitrary critical sections (an fsync under the
    # shard lock, a meta publish under the meta lock), so its lock must
    # out-rank every store lock; it stays below obs because _apply
    # records a metric, and obs never calls back into failpoints.
    "faults": 90,
    # Leaf rank: repro.obs instrument/registry locks.  Metrics
    # are recorded from inside every other critical section (a shard
    # append observes its fsync latency while the shard lock is held),
    # so obs locks must be acquirable while holding anything — and obs
    # code never calls back out, so nothing is ever acquired under them.
    "obs": 100,
}


class LockOrderViolation(RuntimeError):
    """A thread acquired a lock ranked below one it already holds."""


def sanitizer_enabled() -> bool:
    return env.read("REPRO_LOCK_SANITIZER")


_HELD = threading.local()  # .stack: List[_Held] for the current thread


class _Held:
    __slots__ = ("lock", "site")

    def __init__(self, lock: "_SanitizedLock", site: str):
        self.lock = lock
        self.site = site


def _held_stack() -> List[_Held]:
    stack = getattr(_HELD, "stack", None)
    if stack is None:
        stack = _HELD.stack = []
    return stack


def _acquisition_site() -> str:
    """One-line description of the nearest caller frame outside this
    module; cheap enough for hot test paths."""
    frame = sys._getframe(1)
    while frame is not None and frame.f_code.co_filename == __file__:
        frame = frame.f_back
    if frame is None:
        return "<unknown>"
    return (f"{frame.f_code.co_filename}:{frame.f_lineno} "
            f"in {frame.f_code.co_name}")


class _SanitizedLock:
    """Ranked wrapper over a threading Lock/RLock with order checking."""

    def __init__(self, order: str, reentrant: bool):
        if order not in RANKS:
            raise ValueError(
                f"unknown lock order {order!r}; known: {sorted(RANKS)}")
        self.order = order
        self.rank = RANKS[order]
        self.reentrant = reentrant
        self._inner: Union[threading.Lock, threading.RLock] = (
            threading.RLock() if reentrant else threading.Lock())

    def _check(self) -> None:
        stack = _held_stack()
        if not stack:
            return
        if any(h.lock is self for h in stack):
            if self.reentrant:
                return  # RLock re-entry is legal and not an ordering event
            raise LockOrderViolation(
                f"self-deadlock: thread already holds non-reentrant "
                f"{self.order!r} lock (acquired at {next(h.site for h in stack if h.lock is self)})")
        top = max(stack, key=lambda h: h.lock.rank)
        if self.rank < top.lock.rank:
            held = ", ".join(
                f"{h.lock.order}(rank {h.lock.rank}) at {h.site}"
                for h in stack)
            raise LockOrderViolation(
                f"lock-order violation: acquiring {self.order!r} "
                f"(rank {self.rank}) at {_acquisition_site()} while "
                f"holding higher-ranked locks [{held}]; documented order "
                f"is {' < '.join(sorted(RANKS, key=RANKS.get))}")

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        self._check()
        got = self._inner.acquire(blocking, timeout)
        if got:
            _held_stack().append(_Held(self, _acquisition_site()))
        return got

    def release(self) -> None:
        self._inner.release()
        stack = _held_stack()
        for i in range(len(stack) - 1, -1, -1):
            if stack[i].lock is self:
                del stack[i]
                break

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        inner = self._inner
        return inner.locked() if hasattr(inner, "locked") else False

    def __repr__(self) -> str:
        return f"<SanitizedLock order={self.order} rank={self.rank}>"


def make_lock(order: str):
    """A ``threading.Lock()`` tagged with its documented rank; sanitized
    wrapper only when ``REPRO_LOCK_SANITIZER`` is set at creation."""
    if sanitizer_enabled():
        return _SanitizedLock(order, reentrant=False)
    if order not in RANKS:
        raise ValueError(
            f"unknown lock order {order!r}; known: {sorted(RANKS)}")
    return threading.Lock()


def make_rlock(order: str):
    """``threading.RLock()`` counterpart of :func:`make_lock`."""
    if sanitizer_enabled():
        return _SanitizedLock(order, reentrant=True)
    if order not in RANKS:
        raise ValueError(
            f"unknown lock order {order!r}; known: {sorted(RANKS)}")
    return threading.RLock()
