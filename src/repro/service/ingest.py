"""Async ingest queue: producers hand off batches of texts and move on;
a dispatcher thread plans group commits and per-shard writer threads make
them durable in parallel.

Why a queue at all: `ShardedPromptStore.put_many` is synchronous — the
caller eats the codec-pipeline pass *and* two fsyncs per shard touched.
In the request path of a real-time LLM app (the paper's target, §6.2.3)
that latency lands on the user.  Here `submit()` costs one sha256 per
text plus an enqueue; durability happens behind the scenes:

    producers ──submit()──> pending deque ──dispatcher──> per-shard
    (backpressure when      (group-commit    (plan_batch:  writer threads
     max_pending texts       accumulation)    compress +   (commit_batch:
     are queued)                              reserve seq)  parallel fsync)

Group-commit state machine (one flush):

    IDLE --submit--> ACCUMULATING --[>= flush_batch texts
                         |            or flush_interval_s elapsed
                         |            or flush()/drain()/stop()]--> FLUSH
                         '--submit--' (resets nothing; deadline is the
                                       OLDEST pending submission's age)

    FLUSH: dispatcher pops whole submissions until >= flush_batch texts,
    plans them (one batched codec pass, no locks held; the byte stage
    fans records out over the shared codec thread pool — see
    ``repro.core.codec`` — so a flush costs its slowest record, not the
    sum), then enqueues one commit per shard touched.  The flush is DONE when every shard part is
    durable AND every earlier flush is done — completion is prefix-ORDERED
    like WAL group commit (a later ticket never completes before an
    earlier one), so on an error-free run `ticket.wait()` returning means
    everything submitted up to that point is durable.  Errors are isolated
    per flush: a failed flush raises on its OWN tickets only, and later,
    independent flushes still commit — a caller that needs cross-flush
    atomicity must wait on each of its tickets.

Racing duplicates (same text submitted twice before the first commit
lands) may be written twice; content keys make that harmless and the
compactor reclaims the dead copy — see the store's concurrency notes.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

from repro import obs
from repro.core.codec import codec_pool_size
from repro.core.store import ShardedPromptStore, content_key


class IngestError(RuntimeError):
    """A flush failed and this ticket's texts did not commit.  Raised by
    `IngestTicket.wait` as a FRESH instance per call — every ticket of a
    failed flush shares one underlying cause (``__cause__``), but never
    one exception object, so concurrent waiters can't mutate each
    other's tracebacks."""


class IngestTicket:
    """Handle for one `submit()`: the content keys are known immediately
    (they are content addresses); `wait()` blocks until this submission's
    texts are durable on disk — and, because completion is prefix-ordered,
    until every earlier submission has *settled* (committed, or raised on
    its own ticket)."""

    def __init__(self, keys: List[str]) -> None:
        self.keys = keys
        self.submitted_ts = time.monotonic()
        self._event = threading.Event()
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> List[str]:
        if not self._event.wait(timeout):
            raise TimeoutError("ingest ticket not durable within timeout")
        if self._error is not None:
            # wrap per call: re-raising the flush's one exception object
            # from N waiters would let them race on its traceback
            raise IngestError(
                f"ingest flush failed; this ticket's {len(self.keys)} "
                f"text(s) were not committed: {self._error}"
            ) from self._error
        return self.keys

    def _finish(self, error: Optional[BaseException]) -> None:
        self._error = error
        self._event.set()


class _Submission:
    __slots__ = ("ts", "texts", "method", "ticket")

    def __init__(self, texts: Sequence[str], method: Optional[str],
                 ticket: IngestTicket) -> None:
        self.ts = time.monotonic()
        self.texts = list(texts)
        self.method = method
        self.ticket = ticket


class _Flush:
    """One group commit in flight: `remaining` shard parts still being
    fsynced, chained to the previous flush for prefix-ordered completion.
    `id` numbers the queue's flushes; it stamps the flush's trace events."""

    __slots__ = ("id", "tickets", "remaining", "error", "finished",
                 "prev_finished", "next")

    def __init__(self, flush_id: int, tickets: List[IngestTicket],
                 n_parts: int, prev_finished: bool) -> None:
        self.id = flush_id
        self.tickets = tickets
        self.remaining = n_parts
        self.error: Optional[BaseException] = None
        self.finished = False
        self.prev_finished = prev_finished
        self.next: Optional["_Flush"] = None


class IngestQueue:
    """Bounded async ingest into a `ShardedPromptStore`.

    Lifecycle: `start()` -> `submit()`/`flush()`/`drain()` -> `stop()`
    (also usable as a context manager).  `stop()` always drains — pending
    submissions are flushed and committed before the threads exit, so a
    clean shutdown never loses acknowledged work.
    """

    def __init__(self, store: ShardedPromptStore, flush_batch: int = 64,
                 flush_interval_s: float = 0.05, max_pending: int = 1024) -> None:
        if flush_batch < 1:
            raise ValueError("flush_batch must be >= 1")
        if max_pending < 1:
            raise ValueError("max_pending must be >= 1")
        self._store = store
        self.flush_batch = int(flush_batch)
        self.flush_interval_s = float(flush_interval_s)
        self.max_pending = int(max_pending)
        self._cv = threading.Condition()
        self._items: "deque[_Submission]" = deque()
        self._pending_texts = 0
        self._dispatching = False
        self._outstanding = 0          # registered, unfinished flushes
        self._next_flush_id = 0        # dispatcher thread only
        self._tail: Optional[_Flush] = None
        self._flush_requested = False
        self._started = False
        self._stopping = False
        self._stopped = False
        self._writer_queues: List["queue.Queue"] = [
            queue.Queue() for _ in range(store.n_shards)]
        self._writers: List[threading.Thread] = []
        self._dispatcher: Optional[threading.Thread] = None
        # metrics: registry-backed counters (always real; see repro.obs)
        # plus queue-depth and wait-time histograms: submit -> durable,
        # submit -> taken by the dispatcher, and shard part queued ->
        # its writer starting the commit
        self._n_submitted = obs.owned_counter("ingest.submitted")
        self._n_committed = obs.owned_counter("ingest.committed")
        self._n_flushes = obs.owned_counter("ingest.flushes")
        self._n_backpressure_waits = obs.owned_counter(
            "ingest.backpressure_waits")
        self._max_depth = 0
        self._depth_h = obs.histogram("ingest.queue_depth")
        self._wait_h = obs.histogram("ingest.wait.s")
        self._queue_h = obs.histogram("ingest.queue.s")
        self._writer_queue_h = obs.histogram("ingest.writer_queue.s")
        obs.owned_gauge("ingest.pending", lambda: self._pending_texts)

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> "IngestQueue":
        with self._cv:
            if self._started:
                raise RuntimeError("ingest queue already started")
            self._started = True
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="ingest-dispatcher", daemon=True)
        self._dispatcher.start()
        for i in range(self._store.n_shards):
            w = threading.Thread(target=self._writer_loop, args=(i,),
                                 name=f"ingest-writer-{i}", daemon=True)
            w.start()
            self._writers.append(w)
        return self

    def stop(self) -> None:
        """Drain + shut down (idempotent): flush everything pending, wait
        for the writers' fsyncs, then join all threads."""
        with self._cv:
            if not self._started or self._stopped:
                self._stopped = True
                return
            self._stopping = True
            self._cv.notify_all()
        self._dispatcher.join()
        for q in self._writer_queues:
            q.put(None)
        for w in self._writers:
            w.join()
        with self._cv:
            assert self._outstanding == 0 and not self._items
            self._stopped = True

    def __enter__(self) -> "IngestQueue":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- producer API ----------------------------------------------------------

    def submit(self, texts: Sequence[str],
               method: Optional[str] = None) -> IngestTicket:
        """Enqueue a batch; returns immediately (after backpressure) with
        a ticket whose `.keys` are already the final content keys."""
        ticket = IngestTicket([content_key(t) for t in texts])
        if not texts:
            ticket._finish(None)
            return ticket
        with self._cv:
            if not self._started or self._stopping:
                raise RuntimeError("ingest queue is not running")
            while self._pending_texts >= self.max_pending and not self._stopping:
                self._n_backpressure_waits.inc()
                self._cv.wait()
            if self._stopping:
                raise RuntimeError("ingest queue is not running")
            self._items.append(_Submission(texts, method, ticket))
            self._pending_texts += len(texts)
            self._n_submitted.inc(len(texts))
            self._max_depth = max(self._max_depth, self._pending_texts)
            self._depth_h.observe(self._pending_texts)
            self._cv.notify_all()
        return ticket

    def flush(self) -> None:
        """Ask the dispatcher to flush now instead of waiting for the
        batch/interval threshold."""
        with self._cv:
            self._flush_requested = True
            self._cv.notify_all()

    def drain(self) -> None:
        """Block until everything submitted so far is durable."""
        with self._cv:
            if not self._started:
                raise RuntimeError("ingest queue is not running")
            self._flush_requested = True
            self._cv.notify_all()
            while self._items or self._dispatching or self._outstanding:
                self._cv.wait()

    def stats(self) -> dict:
        with self._cv:
            return {
                "submitted": self._n_submitted.value,
                "committed": self._n_committed.value,
                "pending": self._pending_texts,
                "flushes": self._n_flushes.value,
                "backpressure_waits": self._n_backpressure_waits.value,
                "max_queue_depth": self._max_depth,
                "flush_batch": self.flush_batch,
                "flush_interval_s": self.flush_interval_s,
                "max_pending": self.max_pending,
                # compression parallelism the dispatcher's plan_batch calls
                # inherit (REPRO_CODEC_THREADS; 0/1 = sequential)
                "codec_threads": codec_pool_size(),
            }

    # -- dispatcher ------------------------------------------------------------

    def _dispatch_loop(self) -> None:
        while True:
            with self._cv:
                while True:
                    if self._items:
                        now = time.monotonic()
                        deadline = self._items[0].ts + self.flush_interval_s
                        if (self._pending_texts >= self.flush_batch
                                or self._flush_requested or self._stopping
                                or now >= deadline):
                            break
                        self._cv.wait(timeout=max(deadline - now, 1e-3))
                    elif self._stopping:
                        return
                    else:
                        self._cv.wait()
                taken: List[_Submission] = []
                n = 0
                now = time.monotonic()
                while self._items and n < self.flush_batch:
                    sub = self._items.popleft()
                    self._queue_h.observe(now - sub.ticket.submitted_ts)
                    taken.append(sub)
                    n += len(sub.texts)
                self._pending_texts -= n
                if not self._items:
                    self._flush_requested = False
                self._dispatching = True
                self._cv.notify_all()  # wake backpressured producers
            flush_id = self._next_flush_id
            self._next_flush_id += 1
            with obs.trace_context(flush=flush_id), \
                    obs.span("ingest.dispatch", trace_args={"prompts": n}):
                self._plan_and_dispatch(taken, flush_id)

    def _plan_and_dispatch(self, taken: List[_Submission],
                           flush_id: int) -> None:
        """Plan one flush (compress outside any lock) and hand each shard's
        entries to its writer.  Runs on the dispatcher thread, overlapping
        the previous flush's fsyncs."""
        parts: Dict[int, List[dict]] = {}
        plan_error: Optional[BaseException] = None
        try:
            # group by explicit method, preserving submission order per group
            by_method: Dict[Optional[str], List[str]] = {}
            for sub in taken:
                by_method.setdefault(sub.method, []).extend(sub.texts)
            for method, texts in by_method.items():
                _, plan = self._store.plan_batch(texts, method)
                for shard_id, entries in plan.items():
                    parts.setdefault(shard_id, []).extend(entries)
        except BaseException as e:  # fail the whole flush, keep the queue alive
            plan_error = e
            parts = {}
        with self._cv:
            flush = _Flush(
                flush_id,
                tickets=[sub.ticket for sub in taken],
                n_parts=len(parts),
                prev_finished=self._tail is None or self._tail.finished,
            )
            flush.error = plan_error
            if self._tail is not None and not self._tail.finished:
                self._tail.next = flush
            self._tail = flush
            self._outstanding += 1
            self._n_flushes.inc()
            self._dispatching = False
            if not parts:
                self._maybe_finish(flush)
            self._cv.notify_all()
        for shard_id, entries in parts.items():
            # writer threads are a parallelism pool, not the routing: an
            # online rebalance can return shard ids beyond the pool size
            # (and commit_batch re-routes stale plans itself), so the
            # true shard id travels with the work item
            q = self._writer_queues[shard_id % len(self._writer_queues)]
            q.put((shard_id, entries, flush, time.monotonic()))

    def _maybe_finish(self, flush: Optional[_Flush]) -> None:
        """cv held: cascade prefix-ordered flush completion."""
        now = time.monotonic()
        while (flush is not None and flush.remaining == 0
               and flush.prev_finished and not flush.finished):
            flush.finished = True
            self._outstanding -= 1
            for ticket in flush.tickets:
                self._wait_h.observe(now - ticket.submitted_ts)
                ticket._finish(flush.error)
            nxt = flush.next
            if nxt is not None:
                nxt.prev_finished = True
            if self._tail is flush:
                self._tail = None
            flush = nxt
        self._cv.notify_all()

    # -- writers ---------------------------------------------------------------

    def _writer_loop(self, writer_id: int) -> None:
        q = self._writer_queues[writer_id]
        while True:
            item = q.get()
            if item is None:
                return
            shard_id, entries, flush, queued_ts = item
            self._writer_queue_h.observe(time.monotonic() - queued_ts)
            err: Optional[BaseException] = None
            try:
                with obs.trace_context(flush=flush.id, shard=shard_id):
                    self._store.commit_batch(shard_id, entries)
            except BaseException as e:
                err = e
            with self._cv:
                if err is not None and flush.error is None:
                    flush.error = err
                elif err is None:
                    self._n_committed.inc(len(entries))
                flush.remaining -= 1
                self._maybe_finish(flush)
