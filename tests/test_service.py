"""repro.service tier: async ingest queue, background compaction with
codec stage reselection, the serve-path token cache, and PromptService
lifecycle — including the concurrency contracts (threaded store access,
reader/compactor coordination, crash-safe generation swap)."""

import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core.api import PromptCompressor
from repro.core.store import ShardedPromptStore, content_key
from repro.service import (BackgroundCompactor, IngestError, IngestQueue,
                           PromptService, TokenCache, compact_shard,
                           compact_store)
from repro.tokenizer.vocab import default_tokenizer


@pytest.fixture(scope="module")
def tok():
    return default_tokenizer()


def _texts(n, tag="svc", rep=20):
    return [f"{tag} prompt {i}: deploy the canary and watch the dashboards. "
            * rep for i in range(n)]


def _store(root, tok, method="hybrid", n_shards=4):
    return ShardedPromptStore(root, PromptCompressor(tok, method=method),
                              n_shards=n_shards)


# -- token cache --------------------------------------------------------------


def test_token_cache_hit_miss_eviction_budget():
    cache = TokenCache(capacity_bytes=4 * 100)  # room for 4 100-byte arrays
    arrs = {f"k{i}": np.arange(25, dtype=np.uint32) for i in range(6)}  # 100 B
    assert cache.get("k0") is None                        # miss
    for k, a in arrs.items():
        cache.put(k, a)
    st = cache.stats()
    assert st["entries"] == 4 and st["bytes"] == 400      # budget enforced
    assert st["evictions"] == 2                           # k0, k1 evicted (LRU)
    assert cache.get("k0") is None and cache.get("k5") is not None
    # touching k2 makes k3 the LRU victim
    assert cache.get("k2") is not None
    cache.put("k9", np.arange(25, dtype=np.uint32))
    assert cache.get("k3") is None and cache.get("k2") is not None
    # an array bigger than the whole budget is rejected, not thrashed
    cache.put("huge", np.arange(1000, dtype=np.uint32))
    assert cache.get("huge") is None
    assert cache.stats()["oversize_rejects"] == 1
    assert 0.0 < cache.stats()["hit_rate"] < 1.0


def test_token_cache_get_or_load_many_batches_misses():
    cache = TokenCache(capacity_bytes=1 << 20)
    calls = []

    def loader_many(keys):
        calls.append(list(keys))
        return [np.full(3, int(k[1:]), dtype=np.uint32) for k in keys]

    out = cache.get_or_load_many(["k1", "k2", "k1"], loader_many)
    assert calls == [["k1", "k2"]]        # one batched load, dup deduped
    assert np.array_equal(out[0], out[2])
    out2 = cache.get_or_load_many(["k2", "k3"], loader_many)
    assert calls[1] == ["k3"]             # only the miss is loaded
    assert np.array_equal(out2[0], np.full(3, 2, np.uint32))


# -- ingest queue -------------------------------------------------------------


def test_ingest_queue_roundtrip_lossless(tmp_path, tok):
    store = _store(tmp_path, tok)
    texts = _texts(20)
    with IngestQueue(store, flush_batch=8, flush_interval_s=0.02) as q:
        tickets = [q.submit(texts[i:i + 5]) for i in range(0, 20, 5)]
        keys = [k for t in tickets for k in t.wait(20)]
    assert keys == [content_key(t) for t in texts]  # keys known at submit
    assert store.get_many(keys) == texts
    assert store.verify_all()["failure"] == 0
    st = q.stats()
    assert st["submitted"] == st["committed"] == 20 and st["pending"] == 0


def test_ingest_queue_matches_sync_store_bytes(tmp_path, tok):
    """Async group commits lay out every shard byte-identically to the
    same batches through synchronous put_many (same frames, same seq
    order per shard)."""
    texts = _texts(24, tag="bytes")
    a = _store(tmp_path / "a", tok, method="token")
    b = _store(tmp_path / "b", tok, method="token")
    with IngestQueue(a, flush_batch=8) as q:
        for i in range(0, 24, 8):
            q.submit(texts[i:i + 8]).wait(20)  # one flush per submission
    for i in range(0, 24, 8):
        b.put_many(texts[i:i + 8])
    assert a.keys() == b.keys()
    for i in range(4):
        name = f"shard-{i:03d}.bin"
        assert (tmp_path / "a" / name).read_bytes() == \
            (tmp_path / "b" / name).read_bytes()


def test_ingest_wait_histograms_sample_each_submission_and_part(
        tmp_path, tok, monkeypatch):
    """``ingest.queue.s`` takes one sample per submission, when the
    dispatcher takes it; ``ingest.writer_queue.s`` one per shard part,
    when its writer starts the commit; ``ingest.dispatch.s`` one per
    flush."""
    monkeypatch.delenv("REPRO_OBS", raising=False)
    obs.reset()
    try:
        store = _store(tmp_path, tok)
        texts = _texts(12, tag="waits")
        with IngestQueue(store, flush_batch=4, flush_interval_s=0.01) as q:
            for i in range(0, 12, 2):
                q.submit(texts[i:i + 2])
            q.drain()
            flushes = q.stats()["flushes"]
        h = obs.snapshot()["histograms"]
        assert h["ingest.queue.s"]["count"] == 6
        assert h["ingest.dispatch.s"]["count"] == flushes
        parts = h["store.commit.s"]["count"]   # one commit per shard part
        assert flushes <= parts <= 4 * flushes
        assert h["ingest.writer_queue.s"]["count"] == parts
        assert h["ingest.writer_queue.s"]["min"] >= 0.0
    finally:
        obs.reset()


def test_ingest_interval_flush_without_explicit_flush(tmp_path, tok):
    store = _store(tmp_path, tok)
    with IngestQueue(store, flush_batch=1000, flush_interval_s=0.02) as q:
        ticket = q.submit(["interval flush " * 10])
        ticket.wait(20)                       # group-commit timer fired
        assert ticket.keys[0] in store


def test_ingest_prefix_ordered_durability(tmp_path, tok):
    """On an error-free run, ticket N waiting implies every earlier
    submission is durable too (WAL-style group-commit ordering; errors
    are isolated per flush — see test_ingest_error_propagates...)."""
    store = _store(tmp_path, tok)
    texts = _texts(30, tag="prefix")
    with IngestQueue(store, flush_batch=4, flush_interval_s=0.01) as q:
        tickets = [q.submit([t]) for t in texts]
        tickets[-1].wait(20)
        for t, text in zip(tickets, texts):   # all earlier ones done
            assert t.done()
            assert t.keys[0] in store


def test_ingest_backpressure_bounds_queue(tmp_path, tok):
    store = _store(tmp_path, tok)
    texts = _texts(40, tag="bp", rep=4)
    with IngestQueue(store, flush_batch=4, max_pending=8) as q:
        for t in texts:
            q.submit([t])
        q.drain()
    st = q.stats()
    assert st["committed"] == 40
    assert st["max_queue_depth"] <= 8 + 1     # one submission of overshoot
    assert len(store) == 40


def test_ingest_error_propagates_and_queue_survives(tmp_path, tok):
    store = _store(tmp_path, tok)
    with IngestQueue(store, flush_batch=4) as q:
        bad = q.submit(["doomed " * 5], method="no-such-method")
        with pytest.raises(IngestError, match="method") as ei:
            bad.wait(20)
        assert isinstance(ei.value.__cause__, ValueError)
        ok = q.submit(["fine " * 5])          # queue still alive after error
        ok.wait(20)
        assert ok.keys[0] in store
    with pytest.raises(RuntimeError, match="not running"):
        q.submit(["too late"])


def test_ingest_error_distinct_instances_per_ticket(tmp_path, tok):
    """Every ticket of a failed flush (and every wait() on one ticket)
    raises a FRESH IngestError — concurrent waiters must never share one
    exception object whose traceback they'd race to mutate.  The shared
    part is the cause: one underlying flush error."""
    store = _store(tmp_path, tok)
    with IngestQueue(store, flush_batch=64,
                     flush_interval_s=10.0) as q:
        t1 = q.submit(["doomed a " * 5], method="no-such-method")
        t2 = q.submit(["doomed b " * 5], method="no-such-method")
        q.flush()                             # both land in ONE flush
        errs = []
        for t in (t1, t2, t1):                # third: re-wait same ticket
            with pytest.raises(IngestError) as ei:
                t.wait(20)
            errs.append(ei.value)
    assert errs[0] is not errs[1]
    assert errs[0] is not errs[2]
    assert errs[0].__cause__ is errs[1].__cause__  # one flush, one cause


# -- compaction ---------------------------------------------------------------


def test_compaction_preserves_bytes_golden(tmp_path, tok):
    """Compaction is content-lossless: every text and token stream is
    byte/id-identical before and after, sha sweep stays clean, and the
    rebuilt shard carries exactly the records it had."""
    store = _store(tmp_path, tok, method="hybrid")
    texts = _texts(16, tag="golden")
    keys = store.put_many(texts)
    before_texts = store.get_many(keys)
    before_tokens = store.get_tokens_many(keys)
    results = compact_store(store, reselect=True)
    assert [r.shard_id for r in results] == list(range(store.n_shards))
    assert store.keys() == keys               # order preserved
    assert store.get_many(keys) == before_texts
    for a, b in zip(before_tokens, store.get_tokens_many(keys)):
        assert np.array_equal(a, b)
    assert store.verify_all() == {"success": 16, "failure": 0, "total": 16}
    for r in results:
        assert r.bytes_after <= r.bytes_before
    # the swap is a generation bump: old filenames gone, meta committed
    st = store.stats()
    assert st["gens"] == [1] * store.n_shards and st["dead_bytes"] == 0
    assert not (tmp_path / "shard-000.bin").exists()
    # reopen resolves the new generation and preserves order + content
    reopened = _store(tmp_path, tok)
    assert reopened.keys() == keys
    assert reopened.get_many(keys) == before_texts


def test_compaction_reencodes_when_another_pipeline_wins(tmp_path, tok):
    """Stage reselection: a shard stored with a deliberately poor method
    for its mix gets re-encoded with the winning pipeline, and shrinks."""
    store = _store(tmp_path, tok, method="token", n_shards=1)
    # highly repetitive text: byte-compression beats raw token packing
    keys = store.put_many([("the same sentence again and again. " * 120)
                           + str(i) for i in range(6)])
    before = store.shard_stats(0)["file_bytes"]
    res = compact_shard(store, 0, reselect=True)
    assert res.reencoded and res.method in ("zstd", "hybrid")
    assert res.bytes_after < before
    assert store.get_many(keys) and store.verify_all()["failure"] == 0
    # frames are self-describing, so a reopen decodes the new method
    reopened = _store(tmp_path, tok, n_shards=1)
    assert reopened.verify_all()["failure"] == 0


def test_compaction_reclaims_duplicate_dead_bytes(tmp_path, tok):
    """The async-ingest dup race (two planners, same text) leaves a dead
    copy on disk; compaction reclaims it."""
    store = _store(tmp_path, tok, method="zstd", n_shards=1)
    text = "raced duplicate " * 30
    _, plan1 = store.plan_batch([text])
    _, plan2 = store.plan_batch([text])       # planned before plan1 commits
    for plan in (plan1, plan2):
        for sid, entries in plan.items():
            store.commit_batch(sid, entries)
    assert len(store) == 1
    assert store.shard_stats(0)["dead_bytes"] > 0
    res = compact_shard(store, 0, reselect=False)
    assert res.bytes_reclaimed > 0
    assert store.shard_stats(0)["dead_bytes"] == 0
    assert store.get(content_key(text)) == text


def test_crashed_compaction_generations_are_garbage_collected(tmp_path, tok):
    store = _store(tmp_path, tok, n_shards=2)
    keys = store.put_many(_texts(8, tag="gc"))
    # crash BEFORE the meta commit: orphaned next-generation files
    (tmp_path / "shard-000.g0001.bin").write_bytes(b"orphan")
    (tmp_path / "shard-000.g0001.idx.jsonl").write_text("{broken")
    reopened = _store(tmp_path, tok)
    assert not (tmp_path / "shard-000.g0001.bin").exists()
    assert reopened.keys() == keys and reopened.verify_all()["failure"] == 0
    # crash AFTER the meta commit: stale old-generation files linger
    compact_store(reopened, reselect=False)
    (tmp_path / "shard-001.bin").write_bytes(b"stale old gen")
    again = _store(tmp_path, tok)
    assert not (tmp_path / "shard-001.bin").exists()
    assert again.keys() == keys and again.verify_all()["failure"] == 0


def test_gc_globs_do_not_swallow_wider_shard_names(tmp_path, tok):
    """GC patterns must match shard i exactly: 'shard-100*' would also
    match shard-1000+ once n_shards needs 4 digits."""
    store = _store(tmp_path, tok, n_shards=4)
    keys = store.put_many(_texts(8, tag="wide"))
    # a (hypothetical) wider-named shard file must survive shard-000's GC
    wide = tmp_path / "shard-0001.bin"
    wide.write_bytes(b"not shard 000's to collect")
    reopened = _store(tmp_path, tok)
    assert wide.exists()
    wide.unlink()
    assert reopened.keys() == keys


def test_gc_leaves_foreign_family_gen0_files(tmp_path, tok):
    """A legacy data.bin/index.jsonl sitting in a multi-shard root (e.g. a
    restored backup awaiting migration) is not ours to collect — only
    generation-suffixed names are unambiguously store-written, so gen-0
    files of a different naming family survive every GC sweep."""
    store = _store(tmp_path, tok, n_shards=4)
    keys = store.put_many(_texts(8, tag="foreign"))
    (tmp_path / "data.bin").write_bytes(b"someone's backup")
    (tmp_path / "index.jsonl").write_text("not ours either\n")
    compact_store(store, reselect=False)      # in-process GC path
    reopened = _store(tmp_path, tok)          # open-time GC path
    assert (tmp_path / "data.bin").read_bytes() == b"someone's backup"
    assert (tmp_path / "index.jsonl").exists()
    assert reopened.keys() == keys
    (tmp_path / "data.bin").unlink()
    (tmp_path / "index.jsonl").unlink()


def test_all_shard_stats_matches_per_shard(tmp_path, tok):
    store = _store(tmp_path, tok, n_shards=4)
    store.put_many(_texts(12, tag="stats"))
    assert store.all_shard_stats() == [store.shard_stats(i) for i in range(4)]


def test_compaction_catches_up_concurrent_commits(tmp_path, tok):
    """Records committed between the compactor's snapshot and its swap are
    carried into the new generation (reader/compactor coordination)."""
    store = _store(tmp_path, tok, n_shards=1)
    keys = store.put_many(_texts(6, tag="snap"))
    recs = store.shard_records(0)
    blobs = store.read_records(0, recs)
    entries = [{"key": r["key"], "seq": r["seq"], "method": r["method"],
                "n_chars": r["n_chars"], "blob": b}
               for r, b in zip(recs, blobs)]
    late = store.put_many(["committed mid-compaction " * 10])  # after snapshot
    swap = store.swap_shard(0, entries)
    assert swap["n_caught_up"] == 1
    assert store.keys() == keys + late
    assert store.verify_all()["failure"] == 0


# -- rebalance ----------------------------------------------------------------


def test_rebalance_preserves_keys_seq_and_content(tmp_path, tok):
    store = _store(tmp_path, tok, n_shards=4)
    texts = _texts(24, tag="reb")
    keys = store.put_many(texts)
    for target in (8, 3, 1):
        res = store.rebalance(target)
        assert res["n_shards_after"] == target == store.n_shards
        assert store.keys() == keys          # seq order preserved
        assert store.get_many(keys) == texts
        reopened = _store(tmp_path, tok)
        assert reopened.n_shards == target and reopened.keys() == keys
    # writes keep working on the final layout
    extra = store.put_many(_texts(4, tag="after-reb"))
    assert store.keys() == keys + extra
    assert store.rebalance(1)["n_caught_up"] == 0  # no-op path


def test_rebalance_while_writers_commit_reroutes(tmp_path, tok):
    """A plan made under the old layout commits correctly after a
    rebalance: commit_batch re-routes by the new shard count."""
    store = _store(tmp_path, tok, n_shards=2)
    texts = _texts(8, tag="stale-plan")
    _, plan = store.plan_batch(texts)
    store.rebalance(5)                        # invalidates the plan routing
    for sid, entries in plan.items():
        store.commit_batch(sid, entries)
    assert len(store) == 8
    assert store.verify_all()["failure"] == 0
    reopened = _store(tmp_path, tok)
    assert reopened.keys() == store.keys()


# -- PromptService ------------------------------------------------------------


def test_service_cached_admission_decodes_once(tmp_path, tok):
    store = _store(tmp_path, tok)
    keys = store.put_many(_texts(6, tag="adm"))
    with PromptService(store, cache_bytes=1 << 20, ingest_async=False) as svc:
        first = svc.get_tokens_many(keys)
        second = svc.get_tokens_many(keys)
        for a, b in zip(first, second):
            assert np.array_equal(a, b)
        st = svc.cache.stats()
        assert st["misses"] == 6 and st["hits"] == 6
        assert np.array_equal(svc.get_tokens(keys[0]), first[0])
        assert svc.cache.stats()["hits"] == 7


def test_service_sync_degrade_and_stats(tmp_path, tok):
    store = _store(tmp_path, tok)
    with PromptService(store, cache_bytes=0, ingest_async=False) as svc:
        ticket = svc.put_async(["sync degrade " * 8])
        assert ticket.done()                  # already durable
        assert ticket.wait(0) == ticket.keys
        st = svc.stats()
        assert st["cache"] is None and st["ingest"] is None
        assert st["store"]["n_prompts"] == 1


def test_service_lifecycle_stop_idempotent(tmp_path, tok):
    store = _store(tmp_path, tok)
    svc = PromptService(store, compact_interval_s=60.0).start()
    t = svc.put_async(_texts(3, tag="stop"))
    svc.stop()
    assert t.done() and t.wait(0)             # stop() drained first
    svc.stop()                                # idempotent
    with pytest.raises(RuntimeError):
        svc.start()


def test_service_no_zombie_restart_after_stop(tmp_path, tok):
    """start()/__enter__/put_async after stop() must raise, not hand back
    a service whose dispatcher and compactor threads are dead (work
    submitted to that zombie would queue forever, undrained)."""
    store = _store(tmp_path, tok)
    svc = PromptService(store)
    svc.start()
    svc.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        svc.start()
    with pytest.raises(RuntimeError, match="stopped"):
        with svc:
            pass                              # pragma: no cover
    with pytest.raises(RuntimeError, match="stopped"):
        svc.put_async(["too late " * 4])
    # the sync-degrade path must refuse too: no queue, but the contract
    # (stopped service accepts no writes) is the same
    sync_svc = PromptService(store, ingest_async=False)
    sync_svc.start()
    sync_svc.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        sync_svc.put_async(["too late " * 4])


def test_cache_serves_read_only_arrays(tmp_path, tok):
    """Cached token arrays are shared across hits; a caller mutating one
    must get a ValueError, and the cached entry must stay intact."""
    store = _store(tmp_path, tok)
    with PromptService(store, ingest_async=False) as svc:
        key = svc.put("mutation probe " * 8)
        arr = svc.get_tokens(key)             # miss: loads + caches
        with pytest.raises(ValueError):
            arr[0] = 999999
        again = svc.get_tokens(key)           # hit: same shared array
        assert again is arr
        assert np.array_equal(np.asarray(store.get_tokens(key)), arr)
    # direct TokenCache.put enforces the same freeze
    cache = TokenCache(1 << 20)
    src = np.arange(8, dtype=np.int64)
    cache.put("k", src)
    with pytest.raises(ValueError):
        cache.get("k")[0] = 7


# -- concurrency (slow tier) --------------------------------------------------


@pytest.mark.slow
@pytest.mark.concurrency
def test_threaded_put_many_and_get_tokens_many(tmp_path, tok):
    """Writers and readers hammer one ShardedPromptStore; every read is
    lossless and the final store passes the sha sweep."""
    store = _store(tmp_path, tok, method="token", n_shards=4)
    texts = _texts(96, tag="thr", rep=6)
    committed: list = []
    commit_lock = threading.Lock()
    errors: list = []

    def writer(lo, hi):
        try:
            for i in range(lo, hi, 4):
                batch = texts[i:i + 4]
                keys = store.put_many(batch)
                with commit_lock:
                    committed.extend(zip(keys, batch))
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def reader():
        try:
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                with commit_lock:
                    snap = list(committed)
                if len(snap) >= len(texts):
                    break
                if not snap:
                    continue
                keys = [k for k, _ in snap[-8:]]
                toks = store.get_tokens_many(keys)
                for (k, text), ids in zip(snap[-8:], toks):
                    assert tok.decode(ids) == text
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = ([threading.Thread(target=writer, args=(lo, lo + 24))
                for lo in range(0, 96, 24)]
               + [threading.Thread(target=reader) for _ in range(3)])
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(store) == len(texts)
    assert store.verify_all()["failure"] == 0
    # reopen-stable iteration order survives the concurrent commits
    reopened = _store(tmp_path, tok)
    assert reopened.keys() == store.keys()


@pytest.mark.slow
@pytest.mark.concurrency
def test_service_concurrent_ingest_compaction_serve(tmp_path, tok):
    """Acceptance: with the ingest queue AND background compaction
    running, the service stays byte-lossless — verify_all passes and
    every get/get_tokens matches a synchronous reference store."""
    store = _store(tmp_path, tok, method="token", n_shards=4)
    texts = _texts(80, tag="e2e", rep=8)
    svc = PromptService(store, cache_bytes=1 << 20, flush_batch=8,
                        flush_interval_s=0.005, compact_interval_s=0.02,
                        compact_trigger_dead_ratio=0.0, compact_min_dead_bytes=0)
    errors: list = []
    with svc:
        tickets = []

        def producer(lo, hi):
            try:
                for i in range(lo, hi, 5):
                    tickets.append(svc.put_async(texts[i:i + 5]))
            except Exception as e:  # pragma: no cover
                errors.append(e)

        def server_reader():
            try:
                deadline = time.monotonic() + 15
                while time.monotonic() < deadline and len(svc) < len(texts):
                    keys = svc.keys()[-6:]
                    if keys:
                        for ids, key in zip(svc.get_tokens_many(keys), keys):
                            assert content_key(tok.decode(ids)) == key
            except Exception as e:  # pragma: no cover
                errors.append(e)

        producers = [threading.Thread(target=producer, args=(lo, lo + 40))
                     for lo in (0, 40)]
        readers = [threading.Thread(target=server_reader) for _ in range(2)]
        for t in producers + readers:
            t.start()
        for t in producers:
            t.join()
        svc.drain()
        for t in readers:
            t.join()
        for t in tickets:
            t.wait(20)
        assert not errors
        # with warm caches the whole load can finish inside the compactor's
        # first 0.02s tick — give the background thread a bounded window to
        # take its first pass rather than racing it
        deadline = time.monotonic() + 5
        while (time.monotonic() < deadline
               and svc.stats()["compaction"]["compactions"] == 0):
            time.sleep(0.01)
        assert svc.stats()["compaction"]["compactions"] > 0
    assert store.verify_all()["failure"] == 0
    # byte-lossless vs the synchronous reference
    ref = _store(tmp_path / "ref", tok, method="token")
    ref_keys = ref.put_many(texts)
    assert set(store.keys()) == set(ref_keys)
    for key, text in zip(ref_keys, texts):
        assert store.get(key) == ref.get(key) == text
        assert np.array_equal(store.get_tokens(key), ref.get_tokens(key))
    # and the store reopens cleanly after all the generation churn
    reopened = _store(tmp_path, tok)
    assert reopened.verify_all()["failure"] == 0


@pytest.mark.slow
@pytest.mark.concurrency
def test_rebalance_races_ingest_compaction_and_cached_serve(tmp_path, tok):
    """Online rebalances race the async ingest queue, the background
    (dict-training) compactor, and cached `get_tokens` readers on one
    store: no key may be lost, the seq order must be reopen-stable, and
    the TokenCache must never serve an array that does not decode to its
    own content key (content addressing makes staleness structurally
    impossible — this asserts it under the worst interleaving)."""
    store = _store(tmp_path, tok, method="zstd", n_shards=4)
    texts = _texts(120, tag="rebrace", rep=3)
    svc = PromptService(store, cache_bytes=1 << 20, flush_batch=8,
                        flush_interval_s=0.005, compact_interval_s=0.02,
                        compact_trigger_dead_ratio=0.0,
                        compact_min_dead_bytes=0)
    errors: list = []
    tickets: list = []
    with svc:
        def producer(lo, hi):
            try:
                for i in range(lo, hi, 5):
                    tickets.append(svc.put_async(texts[i:i + 5]))
            except Exception as e:  # pragma: no cover
                errors.append(e)

        def cached_reader():
            try:
                deadline = time.monotonic() + 20
                while time.monotonic() < deadline and len(svc) < len(texts):
                    keys = svc.keys()[-6:]
                    if keys:
                        for ids, key in zip(svc.get_tokens_many(keys), keys):
                            assert content_key(tok.decode(ids)) == key
            except Exception as e:  # pragma: no cover
                errors.append(e)

        def rebalancer():
            try:
                for target in (8, 2, 6, 3):
                    time.sleep(0.03)
                    res = svc.rebalance(target)
                    assert res["n_shards_after"] == target
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = ([threading.Thread(target=producer, args=(lo, lo + 40))
                    for lo in (0, 40, 80)]
                   + [threading.Thread(target=cached_reader) for _ in range(2)]
                   + [threading.Thread(target=rebalancer)])
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        svc.drain()
        for t in tickets:
            t.wait(20)
        assert not errors
    assert store.n_shards == 3
    assert len(store) == len(texts)                  # no lost keys
    assert store.verify_all()["failure"] == 0
    reopened = _store(tmp_path, tok)
    assert reopened.keys() == store.keys()           # seq order stable
    assert reopened.n_shards == 3
    by_key = {content_key(t): t for t in texts}
    for key in reopened.keys():
        assert reopened.get(key) == by_key[key]
    assert reopened.verify_all()["failure"] == 0


# -- serve-loop / launcher satellites -----------------------------------------


def test_batch_server_rids_monotonic_across_queue_drain():
    """rid must not recycle after the queue drains (len(queue) did)."""
    from repro.configs.lopace import CONFIG
    from repro.train.serve_loop import BatchServer

    server = BatchServer(None, CONFIG.smoke(), batch_slots=2, max_len=32)
    r0 = server.submit_tokens(np.array([1, 2, 3]))
    r1 = server.submit_tokens(np.array([4, 5]))
    server.queue.clear()                      # simulate a drained queue
    r2 = server.submit_tokens(np.array([6]))
    assert [r0.rid, r1.rid, r2.rid] == [0, 1, 2]


def test_serve_parse_args_rejects_oversized_max_new(capsys):
    from repro.launch import serve

    args = serve.parse_args(["--max-new", "16", "--max-len", "128"])
    assert args.max_new == 16 and args.cache_mb == 0.0
    args = serve.parse_args(["--cache-mb", "32", "--ingest-async", "--compact"])
    assert args.cache_mb == 32.0 and args.ingest_async and args.compact
    serve.parse_args(["--max-new", "126", "--max-len", "128"])  # largest ok
    for max_new in ("127", "128", "500"):  # 127 leaves zero prompt tokens
        with pytest.raises(SystemExit):
            serve.parse_args(["--max-new", max_new, "--max-len", "128"])
    assert "--max-new" in capsys.readouterr().err


def test_build_store_from_corpus_async_matches_sync(tmp_path):
    from repro.data.pipeline import build_store_from_corpus

    sync = build_store_from_corpus(tmp_path / "sync", n_prompts=6, seed=5)
    asyn = build_store_from_corpus(tmp_path / "async", n_prompts=6, seed=5,
                                   async_ingest=True)
    assert asyn.keys() == sync.keys()
    assert asyn.verify_all()["failure"] == 0
