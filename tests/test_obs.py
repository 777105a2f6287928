"""repro.obs: log2-bucket histogram vs a NumPy oracle, multi-thread
hammering under the lock sanitizer, disabled-mode no-op identity,
snapshot/diff round-trips, spans in the JAX profiler's trace (with the
trace context's ids, across the ingest queue's threads), the compile
listener, owned-counter stats() compatibility, and an end-to-end
BatchServer run that must land real ms/token samples in the serve
histograms."""

import dataclasses
import json
import threading
import warnings

import numpy as np
import pytest

from repro import obs
from repro.obs import export
from repro.obs.metrics import (EXP_MAX, EXP_MIN, N_BUCKETS, Counter,
                               Histogram, bucket_index, bucket_mid,
                               canonical_name)


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    """Enabled obs against a private registry per test."""
    monkeypatch.delenv("REPRO_OBS", raising=False)
    obs.reset()
    yield
    obs.reset()


def _host_events(trace_dir, fn):
    """Run ``fn`` under a JAX profiler trace; the trace's host-plane
    events as ``[(name, {stat: value})]`` in start order."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(trace_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = sorted(trace_dir.rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    with warnings.catch_warnings():
        # jaxlib's stats type warns that it has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        events = [(ev.start_ns, ev.name, dict(ev.stats))
                  for plane in data.planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events]
    return [(name, stats) for _, name, stats in sorted(events,
                                                       key=lambda e: e[0])]


# ---------------------------------------------------------------------------
# histogram vs NumPy oracle
# ---------------------------------------------------------------------------

def _oracle_bucket(v: float) -> int:
    if v <= 0.0:
        return 0
    _, e = np.frexp(np.float64(v))
    return int(np.clip(e, EXP_MIN, EXP_MAX)) - EXP_MIN + 1


def test_bucket_index_matches_numpy_frexp():
    rng = np.random.default_rng(0)
    vals = np.concatenate([
        rng.lognormal(0.0, 6.0, 500),          # ~e^-20 .. e^20
        [0.0, -1.0, 1e-300, 1e300, 0.5, 1.0, 2.0, 4.0 - 1e-12],
    ])
    for v in vals:
        assert bucket_index(float(v)) == _oracle_bucket(float(v))
    assert bucket_index(0.0) == 0
    assert 0 <= bucket_index(1e300) < N_BUCKETS


def test_histogram_counts_match_numpy_bincount():
    rng = np.random.default_rng(1)
    vals = rng.lognormal(-2.0, 3.0, 2000)
    h = Histogram("t")
    for v in vals:
        h.observe(float(v))
    oracle = np.bincount([_oracle_bucket(float(v)) for v in vals],
                         minlength=N_BUCKETS)
    assert h.snapshot()["count"] == 2000
    snap = h.snapshot()["buckets"]
    dense = np.zeros(N_BUCKETS, dtype=np.int64)
    for key, n in snap.items():
        idx = 0 if key == "zero" else int(key) - EXP_MIN + 1
        dense[idx] = n
    assert np.array_equal(dense, oracle)


def test_histogram_stats_vs_numpy():
    rng = np.random.default_rng(2)
    vals = rng.lognormal(0.0, 2.0, 5000)
    h = Histogram("t")
    for v in vals:
        h.observe(float(v))
    s = h.snapshot()
    assert s["mean"] == pytest.approx(float(vals.mean()), rel=1e-9)
    assert s["min"] == pytest.approx(float(vals.min()))
    assert s["max"] == pytest.approx(float(vals.max()))
    # log2 buckets bound any percentile to a factor of 2 of the truth
    for q in (50, 90, 99):
        truth = float(np.percentile(vals, q))
        got = h.percentile(q)
        assert truth / 2 <= got <= truth * 2, (q, got, truth)


def test_histogram_zero_and_negative_land_in_zero_bucket():
    h = Histogram("t")
    h.observe(0.0)
    h.observe(-3.0)
    s = h.snapshot()
    assert s["buckets"] == {"zero": 2}
    assert h.percentile(50) == 0.0


def test_bucket_mid_is_inside_its_bucket():
    for v in (1e-9, 0.37, 1.0, 17.3, 4096.0):
        i = bucket_index(v)
        mid = bucket_mid(i)
        assert bucket_index(mid) == i


# ---------------------------------------------------------------------------
# thread safety (sanitizer enabled via the concurrency marker)
# ---------------------------------------------------------------------------

@pytest.mark.concurrency
def test_threaded_hammer_exact_totals():
    c = obs.counter("hammer.count")
    h = obs.histogram("hammer.lat")
    g = obs.gauge("hammer.level")
    n_threads, per = 8, 10_000

    def work(seed):
        for i in range(per):
            c.inc()
            h.observe(float((seed * per + i) % 97) + 0.5)
            g.set(float(i))

    ts = [threading.Thread(target=work, args=(s,)) for s in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per
    assert h.count == n_threads * per
    assert h.sum == pytest.approx(sum(
        float((s * per + i) % 97) + 0.5
        for s in range(n_threads) for i in range(per)))


@pytest.mark.concurrency
def test_threaded_snapshot_while_writing():
    h = obs.histogram("race.lat")
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            h.observe(float(i % 13) + 1.0)
            i += 1

    t = threading.Thread(target=writer)
    t.start()
    try:
        for _ in range(50):
            snap = obs.snapshot()
            hs = snap["histograms"].get("race.lat")
            if hs:
                assert hs["count"] == sum(hs["buckets"].values())
    finally:
        stop.set()
        t.join()


# ---------------------------------------------------------------------------
# disabled mode
# ---------------------------------------------------------------------------

def test_disabled_factories_return_shared_noops(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "0")
    assert not obs.enabled()
    assert obs.counter("a") is obs.counter("b") is obs.NULL_COUNTER
    assert obs.histogram("a") is obs.NULL_HISTOGRAM
    assert obs.gauge("a") is obs.derived_gauge("b", lambda: 1.0) \
        is obs.NULL_GAUGE
    obs.counter("a").inc(5)
    obs.histogram("a").observe(1.0)
    obs.gauge("a").set(3.0)
    assert obs.default_registry().names() == []
    snap = obs.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}


def test_disabled_span_still_times(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "0")
    with obs.span("x.y") as sp:
        assert sp.elapsed_s >= 0.0
    assert sp.duration_s >= 0.0
    assert obs.default_registry().names() == []


def test_importing_obs_does_not_import_jax():
    """Spans reach the profiler only through an already-loaded JAX, so
    CPU-only tools import ``repro.obs`` without it."""
    import subprocess
    import sys

    code = ("import sys, repro.obs, repro.obs.trace\n"
            "with repro.obs.span('no.jax'):\n    pass\n"
            "sys.exit('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_disabled_span_opens_no_annotation(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_OBS", "0")

    def work():
        with obs.trace_context(flush=1), obs.span("off.probe"):
            pass

    names = [name for name, _ in _host_events(tmp_path, work)]
    assert "off.probe" not in names


def test_disabled_owned_counter_still_counts(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "0")
    c = obs.owned_counter("cache.hits")
    c.inc(3)
    assert c.value == 3                       # stats() stays accurate
    assert obs.default_registry().names() == []  # but nothing exported


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_get_or_create_shares_by_name_and_labels():
    a = obs.counter("x", method="m")
    b = obs.counter("x", method="m")
    assert a is b
    assert obs.counter("x", method="other") is not a
    assert canonical_name("x", {"b": 1, "a": 2}) == "x{a=2,b=1}"


def test_kind_mismatch_raises():
    obs.counter("x")
    with pytest.raises(ValueError):
        obs.histogram("x")


def test_owned_counter_replace_follows_newest_instance():
    first = obs.owned_counter("cache.hits")
    first.inc(7)
    second = obs.owned_counter("cache.hits")  # new component instance
    second.inc(2)
    assert obs.snapshot()["counters"]["cache.hits"] == 2
    assert first.value == 7                   # old instance keeps working


def test_owned_gauge_replace_follows_newest_instance():
    obs.owned_gauge("cache.hit_rate", lambda: 0.25)
    obs.owned_gauge("cache.hit_rate", lambda: 0.75)
    assert obs.snapshot()["gauges"]["cache.hit_rate"] == 0.75


# ---------------------------------------------------------------------------
# spans in the profiler's trace
# ---------------------------------------------------------------------------

def test_span_records_histogram_and_journal(tmp_path):
    """A span's journal is the profiler's trace: one histogram sample,
    and one host event named as the span with its labels and
    ``trace_args`` as stats (which stay out of the histogram's name)."""
    box = {}

    def work():
        with obs.span("unit.op", trace_args={"ids": 12}, method="m") as sp:
            pass
        box["sp"] = sp

    events = _host_events(tmp_path, work)
    assert box["sp"].duration_s >= 0.0
    hists = obs.snapshot()["histograms"]
    assert [n for n in hists if n.startswith("unit.op")] \
        == ["unit.op.s{method=m}"]
    assert hists["unit.op.s{method=m}"]["count"] == 1
    (stats,) = [st for name, st in events if name == "unit.op"]
    assert stats == {"method": "m", "ids": 12}


def test_span_records_error_type(tmp_path):
    def work():
        with pytest.raises(RuntimeError):
            with obs.span("unit.boom"):
                raise RuntimeError("nope")

    events = _host_events(tmp_path, work)
    (stats,) = [st for name, st in events if name == "unit.boom"]
    assert stats["error"] == "RuntimeError"
    assert obs.snapshot()["histograms"]["unit.boom.s"]["count"] == 1


def test_span_event_carries_trace_context(tmp_path):
    """The thread's trace context stamps every span under it, nests, and
    stays on its thread."""
    def other_thread():
        with obs.span("ctx.other"):
            pass

    def work():
        with obs.trace_context(flush=7):
            with obs.span("ctx.outer"):
                with obs.trace_context(shard=2):
                    with obs.span("ctx.inner"):
                        pass
                t = threading.Thread(target=other_thread)
                t.start()
                t.join(10)
                assert not t.is_alive()
        with obs.span("ctx.after"):
            pass

    stats = dict(_host_events(tmp_path, work))
    assert stats["ctx.outer"] == {"flush": 7}
    assert stats["ctx.inner"] == {"flush": 7, "shard": 2}
    assert stats["ctx.other"] == {}
    assert stats["ctx.after"] == {}


def test_ingest_flush_spans_share_the_flush_id(tmp_path):
    """One group commit's spans carry one ``flush`` id on the dispatcher
    (``ingest.dispatch``, ``store.plan``, the codec's) and on the
    writers (``store.commit``, with its shard)."""
    from repro.core.api import PromptCompressor
    from repro.core.store import ShardedPromptStore
    from repro.service import IngestQueue
    from repro.tokenizer.vocab import default_tokenizer

    store = ShardedPromptStore(
        tmp_path / "store", PromptCompressor(default_tokenizer(),
                                             method="hybrid"), n_shards=2)
    texts = [f"flush span probe {i} " * 30 for i in range(6)]

    def work():
        with IngestQueue(store, flush_batch=3) as q:
            for i in range(0, 6, 3):
                q.submit(texts[i:i + 3]).wait(30)

    events = _host_events(tmp_path / "trace", work)
    store.close()
    dispatch = [st for name, st in events if name == "ingest.dispatch"]
    assert [st["flush"] for st in dispatch] == [0, 1]
    assert all(st["prompts"] == 3 for st in dispatch)
    for name in ("store.plan", "codec.bpe.encode", "codec.pack.encode"):
        assert sorted(st["flush"] for n, st in events if n == name) \
            == [0, 1], name
    commits = [st for name, st in events if name == "store.commit"]
    assert commits and {st["flush"] for st in commits} == {0, 1}
    assert all(st["shard"] in (0, 1) for st in commits)


# ---------------------------------------------------------------------------
# snapshot / diff round-trip
# ---------------------------------------------------------------------------

def test_snapshot_diff_roundtrip_through_json():
    c = obs.counter("req.count")
    h = obs.histogram("req.lat")
    obs.derived_gauge("req.ratio", lambda: 2.5)
    c.inc(3)
    h.observe(0.5)
    before = json.loads(json.dumps(obs.snapshot()))
    c.inc(7)
    h.observe(1.5)
    h.observe(2.5)
    after = json.loads(json.dumps(obs.snapshot()))

    d = obs.diff(before, after)
    assert d["counters"]["req.count"]["delta"] == 7
    assert d["counters"]["req.count"]["rate_per_s"] >= 0.0
    assert d["histograms"]["req.lat"]["count_delta"] == 2
    assert after["gauges"]["req.ratio"] == 2.5

    text = obs.render(after) + obs.render_diff(d)
    for needle in ("req.count", "req.lat", "req.ratio"):
        assert needle in text


def test_derived_gauge_error_reads_zero():
    obs.derived_gauge("bad.ratio", lambda: 1 / 0)
    assert obs.snapshot()["gauges"]["bad.ratio"] == 0.0


def test_snapshot_version_and_shape():
    snap = obs.snapshot()
    assert snap["version"] == export.SNAPSHOT_VERSION
    assert set(snap) >= {"version", "ts", "counters", "gauges", "histograms"}
    with obs.span("shape.probe"):
        pass
    snap = obs.snapshot()
    assert set(snap) == {"version", "ts", "counters", "gauges", "histograms"}
    assert snap["histograms"]["shape.probe.s"]["count"] == 1
    assert "histogram shape.probe.s count=1" in obs.render(snap)


# ---------------------------------------------------------------------------
# component integration
# ---------------------------------------------------------------------------

def test_token_cache_stats_keys_on_registry():
    from repro.service.cache import TokenCache

    cache = TokenCache(1 << 20)
    cache.put("k", np.arange(8, dtype=np.int64))
    cache.get("k")
    cache.get("absent")
    cache.invalidate("k")
    cache.clear()
    st = cache.stats()
    # pre-obs keys, byte-compatible + the two new lifecycle counters
    assert set(st) == {"capacity_bytes", "bytes", "entries", "hits",
                       "misses", "evictions", "oversize_rejects",
                       "invalidations", "clears", "hit_rate"}
    assert st["hits"] == 1 and st["misses"] == 1
    assert st["invalidations"] == 1 and st["clears"] == 1
    snap = obs.snapshot()
    assert snap["counters"]["cache.hits"] == 1
    assert snap["gauges"]["cache.hit_rate"] == pytest.approx(0.5)


def test_codec_pipeline_gauges_track_traffic():
    from repro.core.codec import method_pipeline
    from repro.tokenizer.vocab import default_tokenizer

    codec = method_pipeline("hybrid", default_tokenizer())
    payloads = [("sample text for the obs layer %d " % i * 40).encode()
                for i in range(4)]
    enc = codec.encode_batch(payloads)
    assert codec.decode_batch(enc) == payloads
    snap = obs.snapshot()
    assert snap["counters"]["codec.encode.bytes_in{method=hybrid}"] \
        == sum(len(p) for p in payloads)
    assert snap["gauges"]["codec.compression_ratio{method=hybrid}"] > 1.0
    # MB = 10**6 bytes, the paper's unit
    enc_s = snap["histograms"]["codec.encode.s{method=hybrid}"]
    assert snap["gauges"]["codec.encode_mb_s{method=hybrid}"] \
        == pytest.approx(sum(len(p) for p in payloads) / 1e6
                         / (enc_s["mean"] * enc_s["count"]))
    dec_s = snap["histograms"]["codec.decode.s{method=hybrid}"]
    assert snap["gauges"]["codec.decode_mb_s{method=hybrid}"] \
        == pytest.approx(sum(len(p) for p in payloads) / 1e6
                         / (dec_s["mean"] * dec_s["count"]))


def test_token_pack_stage_splits_into_bpe_and_pack_spans():
    """``codec.bpe.encode`` and ``codec.pack.encode`` each take one
    sample per batch and together lie inside the stage's own time."""
    from repro.core.codec import TokenPackCodec
    from repro.tokenizer.vocab import default_tokenizer

    codec = TokenPackCodec(default_tokenizer())
    for i in range(3):
        codec.encode_batch([(f"split the stage {i} " * 50).encode()] * 2)
    h = obs.snapshot()["histograms"]
    stage = h["codec.encode.s{scheme=fixed,stage=token-pack}"]
    bpe, pack = h["codec.bpe.encode.s"], h["codec.pack.encode.s"]
    assert stage["count"] == bpe["count"] == pack["count"] == 3
    assert bpe["sum"] + pack["sum"] <= stage["sum"]


def test_pack_kernel_span_carries_each_launch_ids(tmp_path):
    """``kernel.token_pack`` opens once per width-group launch, with the
    group's real id count, its padded launch length and width as stats."""
    from repro.kernels import size_bucket
    from repro.kernels.token_pack import pack_fixed_batch_device

    ids = [np.arange(300, dtype=np.uint32), np.arange(50, dtype=np.uint32),
           np.array([70_000, 1], dtype=np.uint32)]
    events = _host_events(tmp_path,
                          lambda: pack_fixed_batch_device(ids, interpret=True))
    launches = sorted((st["width"], st["ids"], st["padded"])
                      for name, st in events if name == "kernel.token_pack")
    assert [(w, n) for w, n, _ in launches] == [(2, 350), (4, 2)]
    assert all(p == size_bucket(n, 2048) for _, n, p in launches)
    assert obs.snapshot()["histograms"]["kernel.token_pack.s"]["count"] == 2


def test_fresh_compile_lands_in_device_compile_histogram():
    """From the first device routing decision on, a backend compile adds
    one sample under its jitted function's name."""
    import jax
    import jax.numpy as jnp

    from repro.core import device

    assert device.use_device(1, "REPRO_PACK_DEVICE_MIN", 0, force=True)

    def obs_compile_probe(x):
        return x * 3 + 1

    f = jax.jit(obs_compile_probe)
    name = "device.compile.s{fn=jit(obs_compile_probe)}"
    assert name not in obs.snapshot()["histograms"]
    f(jnp.arange(5)).block_until_ready()
    f(jnp.arange(5)).block_until_ready()          # cached: no compile
    h = obs.snapshot()["histograms"][name]
    assert h["count"] == 1 and h["sum"] > 0.0


def test_serve_loop_ms_per_token_histograms():
    """BatchServer fills serve.prefill/decode ms_per_token with real,
    nonzero samples end-to-end (paper serving-latency accounting)."""
    import jax

    from repro.configs.lopace import CONFIG as LOPACE_CONFIG
    from repro.train.serve_loop import BatchServer
    from repro.train.train_loop import init_train_state

    cfg = dataclasses.replace(LOPACE_CONFIG.smoke(), vocab_size=512,
                              name="obs-serve")
    params, _ = init_train_state(jax.random.PRNGKey(0), cfg)
    server = BatchServer(params, cfg, batch_slots=2, max_len=64)
    rng = np.random.default_rng(3)
    reqs = [server.submit_tokens(
        rng.integers(0, cfg.vocab_size, size=12).astype(np.int64),
        max_new_tokens=4) for _ in range(3)]
    server.run(max_steps=200)
    assert all(r.done for r in reqs)

    snap = obs.snapshot()
    prefill = snap["histograms"]["serve.prefill.ms_per_token"]
    decode = snap["histograms"]["serve.decode.ms_per_token"]
    assert prefill["count"] == 3            # one sample per filled slot
    assert decode["count"] >= 4             # one per wave step
    for hs in (prefill, decode):
        assert hs["p50"] > 0.0 and hs["p99"] >= hs["p50"] > 0.0
        assert hs["mean"] > 0.0
    assert snap["counters"]["serve.decode.tokens"] \
        == sum(len(r.out_tokens) for r in reqs)
