"""The benchmark's readers of the program's ingest-path spans and
histograms (``bench/metrics/<name>.py``), on synthetic obs diffs: the
arithmetic of each, and None where the program has no such span."""

import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
STAGE = "{scheme=fixed,stage=token-pack}"
NEW_READERS = ["codec.bpe_s_per_mb", "codec.pack_s_per_mb", "device.compile_s",
               "ingest.dispatcher_busy_pct", "ingest.queue_wait_ms",
               "ingest.writer_wait_ms"]


@pytest.fixture
def reader(monkeypatch):
    monkeypatch.syspath_prepend(str(BENCH))   # the readers import stats

    def load(name):
        spec = importlib.util.spec_from_file_location(
            "reader_" + name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    return load


def _hist(count, total):
    return {"count_delta": count, "rate_per_s": count / 51.0,
            "mean_in_window": total / count if count else 0.0}


def _ctx(histograms=None, counters=None, seconds=51.0):
    return SimpleNamespace(seconds=seconds, obs={
        "dt_s": seconds, "histograms": histograms or {},
        "counters": {k: {"delta": v, "rate_per_s": v / seconds}
                     for k, v in (counters or {}).items()}, "gauges": {}})


def test_bpe_seconds_per_mb_of_stage_input(reader):
    ctx = _ctx({"codec.bpe.encode.s": _hist(40, 6.0),
                "codec.pack.encode.s": _hist(40, 2.0)},
               {"codec.encode.bytes_in" + STAGE: 30_000_000})
    assert reader("codec.bpe_s_per_mb")(ctx) == pytest.approx(6.0 / 30.0)


def test_pack_seconds_per_mb_of_stage_input(reader):
    ctx = _ctx({"codec.bpe.encode.s": _hist(40, 6.0),
                "codec.pack.encode.s": _hist(40, 2.0)},
               {"codec.encode.bytes_in" + STAGE: 30_000_000})
    assert reader("codec.pack_s_per_mb")(ctx) == pytest.approx(2.0 / 30.0)


def test_compile_seconds_sum_every_function(reader):
    ctx = _ctx({"device.compile.s{fn=jit(_pack_padded)}": _hist(120, 14.5),
                "device.compile.s{fn=jit(_token_histogram)}": _hist(2, 0.25),
                "device.compile.s{fn=jit(idle)}": _hist(0, 0.0),
                "codec.pack.encode.s": _hist(40, 99.0)})
    assert reader("device.compile_s")(ctx) == pytest.approx(14.75)
    # the listener is on but nothing compiled in the window: zero
    quiet = _ctx({"device.compile.s{fn=jit(_pack_padded)}": _hist(0, 0.0)})
    assert reader("device.compile_s")(quiet) == 0.0


def test_dispatcher_busy_share_of_window(reader):
    ctx = _ctx({"ingest.dispatch.s": _hist(150, 45.9)}, seconds=51.0)
    assert reader("ingest.dispatcher_busy_pct")(ctx) == pytest.approx(90.0)


def test_queue_wait_mean_in_ms(reader):
    ctx = _ctx({"ingest.queue.s": _hist(400, 100.0)})
    assert reader("ingest.queue_wait_ms")(ctx) == pytest.approx(250.0)


def test_writer_wait_mean_in_ms(reader):
    ctx = _ctx({"ingest.writer_queue.s": _hist(600, 0.3)})
    assert reader("ingest.writer_wait_ms")(ctx) == pytest.approx(0.5)


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_is_silent_without_the_programs_span(reader, name):
    """A program without these spans (the one before them) reads None,
    which the harness leaves out of the result line, and never raises."""
    ctx = _ctx({"codec.encode.s" + STAGE: _hist(40, 9.0)},
               {"codec.encode.bytes_in" + STAGE: 30_000_000})
    assert reader(name)(ctx) is None
