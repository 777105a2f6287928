"""Tests of the benchmark itself, off the chip, at a tiny size.

    python -m pytest -q bench/tests        # from the repository root

Each run is a child process (``probe.py --no-chip``): the check runs in
full, and no metric is printed.  A sound run is correct; the control and
every fault a cell can have make it not correct.  The repository's own
test suite does not collect these (its ``testpaths`` is ``tests``).
The deployment test runs in this process and builds nothing: it records
the arguments ``deploy.py`` hands the program's constructors.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
#: each kind of traffic at a tiny size
TINY = {"closed_batch": ["--set", "traffic.pool_per_client=4",
                         "--set", "traffic.warmup_s=1.0",
                         "--set", "traffic.readback_sample=6"],
        "open_mix": ["--set", "traffic.records=48",
                     "--set", "traffic.rate_per_s=20",
                     "--set", "traffic.warm_min_chars=20000",
                     "--set", "traffic.warmup_s=1.0",
                     "--set", "traffic.readback_sample=6"]}
SEED = 2_200_000_017           # larger than 32 signed bits hold
#: the checks every kind is held to, in the result's order, then each
#: kind's own
PUT_CHECKS = ["lost", "errors", "wrong_keys", "not_durable_at_ack",
              "acked_missing", "readback_ids_bad", "readback_text_bad"]
KIND_CHECKS = {"closed_batch": [],
               "open_mix": ["get_tokens_bad", "get_unknown_key"]}


def _env() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def probe(cell: str, *extra: str, root: Path = ROOT,
          seconds: float = 2.5, tiny: str = "") -> dict:
    out = subprocess.run(
        [sys.executable, str(root / "bench" / "probe.py"), "--workload",
         cell, "--seed", str(SEED), "--seconds", str(seconds), "--no-chip",
         *TINY[tiny or _kind(cell)], *extra],
        cwd=root, env=_env(), capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _doc() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _cells():
    return [w["name"] for w in _doc()["workloads"]]


def _copy(dest: Path, doc: dict) -> Path:
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns(".work", ".jax_cache",
                                                  "__pycache__"))
    os.symlink(ROOT / "src", dest / "src")
    (dest / "BENCHMARK.json").write_text(json.dumps(doc))
    return dest


def _kind(cell: str) -> str:
    (w,) = [w for w in _doc()["workloads"] if w["name"] == cell]
    return json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                      .read_text())["kind"]


#: the faults each kind of traffic can show, and the number each has to
#: fail: a state left unchanged, half a batch left out, a token altered
#: where it is produced (and, where the kind reads, where it is read),
#: the durable acknowledgement broken
PUT_FAULTS = {"no_write": "acked_missing", "half": "acked_missing",
              "token_put": "readback_ids_bad", "no_fsync": "acked_missing",
              "late_fsync": "not_durable_at_ack"}
FAULTS = {"closed_batch": PUT_FAULTS,
          "open_mix": dict(PUT_FAULTS, token_get="get_tokens_bad")}


@pytest.mark.parametrize("cell", _cells())
def test_sound_run_is_correct(cell):
    r = probe(cell)
    assert r["correct"], r["checks"]
    assert r["metrics"] == {}, "a rehearsal off the chip prints no metric"
    assert r["attempted"] > 0
    assert list(r["checks"]) == PUT_CHECKS + KIND_CHECKS[_kind(cell)]


@pytest.mark.parametrize("cell", _cells())
def test_control_is_not_correct(cell):
    # the control breaks the prompts that carry special markers (1 in 9):
    # read back enough that some are among them
    r = probe(cell, "--fault", "pack16", "--set", "traffic.readback_sample=40")
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in _cells() for f in FAULTS[_kind(c)]])
def test_fault_is_not_correct(cell, fault):
    r = probe(cell, "--fault", fault)
    assert not r["correct"], r["checks"]
    assert r["checks"][FAULTS[_kind(cell)][fault]]["value"] > 0, r["checks"]


def test_run_refuses_the_cpu():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", _cells()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""


def test_run_refuses_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".work", ".jax_cache",
                                                  "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", _cells()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""


#: a kind added as a file: the backfill loop, with a set-up hook that
#: pings the gateway and a check hook that counts what it saw
SCRATCH_KIND = '''
import asyncio

import spec
import wire

_base = spec.kind_module("closed_batch")
prepare, drive = _base.prepare, _base.drive
put_text, put_chars = _base.put_text, _base.put_chars


async def _ping(port):
    conn = await wire.Connection.open(port)
    resp, sent, done = await conn.call("ping")
    await conn.close()
    return {"op": "ping", "due": sent, "sent": sent, "done": done,
            "status": "ok" if resp and resp.get("ok") else "error"}


def setup(params, config, seed, seconds, port):
    return {"records": [asyncio.run(_ping(port))], "go": {"pinged": True}}


def check(ctx, encoder):
    pings = [r for r in ctx.records if r["op"] == "ping"]
    return {"scratch_pings_bad": sum(r["status"] != "ok" for r in pings)
            + abs(len(pings) - 1),
            "scratch_encoder_bad": int(not encoder.encode("scratch"))}
'''

#: a reference added as a file: the hybrid one, leaving a mark when used
SCRATCH_REFERENCE = '''
from pathlib import Path

import spec

_base = spec.load_module(spec.BENCH / "reference" / "hybrid.py")


def encoder(config):
    Path(__file__).with_suffix(".used").touch()
    return _base.encoder(config)
'''


def test_new_cell_is_data_only(tmp_path):
    """A configuration (with program options and a reference of its own),
    a traffic mix and a kind with both hooks, added as files, and a cell
    added as an entry, run with no edit to any other file."""
    doc = _doc()
    doc["configs"].append({"name": "scratch-cfg", "source": "test",
                           "file": "bench/configs/scratch-cfg.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "scratch-cfg.scratch-mix",
                             "config": "scratch-cfg", "traffic": "scratch-mix",
                             "chips": 1, "why": "test"})
    root = _copy(tmp_path, doc)
    cfg = json.loads((BENCH / "configs" / "hybrid-zstd.json").read_text())
    cfg.update(shards=2, flush_batch=8, reference="scratch-ref",
               program={"service": {"max_pending": 512},
                        "gateway": {"frame_max": 1 << 26}})
    (root / "bench" / "configs" / "scratch-cfg.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "reference" / "scratch-ref.py").write_text(
        SCRATCH_REFERENCE)
    (root / "bench" / "traffic" / "kinds" / "scratch_kind.py").write_text(
        SCRATCH_KIND)
    mix = json.loads((BENCH / "traffic" / "ingest.json").read_text())
    mix.update(batch=4, kind="scratch_kind")
    (root / "bench" / "traffic" / "scratch-mix.json").write_text(
        json.dumps(mix))
    r = probe("scratch-cfg.scratch-mix", root=root, tiny="closed_batch")
    assert r["correct"], r["checks"]
    assert list(r["checks"]) == PUT_CHECKS + ["scratch_pings_bad",
                                              "scratch_encoder_bad"]
    assert (root / "bench" / "reference" / "scratch-ref.used").exists()


#: today's arguments of each constructor, for the accepted configurations
TODAY = {
    "hybrid-zstd": {"method": "hybrid", "backend": "zstd", "level": 15,
                    "n_shards": 4, "cache_bytes": 33554432, "flush_batch": 64,
                    "flush_interval_s": 0.05, "max_inflight": 64,
                    "conn_window": 8},
    "hybrid-lzr": {"method": "hybrid", "backend": "repro-lzr", "level": 15,
                   "n_shards": 4, "cache_bytes": 33554432, "flush_batch": 64,
                   "flush_interval_s": 0.05, "max_inflight": 64,
                   "conn_window": 8}}


@pytest.mark.parametrize("config", ["hybrid-zstd", "hybrid-lzr", "program"])
def test_deploy_passes_the_constructors_their_arguments(config, monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT / "src"))
    monkeypatch.syspath_prepend(str(BENCH))
    import deploy
    import repro.core.api
    import repro.core.store
    import repro.service
    import repro.service.gateway

    calls = []

    def recorder(name):
        def build(*args, **kwargs):
            calls.append((name, args, kwargs))
            return name
        return build

    for module, attr in ((repro.core.api, "PromptCompressor"),
                         (repro.core.store, "ShardedPromptStore"),
                         (repro.service, "PromptService"),
                         (repro.service.gateway, "start_in_thread")):
        monkeypatch.setattr(module, attr, recorder(attr))
    name = "hybrid-lzr" if config == "program" else config
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    extra = {"compressor": {}, "store": {}, "service": {}, "gateway": {}}
    if config == "program":
        extra = {"compressor": {"scheme": "x"}, "store": {"lease": None},
                 "service": {"max_pending": 7}, "gateway": {"drain_s": 1.0}}
        cfg["program"] = extra
    comp = deploy.compressor(cfg, "tokenizer")
    deploy.store(cfg, "root", comp)
    deploy.store(cfg, "root", comp, readonly=True)
    deploy.gateway(cfg, deploy.service(cfg, "store"))
    t = TODAY[name]
    assert calls == [
        ("PromptCompressor", ("tokenizer",),
         dict(method=t["method"], backend=t["backend"], level=t["level"],
              **extra["compressor"])),
        ("ShardedPromptStore", ("root", "PromptCompressor"),
         dict(n_shards=t["n_shards"], **extra["store"])),
        ("ShardedPromptStore", ("root", "PromptCompressor"),
         dict(readonly=True, **extra["store"])),
        ("PromptService", ("store",),
         dict(cache_bytes=t["cache_bytes"], flush_batch=t["flush_batch"],
              flush_interval_s=t["flush_interval_s"], **extra["service"])),
        ("start_in_thread", ("PromptService",),
         dict(max_inflight=t["max_inflight"], conn_window=t["conn_window"],
              **extra["gateway"]))]
    with pytest.raises(ValueError, match="program"):
        deploy.service(dict(cfg, program={"router": {}}), "store")


def test_trace_reduction_on_the_recorded_chip_trace():
    out = subprocess.run([sys.executable, str(BENCH / "selfcheck.py")],
                         cwd=ROOT, env=_env(), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
