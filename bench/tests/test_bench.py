"""Tests of the benchmark itself, off the chip, at a tiny size.

    python -m pytest -q bench/tests        # from the repository root

Each run is a child process (``probe.py --no-chip``): the check runs in
full, and no metric is printed.  A sound run is correct; the control and
every fault a cell can have make it not correct.  What these tests need
of a cell, its kind declares in its own module (``REHEARSAL``, ``CHECKS``,
``FAULTS``), so a cell added as files and an entry is tested with no edit
here.  The repository's own test suite does not collect these (its
``testpaths`` is ``tests``).  The deployment and fault-undo tests run in
this process and build nothing.
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
sys.path[:0] = [str(BENCH), str(BENCH / "traffic")]

import spec  # noqa: E402

SEED = 2_200_000_017           # larger than 32 signed bits hold
#: the checks every kind is held to, in the result's order; a kind's own
#: (its ``CHECKS``) follow them
PUT_CHECKS = ["lost", "errors", "wrong_keys", "not_durable_at_ack",
              "acked_missing", "readback_ids_bad", "readback_text_bad"]
#: the faults every kind can show, and the number each has to fail: a
#: state left unchanged, half a batch left out, a token altered where it
#: is produced, the durable acknowledgement broken; a kind adds its own
#: (its ``FAULTS``: a token altered where it is read, say)
PUT_FAULTS = {"no_write": "acked_missing", "half": "acked_missing",
              "token_put": "readback_ids_bad", "no_fsync": "acked_missing",
              "late_fsync": "not_durable_at_ack"}


def _env() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def _doc() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _cells():
    return [w["name"] for w in _doc()["workloads"]]


def _kind(cell: str):
    """The traffic kind's module of a cell, which declares what these
    tests need of it."""
    return spec.kind_module(spec.cell(cell).traffic["kind"])


def _faults(cell: str) -> dict:
    # read while the module is collected: a kind that declares no FAULTS
    # fails test_kind_declares_what_the_tests_need, not the collection
    return dict(PUT_FAULTS, **getattr(_kind(cell), "FAULTS", {}))


def probe(cell: str, *extra: str, seconds: float = 2.5) -> dict:
    tiny = [arg for key, value in _kind(cell).REHEARSAL.items()
            for arg in ("--set", f"traffic.{key}={json.dumps(value)}")]
    out = subprocess.run(
        [sys.executable, str(BENCH / "probe.py"), "--workload", cell,
         "--seed", str(SEED), "--seconds", str(seconds), "--no-chip",
         *tiny, *extra],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _copy(dest: Path, doc: dict) -> Path:
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns(".work", ".jax_cache",
                                                  "__pycache__"))
    os.symlink(ROOT / "src", dest / "src")
    (dest / "BENCHMARK.json").write_text(json.dumps(doc))
    return dest


@pytest.mark.parametrize("cell", _cells())
def test_kind_declares_what_the_tests_need(cell):
    kind = _kind(cell)
    assert isinstance(kind.REHEARSAL, dict) and kind.REHEARSAL
    assert isinstance(kind.CHECKS, list)
    assert isinstance(kind.FAULTS, dict)
    for fault, check in kind.FAULTS.items():
        assert (BENCH / "faults" / f"{fault}.py").is_file(), fault
        assert check in PUT_CHECKS + kind.CHECKS, (fault, check)


@pytest.mark.parametrize("cell", _cells())
def test_sound_run_is_correct(cell):
    r = probe(cell)
    assert r["correct"], r["checks"]
    assert r["metrics"] == {}, "a rehearsal off the chip prints no metric"
    assert r["attempted"] > 0
    assert list(r["checks"]) == PUT_CHECKS + _kind(cell).CHECKS


@pytest.mark.parametrize("cell", _cells())
def test_control_is_not_correct(cell):
    # the control breaks the prompts that carry special markers (1 in 9):
    # read back enough that some are among them
    r = probe(cell, "--fault", "pack16", "--set", "traffic.readback_sample=40")
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in _cells() for f in _faults(c)])
def test_fault_is_not_correct(cell, fault):
    r = probe(cell, "--fault", fault)
    assert not r["correct"], r["checks"]
    assert r["checks"][_faults(cell)[fault]]["value"] > 0, r["checks"]


#: what each fault patched when it was one function of one module, and
#: patches still
PATCHED = {
    "pack16": [("repro.core.codec", "TokenPackCodec.encode_ids_batch")],
    "no_write": [("repro.core.store", "ShardedPromptStore.commit_batch")],
    "half": [("repro.core.store", "ShardedPromptStore.commit_batch")],
    "token_put": [("repro.core.codec", "TokenPackCodec.encode_ids_batch")],
    "token_get": [("repro.core.codec", "TokenPackCodec.decode_ids_batch")],
    "no_fsync": [("repro.core.store", "fsync_file")],
    "late_fsync": [("repro.core.store", "fsync_file")]}


@pytest.mark.parametrize("fault", sorted(
    p.stem for p in (BENCH / "faults").glob("*.py") if p.stem != "__init__"))
def test_fault_undone_leaves_what_it_patched(fault, monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT / "src"))
    import faults

    patched = []
    patch = faults.patch

    def recording(module, attr, make):
        owner, name = faults.target(module, attr)
        patched.append((module, attr, owner, name, getattr(owner, name)))
        return patch(module, attr, make)

    monkeypatch.setattr(faults, "patch", recording)
    undo = faults.apply(fault)
    assert patched, "a fault patches the program"
    for _, _, owner, name, orig in patched:
        assert getattr(owner, name) is not orig
    undo()
    for _, _, owner, name, orig in patched:
        assert getattr(owner, name) is orig
    if fault in PATCHED:
        assert [p[:2] for p in patched] == PATCHED[fault]


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
#: pings the gateway and a check hook that counts what it saw, declaring
#: its own rehearsal size, checks and fault
SCRATCH_KIND = '''
import asyncio

import spec
import wire

_base = spec.kind_module("closed_batch")
prepare, drive = _base.prepare, _base.drive
put_text, put_chars = _base.put_text, _base.put_chars

REHEARSAL = {"pool_per_client": 3, "warmup_s": 1.0, "readback_sample": 6}
CHECKS = ["scratch_pings_bad", "scratch_encoder_bad"]
FAULTS = {"scratch_fault": "scratch_pings_bad"}


async def _ping(port):
    conn = await wire.Connection.open(port)
    resp, sent, done = await conn.call("ping")
    await conn.close()
    return {"op": "ping", "due": sent, "sent": sent, "done": done,
            "status": "ok" if resp and resp.get("ok") else "error",
            "pong": bool(resp and resp.get("pong"))}


def setup(params, config, seed, seconds, port):
    return {"records": [asyncio.run(_ping(port))], "go": {"pinged": True}}


def check(ctx, encoder):
    pings = [r for r in ctx.records if r["op"] == "ping"]
    return {"scratch_pings_bad": sum(r["status"] != "ok" or not r["pong"]
                                     for r in pings) + abs(len(pings) - 1),
            "scratch_encoder_bad": int(not encoder.encode("scratch"))}
'''

#: a fault added as a file: the gateway's ping answered, but wrongly
SCRATCH_FAULT = '''
import faults


def make(orig):
    def _op_ping(self, req):
        return dict(orig(self, req), pong=False)
    return _op_ping


def apply():
    return faults.patch("repro.service.gateway", "GatewayServer._op_ping",
                        make)
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
    a traffic mix, a kind with both hooks and its own rehearsal size,
    checks and fault, and that fault, added as files, and a cell added as
    an entry: the copied tree's own tests collect, and its sound run, its
    control and every fault it declares pass them, with no edit to any
    file that was there."""
    doc = _doc()
    doc["configs"].append({"name": "scratch-cfg", "source": "test",
                           "file": "bench/configs/scratch-cfg.json",
                           "reduced": [], "why": "test"})
    cell = "scratch-cfg.scratch-mix"
    doc["workloads"].append({"name": cell, "config": "scratch-cfg",
                             "traffic": "scratch-mix", "chips": 1,
                             "why": "test"})
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
    (root / "bench" / "faults" / "scratch_fault.py").write_text(SCRATCH_FAULT)
    mix = json.loads((BENCH / "traffic" / "ingest.json").read_text())
    mix.update(batch=4, kind="scratch_kind")
    (root / "bench" / "traffic" / "scratch-mix.json").write_text(
        json.dumps(mix))
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:randomly", "bench/tests", "-k", "scratch"],
        cwd=root, env=_env(), capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stdout[-5000:] + out.stderr[-2000:]
    ran = {line.split(" ")[0].split("::")[-1] for line in
           out.stdout.splitlines() if line.endswith("PASSED")
           or " PASSED " in line}
    faults = dict(PUT_FAULTS, scratch_fault="scratch_pings_bad")
    assert ran == {f"test_kind_declares_what_the_tests_need[{cell}]",
                   f"test_sound_run_is_correct[{cell}]",
                   f"test_control_is_not_correct[{cell}]",
                   *(f"test_fault_is_not_correct[{cell}-{f}]"
                     for f in faults),
                   "test_fault_undone_leaves_what_it_patched[scratch_fault]"
                   }, out.stdout[-5000:]
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
