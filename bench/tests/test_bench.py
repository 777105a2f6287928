"""Tests of the benchmark itself, off the chip, at a tiny size.

    python -m pytest -q bench/tests        # from the repository root

Each run is a child process (``probe.py --no-chip``): the check runs in
full, and no metric is printed.  A sound run is correct; the control and
every fault a cell can have make it not correct.  The repository's own
test suite does not collect these (its ``testpaths`` is ``tests``).
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
TINY = ["--set", "traffic.pool_per_client=4", "--set", "traffic.warmup_s=1.0",
        "--set", "traffic.readback_sample=6"]
SEED = 2_200_000_017           # larger than 32 signed bits hold


def _env() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def probe(cell: str, *extra: str, root: Path = ROOT,
          seconds: float = 2.5) -> dict:
    out = subprocess.run(
        [sys.executable, str(root / "bench" / "probe.py"), "--workload",
         cell, "--seed", str(SEED), "--seconds", str(seconds), "--no-chip",
         *TINY, *extra],
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
#: where it is produced, the durable acknowledgement broken
FAULTS = {"closed_batch": {"no_write": "acked_missing",
                           "half": "acked_missing",
                           "token_put": "readback_ids_bad",
                           "no_fsync": "acked_missing",
                           "late_fsync": "not_durable_at_ack"}}


@pytest.mark.parametrize("cell", _cells())
def test_sound_run_is_correct(cell):
    r = probe(cell)
    assert r["correct"], r["checks"]
    assert r["metrics"] == {}, "a rehearsal off the chip prints no metric"
    assert r["attempted"] > 0


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


def test_new_cell_is_data_only(tmp_path):
    """A configuration and a traffic mix added as files, and a cell added
    as an entry, run with no edit to any other file."""
    doc = _doc()
    doc["configs"].append({"name": "scratch-cfg", "source": "test",
                           "file": "bench/configs/scratch-cfg.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "scratch-cfg.scratch-mix",
                             "config": "scratch-cfg", "traffic": "scratch-mix",
                             "chips": 1, "why": "test"})
    root = _copy(tmp_path, doc)
    cfg = json.loads((BENCH / "configs" / "hybrid-zstd.json").read_text())
    cfg.update(shards=2, flush_batch=8)
    (root / "bench" / "configs" / "scratch-cfg.json").write_text(
        json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "ingest.json").read_text())
    mix.update(batch=4)
    (root / "bench" / "traffic" / "scratch-mix.json").write_text(
        json.dumps(mix))
    r = probe("scratch-cfg.scratch-mix", root=root)
    assert r["correct"], r["checks"]


def test_trace_reduction_on_the_recorded_chip_trace():
    out = subprocess.run([sys.executable, str(BENCH / "selfcheck.py")],
                         cwd=ROOT, env=_env(), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
