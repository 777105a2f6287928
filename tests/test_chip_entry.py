"""The chip-facing entry points on a host without a chip: ``chip_smoke.py``
refuses to run, no kernel wrapper defaults to interpret mode, the
compile cache lands where it is told (or at one fixed path in the
checkout), and gateway roles name their platform so only the writer of
a fleet opens the chip."""

import inspect
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import kernels
from repro.core import device
from repro.kernels.histogram import byte_histogram_device, token_histogram
from repro.kernels.lz_match import lz_candidates_device
from repro.kernels.rans_lanes import (rans_decode_interleaved_device,
                                      rans_encode_interleaved_device)
from repro.kernels.token_pack import (delta_zigzag_device,
                                      pack_fixed_batch_device,
                                      pack_tokens_device)
from repro.launch import gateway as launch_gateway

ROOT = Path(__file__).resolve().parent.parent


def _run_smoke(script: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=120)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_the_cpu(tmp_path, where):
    """No TPU: exit non-zero, say so, print no result line — from the
    repository and from a directory holding only the script."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    res = _run_smoke(script)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "no TPU found" in res.stderr


@pytest.mark.parametrize("wrapper", [
    pack_tokens_device, pack_fixed_batch_device, delta_zigzag_device,
    token_histogram, byte_histogram_device, lz_candidates_device,
    rans_encode_interleaved_device, rans_decode_interleaved_device,
], ids=lambda f: f.__name__)
def test_kernel_wrappers_default_to_compiled(wrapper):
    """``interpret=None`` resolves per backend: compiled on a chip,
    interpreted only where JAX's backend is the CPU."""
    assert inspect.signature(wrapper).parameters["interpret"].default is None


def test_interpret_default_follows_backend():
    assert kernels.interpret_default() is (jax.default_backend() == "cpu")
    assert kernels.interpret_default(False) is False
    assert kernels.interpret_default(True) is True


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert device.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX's own


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                     cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = device.enable_compile_cache()
    assert first == device.enable_compile_cache() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


@pytest.mark.parametrize("role,want", [("writer", "auto"),
                                       ("standby", "auto"),
                                       ("replica", "cpu")])
def test_gateway_role_platform_defaults(role, want):
    args = launch_gateway.parse_args(["--store-dir", "s", "--role", role])
    assert args.platform == want


def test_gateway_platform_pin(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    launch_gateway.pin_platform("auto")
    assert calls == []
    launch_gateway.pin_platform("cpu")
    assert calls == [("jax_platforms", "cpu")]
