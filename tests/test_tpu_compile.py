"""The main-path Pallas kernels compile for a TPU v5e chip at real sizes.

No chip is attached: each stage is lowered for a *described* ``v5e:2x2``
topology and compiled by the TPU compiler installed with JAX, which
refuses what interpret mode lets through (ops Mosaic cannot lower,
unaligned slices, VMEM overruns).  Each compiled program must contain
the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.  The persistent compilation cache is off
around these compiles (a described-chip entry cannot be read back).
"""

import jax
import jax.numpy as jnp
import pytest

from repro.core.rans_np import _auto_lanes
from repro.kernels.histogram import ops as hist_ops
from repro.kernels.lz_match import ops as lz_ops
from repro.kernels.rans_lanes import ops as rans_ops
from repro.kernels.token_pack import ops as pack_ops

PACK_IDS = 266_240       # a 40-prompt group commit of the paper corpus
HIST_BYTES = 64 << 10
PROMPT_MEDIAN, PROMPT_MAX = 20_803, 213_379   # paper §4.1, characters
# rANS record lengths whose auto lane count is 1, 16 and 1024
RANS_N = {1: 4_000, 16: 6_000, 1024: 300_000}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(one_chip, fn, *args) -> str:
    args = [jax.ShapeDtypeStruct(a[0], a[1], sharding=one_chip)
            if isinstance(a, tuple) else a for a in args]
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("width", [2, 4])
def test_token_pack_compiles(one_chip, width):
    text = _compiled_text(one_chip, pack_ops._pack_padded,
                          ((PACK_IDS,), jnp.int32), width, False)
    assert "tpu_custom_call" in text


def test_byte_histogram_compiles(one_chip):
    n = hist_ops.size_bucket(HIST_BYTES, hist_ops._HIST_PAD_MIN)
    text = _compiled_text(one_chip, hist_ops._token_histogram,
                          ((n,), jnp.int32), 256, False)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("chars", [PROMPT_MEDIAN, PROMPT_MAX])
def test_lz_candidates_compile(one_chip, chars):
    p = lz_ops.size_bucket(chars, lz_ops._PAD_MIN)
    text = _compiled_text(one_chip, lz_ops._candidate_stage,
                          ((p,), jnp.uint8), ((), jnp.int32),
                          ((), jnp.int32), p, False)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("lanes,records", [(1, 64), (16, 1), (1024, 1),
                                           (1024, 4)])
def test_rans_encode_compiles(one_chip, lanes, records):
    """The launch of ``records`` records of this lane count: a flush's
    single-lane columns, a batch of one, and a group wider than the
    lane floor."""
    n = RANS_N[lanes]
    assert _auto_lanes(n) == lanes
    t, rem = divmod(n, lanes)
    tp, lp, rp = rans_ops.launch_shape(t + (rem > 0), lanes * records,
                                       records, lanes == 1)
    text = _compiled_text(one_chip, rans_ops._encode_stage,
                          ((tp, lp), jnp.uint8), ((1, lp), jnp.int32),
                          ((1, lp), jnp.int32), ((rp, 256), jnp.uint32),
                          12, False)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("lanes", [16, 1024])
def test_rans_decode_compiles(one_chip, lanes):
    n = RANS_N[lanes]
    text = _compiled_text(one_chip, rans_ops._decode_stage,
                          ((n // 2,), jnp.uint16), ((lanes,), jnp.uint32),
                          ((256,), jnp.uint32), n, lanes, 12, False)
    assert "tpu_custom_call" in text
