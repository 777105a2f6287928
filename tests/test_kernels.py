"""Per-kernel validation (deliverable c): shape/dtype sweeps in
interpret=True mode against the pure-jnp oracles in each ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.packing import pack_tokens
from repro.kernels import size_bucket
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.histogram import histogram_ref, token_histogram
from repro.kernels.token_pack import (delta_zigzag_device, delta_zigzag_ref,
                                      pack_fixed_batch_device, pack_ref,
                                      pack_tokens_device)
from repro.kernels.token_pack import ops as pack_ops

RNG = np.random.default_rng(0)


# -- flash attention ---------------------------------------------------------

SWEEP = [
    # B, Sq, Skv, Hq, Hkv, hd, causal, window, cap
    (2, 128, 128, 4, 2, 64, True, 0, 0.0),
    (1, 256, 256, 4, 4, 32, True, 64, 0.0),
    (2, 128, 128, 8, 1, 64, True, 0, 50.0),     # MQA + gemma2 softcap
    (1, 96, 96, 2, 2, 64, True, 0, 0.0),        # pad path
    (2, 1, 384, 4, 2, 64, True, 0, 0.0),        # decode with offset
    (1, 64, 64, 2, 2, 128, True, 0, 0.0),       # hw-aligned head dim
]


@pytest.mark.parametrize("case", SWEEP, ids=[str(i) for i in range(len(SWEEP))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(case, dtype):
    B, Sq, Skv, Hq, Hkv, hd, causal, window, cap = case
    off = Skv - Sq if Sq < Skv else 0
    q = jnp.asarray(RNG.normal(size=(B, Sq, Hq, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Skv, Hkv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Skv, Hkv, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          block_q=64, block_kv=64, q_offset=off, interpret=True)
    ref = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=causal, window=window,
                        softcap=cap, q_offset=off).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_attention_matches_model_engine():
    """Kernel == the model's blockwise/flash jnp engines (one oracle)."""
    from repro.models.attention import blockwise_attention, flash_self_attention

    q = jnp.asarray(RNG.normal(size=(2, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 128, 2, 32)), jnp.float32)
    pos = jnp.arange(128, dtype=jnp.int32)
    a = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    b = blockwise_attention(q, k, v, pos, pos, block_q=64, block_kv=64)
    c = flash_self_attention(q, k, v, True, 0, 0.0, None, (64, 64), 0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-5, atol=1e-5)


# -- token pack --------------------------------------------------------------

@pytest.mark.parametrize("n,hi", [(1, 60000), (777, 60000), (2048, 60000),
                                  (4096, 100000), (3000, 2**31 - 1),
                                  (2049, 60000), (16385, 100000)])
def test_pack_kernel_bit_identical(n, hi):
    ids = RNG.integers(0, hi, n)
    fb, data = pack_tokens_device(ids)
    assert bytes([fb]) + data == pack_tokens(ids, "fixed")


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 2**31 - 1), min_size=1, max_size=200))
def test_pack_kernel_property(ids):
    arr = np.asarray(ids, np.uint32)
    fb, data = pack_tokens_device(arr)
    assert bytes([fb]) + data == pack_tokens(arr, "fixed")


def test_pack_batch_kernel_matches_numpy():
    """Pallas batch path (one launch per width group, interpret mode) is
    bit-identical to per-stream pack_fixed — mixed widths, empty streams,
    and non-block-multiple lengths in one batch."""
    streams = [RNG.integers(0, 60000, 37),          # u16
               RNG.integers(0, 2**31 - 1, 2048),    # u32, block-aligned
               np.zeros(0, np.uint32),              # empty
               RNG.integers(0, 100, 1),             # u16 single
               RNG.integers(0, 100352, 555),        # u32 (special-token range)
               RNG.integers(0, 65536, 4097)]        # u16, crosses a block boundary
    got = pack_fixed_batch_device(streams, interpret=True)
    want = [pack_tokens(ids, "fixed") for ids in streams]
    assert got == want


@settings(max_examples=15, deadline=None)
@given(st.lists(st.lists(st.integers(0, 2**31 - 1), max_size=100), max_size=8))
def test_pack_batch_kernel_property(streams):
    arrs = [np.asarray(s, np.uint32) for s in streams]
    got = pack_fixed_batch_device(arrs, interpret=True)
    assert got == [pack_tokens(a, "fixed") for a in arrs]


def _streams_of(total, hi, rng):
    """1-5 streams of ids below ``hi`` whose lengths sum to ``total``."""
    cuts = np.sort(rng.choice(np.arange(1, total), min(rng.integers(0, 5),
                                                      total - 1), replace=False))
    return [rng.integers(0, hi, k).astype(np.uint32)
            for k in np.diff(np.concatenate([[0], cuts, [total]]))]


# the last is a bucket length plus one
@pytest.mark.parametrize("total", [2047, 2048, 2049, 16383, 16385,
                                   size_bucket(20000, 2048) + 1])
def test_pack_batch_kernel_bucket_edges(total):
    """Group totals at the edges of the size buckets the launches are
    padded to, beside a width-4 group: the pad never reaches a frame."""
    rng = np.random.default_rng(total)
    streams = _streams_of(total, 65536, rng) + [
        rng.integers(0, 2**31 - 1, 3).astype(np.uint32)]
    got = pack_fixed_batch_device(streams, interpret=True)
    assert got == [pack_tokens(ids, "fixed") for ids in streams]


def test_pack_batch_compiles_per_bucket_not_per_total():
    """40 group commits whose width-2 totals all differ but share one
    octave compile the pack kernel for at most eight shapes, and every
    frame still equals per-stream ``pack_fixed``."""
    rng = np.random.default_rng(15)
    totals = rng.choice(np.arange(16385, 32768), 40, replace=False)
    before = pack_ops._pack_padded._cache_size()
    for total in totals:
        streams = _streams_of(int(total), 65536, rng)
        got = pack_fixed_batch_device(streams, interpret=True)
        assert got == [pack_tokens(ids, "fixed") for ids in streams]
    assert pack_ops._pack_padded._cache_size() - before <= 8


def test_pack_ref_widths():
    ids = jnp.asarray([0, 1, 255, 256, 65535], jnp.int32)
    b2 = pack_ref(ids, 2)
    assert b2.shape == (5, 2)
    assert bytes(np.asarray(b2[4])) == b"\xff\xff"


def test_delta_zigzag_kernel():
    ids = jnp.asarray(RNG.integers(0, 2**30, 3000), jnp.int32)
    prev = jnp.concatenate([jnp.zeros(1, ids.dtype), ids[:-1]])
    np.testing.assert_array_equal(np.asarray(delta_zigzag_device(ids)),
                                  np.asarray(delta_zigzag_ref(ids, prev)))


@pytest.mark.parametrize("n", [1, 2047, 2049])
def test_delta_zigzag_kernel_bucket_edges(n):
    """The zero pad up to the size bucket never leaks into the deltas."""
    ids = jnp.asarray(RNG.integers(0, 2**30, n), jnp.int32)
    prev = jnp.concatenate([jnp.zeros(1, ids.dtype), ids[:-1]])
    np.testing.assert_array_equal(np.asarray(delta_zigzag_device(ids)),
                                  np.asarray(delta_zigzag_ref(ids, prev)))


# -- histogram ---------------------------------------------------------------

@pytest.mark.parametrize("n,v", [(100, 512), (5000, 8192), (4096, 100352),
                                 (1, 8), (1024, 2048)])
def test_histogram_vs_ref(n, v):
    ids = jnp.asarray(RNG.integers(0, v, n), jnp.int32)
    h = token_histogram(ids, v)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(histogram_ref(ids, v)))
    assert int(np.asarray(h).sum()) == n


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 511), min_size=1, max_size=300))
def test_histogram_property(ids):
    arr = jnp.asarray(ids, jnp.int32)
    h = np.asarray(token_histogram(arr, 512))
    assert h.sum() == len(ids)
    ref = np.bincount(np.asarray(ids), minlength=512)
    np.testing.assert_array_equal(h, ref)


def test_histogram_ignores_padding_ids():
    ids = jnp.asarray([-1, 3, 3, -1, 7], jnp.int32)
    h = np.asarray(token_histogram(ids, 8))
    assert h[3] == 2 and h[7] == 1 and h.sum() == 3
