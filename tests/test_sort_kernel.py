"""Interpret-mode semantics for the experimental Pallas bitonic sort.

Exactness only — nothing dispatches to this kernel by default (see
ops/sort_kernel.py: on-chip profiling gates adoption)."""

import numpy as np
import pytest

import jax.numpy as jnp

from sparkrdma_tpu.ops.sort_kernel import (
    LANES,
    sort_pairs_blocks,
    sort_pairs_full,
)


def _pairs(n, seed, lo=None, hi=None, dtype=np.int32):
    rng = np.random.default_rng(seed)
    lo = -(1 << 30) if lo is None else lo
    hi = (1 << 30) if hi is None else hi
    k = rng.integers(lo, hi, n, dtype=dtype)
    v = np.arange(n, dtype=np.int32)  # unique: checks pairs move together
    return k, v


@pytest.mark.parametrize("block_rows", [8, 32])
def test_block_sort_each_block_sorted(block_rows):
    B = block_rows * LANES
    n = 4 * B
    k, v = _pairs(n, 1)
    ok, ov = sort_pairs_blocks(
        jnp.asarray(k), jnp.asarray(v), block_rows=block_rows,
        interpret=True,
    )
    ok = np.asarray(ok).reshape(4, B)
    ov = np.asarray(ov).reshape(4, B)
    for b in range(4):
        want_k = np.sort(k.reshape(4, B)[b])
        np.testing.assert_array_equal(ok[b], want_k)
        # pairs stayed together: v carries the original index
        np.testing.assert_array_equal(k[ov[b]], ok[b])


def test_block_sort_duplicate_and_extreme_keys():
    block_rows = 8
    B = block_rows * LANES
    rng = np.random.default_rng(2)
    k = rng.integers(0, 7, B, dtype=np.int32)  # heavy duplicates
    k[:4] = [np.iinfo(np.int32).max, np.iinfo(np.int32).min, 0, -1]
    v = np.arange(B, dtype=np.int32)
    ok, ov = sort_pairs_blocks(
        jnp.asarray(k), jnp.asarray(v), block_rows=block_rows,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(ok), np.sort(k))
    np.testing.assert_array_equal(k[np.asarray(ov)], np.asarray(ok))


@pytest.mark.parametrize("seed,n_buckets", [(3, 4), (4, 16)])
def test_full_sort_matches_numpy(seed, n_buckets):
    block_rows = 8
    B = block_rows * LANES
    n = 16 * B
    k, v = _pairs(n, seed)
    ok, ov, valid, fn, overflow = sort_pairs_full(
        jnp.asarray(k), jnp.asarray(v), block_rows=block_rows,
        n_buckets=n_buckets, interpret=True,
    )
    assert int(overflow) <= np.asarray(ok).shape[0] // n_buckets
    ok = np.asarray(ok)
    ov = np.asarray(ov)
    m = np.asarray(valid) > 0
    assert m.sum() == n
    np.testing.assert_array_equal(ok[m], np.sort(k))
    np.testing.assert_array_equal(k[ov[m]], ok[m])


def test_full_sort_skewed_keys():
    block_rows = 8
    B = block_rows * LANES
    n = 8 * B
    rng = np.random.default_rng(9)
    k = np.where(
        rng.random(n) < 0.7, np.int32(42),
        rng.integers(0, 1000, n, dtype=np.int32),
    )
    v = np.arange(n, dtype=np.int32)
    ok, ov, valid, fn, overflow = sort_pairs_full(
        jnp.asarray(k), jnp.asarray(v), block_rows=block_rows,
        n_buckets=4, cap_factor=2.0, interpret=True,
    )
    cap = np.asarray(ok).shape[0] // 4
    if int(overflow) <= cap:  # no overflow at this factor
        m = np.asarray(valid) > 0
        np.testing.assert_array_equal(np.asarray(ok)[m], np.sort(k))


@pytest.mark.parametrize("pattern", ["sorted", "reverse", "constant"])
def test_full_sort_adversarial_patterns(pattern):
    """Pre-sorted, reverse-sorted, and all-equal inputs (the splitter
    sampling's worst cases) must stay exact — a caller may adopt this
    engine unattended on hardware."""
    block_rows = 8
    B = block_rows * LANES
    n = 8 * B
    if pattern == "sorted":
        k = np.arange(n, dtype=np.int32)
    elif pattern == "reverse":
        k = np.arange(n, 0, -1).astype(np.int32)
    else:
        k = np.full(n, 7, np.int32)
    v = np.arange(n, dtype=np.int32)
    ok, ov, valid, fn, overflow = sort_pairs_full(
        jnp.asarray(k), jnp.asarray(v), block_rows=block_rows,
        n_buckets=4, cap_factor=2.0, interpret=True,
    )
    cap = np.asarray(ok).shape[0] // 4
    if int(overflow) > cap:
        return  # caller-visible overflow: retry path, not silent error
    m = np.asarray(valid) > 0
    assert m.sum() == n
    np.testing.assert_array_equal(np.asarray(ok)[m], np.sort(k))
    np.testing.assert_array_equal(k[np.asarray(ov)[m]], np.asarray(ok)[m])
