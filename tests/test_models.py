"""Device-native model pipelines (TeraSort, WordCount) on the 8-device
CPU mesh — the flagship workloads (SURVEY.md §6 benchmarks)."""

import numpy as np
import pytest

from sparkrdma_tpu.models import TeraSorter, WordCounter
from sparkrdma_tpu.parallel import make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def test_terasort_uniform(mesh, devices):
    sorter = TeraSorter(mesh)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1 << 31, size=100_000, dtype=np.int32)
    vals = rng.integers(0, 1 << 31, size=100_000, dtype=np.int32)
    sk, sv = sorter.sort(keys, vals)
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(sk, keys[order])
    np.testing.assert_array_equal(np.sort(sv), np.sort(vals))
    # key-value alignment preserved through the exchange
    kv = dict()
    for k, v in zip(keys.tolist(), vals.tolist()):
        kv.setdefault(k, []).append(v)
    for k, v in zip(sk[:100].tolist(), sv[:100].tolist()):
        assert v in kv[k]


def test_terasort_skewed_overflow_retry(mesh, devices):
    sorter = TeraSorter(mesh, capacity_factor=1.05)
    rng = np.random.default_rng(1)
    # 60% of keys in a tiny range → one device's bucket overflows at
    # factor 1.05 and the host must retry with doubled capacity
    a = rng.integers(0, 100, size=60_000, dtype=np.int32)
    b = rng.integers(0, 1 << 30, size=40_000, dtype=np.int32)
    keys = np.concatenate([a, b])
    rng.shuffle(keys)
    sk, _ = sorter.sort(keys, keys)
    np.testing.assert_array_equal(sk, np.sort(keys))


@pytest.mark.parametrize("n_local", [1 << 10, 1 << 21, 1 << 24])
def test_terasort_sample_positions_exact_at_chip_sizes(n_local):
    """The splitter sample sits at the exact local quantiles i*n/S at
    every size a chip holds.  In int32, i*n wrapped from n_local = 2^21
    (S = 1024): on four v5e chips at 2^24 records each the skewed
    splitters overflowed every bucket and the capacity retries ran out
    of HBM (chip_smoke.py --chips 4, PR 21)."""
    from sparkrdma_tpu.models.terasort import _sample_positions

    S = 1024
    ref = [i * n_local // S for i in range(S)]
    np.testing.assert_array_equal(_sample_positions(n_local, S), ref)


def test_wide_rows_ride_their_keys(mesh, devices):
    """HiBench-shaped rows ([n, W] payload) through the host-facing
    sort: keys sorted, and each payload row still on its key."""
    sorter = TeraSorter(mesh)
    rng = np.random.default_rng(3)
    n = 8 * 4096
    keys = rng.integers(0, 1 << 12, size=n, dtype=np.int32)  # ties
    pay = rng.integers(0, 1 << 31, size=(n, 24), dtype=np.int32)
    pay[:, 0] = np.arange(n)
    sk, sp = sorter.sort(keys, pay)
    perm = sp[:, 0]
    np.testing.assert_array_equal(np.sort(perm), np.arange(n))
    np.testing.assert_array_equal(sk, np.sort(keys))
    np.testing.assert_array_equal(keys[perm], sk)
    np.testing.assert_array_equal(pay[perm], sp)
    with pytest.raises(ValueError, match="divisible"):
        sorter.sort(keys[:-1], pay[:-1])


def test_terasort_ragged_length_and_empty(mesh, devices):
    sorter = TeraSorter(mesh)
    keys = np.array([5, 3, 9], dtype=np.int32)  # not divisible by 8
    sk, sv = sorter.sort(keys, keys * 10)
    np.testing.assert_array_equal(sk, [3, 5, 9])
    np.testing.assert_array_equal(sv, [30, 50, 90])
    ek, ev = sorter.sort(np.array([], dtype=np.int32))
    assert ek.size == 0 and ev.size == 0


def test_wordcount(mesh, devices):
    wc = WordCounter(mesh)
    rng = np.random.default_rng(2)
    keys = rng.integers(0, 1000, size=50_000, dtype=np.int32)
    got = wc.count(keys)
    expect = {int(k): int(c) for k, c in zip(*np.unique(keys, return_counts=True))}
    assert got == expect


def test_wordcount_weighted_values(mesh, devices):
    wc = WordCounter(mesh)
    keys = np.array([1, 2, 1, 3, 2, 1], dtype=np.int32)
    vals = np.array([10, 20, 30, 40, 50, 60], dtype=np.int32)
    assert wc.count(keys, vals) == {1: 100, 2: 70, 3: 40}


def test_wordcount_single_hot_key(mesh, devices):
    # extreme skew: every record hits one key on one device
    wc = WordCounter(mesh, capacity_factor=1.1)
    keys = np.full(10_000, 77, dtype=np.int32)
    assert wc.count(keys) == {77: 10_000}


def test_max_value_keys_not_confused_with_padding(mesh, devices):
    # reviewer finding: keys equal to iinfo.max must survive both models
    sentinel = np.iinfo(np.int32).max
    wc = WordCounter(mesh)
    k = np.array([sentinel, sentinel, 5], dtype=np.int32)  # ragged: pads added
    assert wc.count(k) == {sentinel: 2, 5: 1}

    sorter = TeraSorter(mesh)
    keys = np.array([sentinel, 1, sentinel, 3, 2], dtype=np.int32)
    vals = np.array([10, 11, 12, 13, 14], dtype=np.int32)
    sk, sv = sorter.sort(keys, vals)
    np.testing.assert_array_equal(sk, [1, 2, 3, sentinel, sentinel])
    assert sv[0] == 11 and sv[1] == 14 and sv[2] == 13
    assert sorted(sv[3:]) == [10, 12]  # max-key values kept, not pad zeros


def test_sort_device_arbitrary_valid_column(mesh, devices):
    """sort_device must honor a valid column whose invalid slots carry
    ARBITRARY keys (not pre-set to the dtype max): invalid records are
    dropped, all real records survive."""
    import jax.numpy as jnp

    sorter = TeraSorter(mesh)
    rng = np.random.default_rng(7)
    n = 8 * 1024
    keys = rng.integers(0, 1 << 31, size=n, dtype=np.int32)
    vals = rng.integers(0, 1 << 31, size=n, dtype=np.int32)
    valid = (rng.random(n) < 0.7).astype(np.int32)
    (sk, sv, n_valid, _), cap = sorter.sort_device(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid)
    )
    D = sorter.n_devices
    sk_h = np.asarray(sk).reshape(D, -1)
    sv_h = np.asarray(sv).reshape(D, -1)
    nv = np.asarray(n_valid).reshape(-1)
    out_k = np.concatenate([sk_h[d, : nv[d]] for d in range(D)])
    out_v = np.concatenate([sv_h[d, : nv[d]] for d in range(D)])
    real = valid > 0
    np.testing.assert_array_equal(out_k, np.sort(keys[real], kind="stable"))
    np.testing.assert_array_equal(np.sort(out_v), np.sort(vals[real]))


def _join_case(seed, n_fact, n_dim, key_space):
    rng = np.random.default_rng(seed)
    dim_keys = rng.choice(key_space, size=n_dim, replace=False).astype(np.int32)
    dim_vals = rng.integers(0, 1 << 30, size=n_dim, dtype=np.int32)
    fact_keys = rng.integers(0, key_space, size=n_fact, dtype=np.int32)
    fact_vals = rng.integers(0, 1 << 30, size=n_fact, dtype=np.int32)
    lookup = dict(zip(dim_keys.tolist(), dim_vals.tolist()))
    expected = sorted(
        (int(k), int(v), lookup[int(k)])
        for k, v in zip(fact_keys, fact_vals) if int(k) in lookup
    )
    return fact_keys, fact_vals, dim_keys, dim_vals, expected


@pytest.mark.parametrize("joiner_cls", ["hash", "broadcast"])
def test_device_join(joiner_cls, mesh, devices):
    from sparkrdma_tpu.models.join import BroadcastJoiner, HashJoiner

    fk, fv, dk, dv, expected = _join_case(5, 4000, 300, 1000)
    j = (HashJoiner if joiner_cls == "hash" else BroadcastJoiner)(mesh)
    k, lv, rv = j.join(fk, fv, dk, dv)
    got = sorted(zip(k.tolist(), lv.tolist(), rv.tolist()))
    assert got == expected


def test_hash_join_skewed_overflow_retry(mesh, devices):
    from sparkrdma_tpu.models.join import HashJoiner

    rng = np.random.default_rng(9)
    # 70% of fact keys identical -> one device's bucket overflows
    hot = np.full(7000, 42, np.int32)
    cold = rng.integers(0, 500, size=3000, dtype=np.int32)
    fk = np.concatenate([hot, cold])
    fv = np.arange(10000, dtype=np.int32)
    dk = np.arange(500, dtype=np.int32)
    dv = dk * 3
    j = HashJoiner(mesh, capacity_factor=1.1)
    k, lv, rv = j.join(fk, fv, dk, dv)
    assert len(k) == 10000  # every fact key exists in dim
    assert (rv == k * 3).all()


@pytest.mark.parametrize("joiner_cls", ["hash", "broadcast"])
def test_join_dtype_max_fact_key(joiner_cls, mesh, devices):
    # reviewer finding: a fact key equal to iinfo.max must not match a
    # sentinel-masked padding/fill slot (validity of the hit is checked)
    from sparkrdma_tpu.models.join import BroadcastJoiner, HashJoiner

    imax = np.iinfo(np.int32).max
    fk = np.array([1, 2, imax, 5], np.int32)
    fv = np.array([10, 20, 30, 50], np.int32)
    dk = np.array([1, 2, 3], np.int32)
    dv = np.array([100, 200, 300], np.int32)
    j = (HashJoiner if joiner_cls == "hash" else BroadcastJoiner)(mesh)
    k, lv, rv = j.join(fk, fv, dk, dv)
    got = sorted(zip(k.tolist(), lv.tolist(), rv.tolist()))
    assert got == [(1, 10, 100), (2, 20, 200)]


@pytest.mark.parametrize("joiner_cls", ["hash", "broadcast"])
def test_join_dtype_max_dim_key_matches(joiner_cls, mesh, devices):
    # a REAL dim key equal to iinfo.max must still be matchable
    from sparkrdma_tpu.models.join import BroadcastJoiner, HashJoiner

    imax = np.iinfo(np.int32).max
    fk = np.array([imax, 7], np.int32)
    fv = np.array([1, 2], np.int32)
    dk = np.array([imax, 7], np.int32)
    dv = np.array([111, 77], np.int32)
    j = (HashJoiner if joiner_cls == "hash" else BroadcastJoiner)(mesh)
    k, lv, rv = j.join(fk, fv, dk, dv)
    got = sorted(zip(k.tolist(), lv.tolist(), rv.tolist()))
    assert got == [(7, 2, 77), (imax, 1, 111)]


@pytest.mark.parametrize("joiner_cls", ["hash", "broadcast"])
def test_join_empty_dimension(joiner_cls, mesh, devices):
    # reviewer finding: empty dimension side -> empty result, not a crash
    from sparkrdma_tpu.models.join import BroadcastJoiner, HashJoiner

    fk = np.array([1, 2, 3, 4], np.int32)
    fv = np.array([10, 20, 30, 40], np.int32)
    j = (HashJoiner if joiner_cls == "hash" else BroadcastJoiner)(mesh)
    k, lv, rv = j.join(fk, fv, np.array([], np.int32), np.array([], np.int32))
    assert len(k) == 0 and len(lv) == 0 and len(rv) == 0


def test_keyed_aggregator_full_stats(mesh, devices):
    from sparkrdma_tpu.models.aggregate import KeyedAggregator

    rng = np.random.default_rng(12)
    n = 20000
    keys = rng.integers(0, 300, n).astype(np.int32)
    vals = rng.integers(-1000, 1000, n).astype(np.int32)
    agg = KeyedAggregator(mesh)
    out = agg.aggregate(keys, vals)
    assert set(out) == set(np.unique(keys).tolist())
    for k in np.unique(keys):
        sel = vals[keys == k]
        st = out[int(k)]
        assert st.sum == int(sel.sum())
        assert st.count == len(sel)
        assert st.min == int(sel.min())
        assert st.max == int(sel.max())
        assert abs(st.mean - sel.mean()) < 1e-9


def test_keyed_aggregator_sentinel_key_and_padding(mesh, devices):
    from sparkrdma_tpu.models.aggregate import KeyedAggregator

    imax = np.iinfo(np.int32).max
    # a real key equal to the sentinel, with a size forcing padding
    keys = np.array([imax, 5, imax, 5, imax], np.int32)
    vals = np.array([7, -2, 3, 4, -9], np.int32)
    out = KeyedAggregator(mesh).aggregate(keys, vals)
    assert out[imax] == (1, 3, -9, 7)
    assert out[5] == (2, 2, -2, 4)


def test_keyed_aggregator_skew_retry(mesh, devices):
    from sparkrdma_tpu.models.aggregate import KeyedAggregator

    rng = np.random.default_rng(13)
    hot = np.full(9000, 17, np.int32)
    cold = rng.integers(0, 50, 1000).astype(np.int32)
    keys = np.concatenate([hot, cold])
    vals = np.arange(10000, dtype=np.int32)
    out = KeyedAggregator(mesh, capacity_factor=1.1).aggregate(keys, vals)
    sel = vals[keys == 17]
    assert out[17] == (int(sel.sum()), len(sel), int(sel.min()), int(sel.max()))


def test_keyed_aggregator_rejects_silent_int64_truncation(mesh, devices):
    from sparkrdma_tpu.models.aggregate import KeyedAggregator
    import jax as _jax

    if _jax.config.jax_enable_x64:
        pytest.skip("x64 enabled: int64 is exact, nothing to reject")
    keys = np.zeros(8, np.int32)
    vals = np.full(8, 2**40, np.int64)
    with pytest.raises(ValueError, match="int64"):
        KeyedAggregator(mesh).aggregate(keys, vals)


def test_wordcount_rejects_silent_int64_truncation(mesh, devices):
    # reviewer finding: the guard must cover every keyed model and BOTH
    # columns (int64 keys collide after a silent int32 downcast)
    from sparkrdma_tpu.models.wordcount import WordCounter
    from sparkrdma_tpu.models.aggregate import KeyedAggregator
    import jax as _jax

    if _jax.config.jax_enable_x64:
        pytest.skip("x64 enabled: int64 is exact, nothing to reject")
    with pytest.raises(ValueError, match="int64 vals"):
        WordCounter(mesh).count(
            np.zeros(8, np.int32), np.full(8, 2**40, np.int64)
        )
    with pytest.raises(ValueError, match="int64 keys"):
        KeyedAggregator(mesh).aggregate(
            np.array([2**33 + 1, 1] * 4, np.int64), np.ones(8, np.int32)
        )


@pytest.mark.parametrize("joiner_cls", ["hash", "broadcast"])
def test_join_mixed_dtype_fact_vals_exact(joiner_cls, mesh, devices):
    # reviewer finding: int32 fact values joined against float32 dim
    # values must come back EXACT (no silent promotion through the sort)
    from sparkrdma_tpu.models.join import BroadcastJoiner, HashJoiner

    fk = np.array([1, 2, 3], np.int32)
    fv = np.array([2**24 + 1, 7, 9], np.int32)  # 2^24+1 not float32-exact
    dk = np.array([1, 2], np.int32)
    dv = np.array([0.5, 1.5], np.float32)
    j = (HashJoiner if joiner_cls == "hash" else BroadcastJoiner)(mesh)
    k, lv, rv = j.join(fk, fv, dk, dv)
    got = sorted(zip(k.tolist(), lv.tolist(), rv.tolist()))
    assert got == [(1, 2**24 + 1, 0.5), (2, 7, 1.5)]
    assert lv.dtype == np.int32


def test_join_rejects_silent_int64_truncation(mesh, devices):
    from sparkrdma_tpu.models.join import HashJoiner
    import jax as _jax

    if _jax.config.jax_enable_x64:
        pytest.skip("x64 enabled: int64 is exact, nothing to reject")
    fk = np.array([2**33 + 1, 5], np.int64)
    fv = np.array([10, 20], np.int32)
    dk = np.array([1], np.int64)
    dv = np.array([99], np.int32)
    with pytest.raises(ValueError, match="int64 keys"):
        HashJoiner(mesh).join(fk, fv, dk, dv)


def test_external_sort_streaming_chunks(mesh, devices):
    from sparkrdma_tpu.models.external_sort import ExternalTeraSorter

    rng = np.random.default_rng(50)
    all_k, all_v = [], []

    def chunks():
        for _ in range(10):
            n = int(rng.integers(1000, 5000))
            k = rng.integers(0, 1 << 30, n).astype(np.int32)
            v = rng.integers(0, 1 << 30, n).astype(np.int32)
            all_k.append(k)
            all_v.append(v)
            yield k, v

    ext = ExternalTeraSorter(mesh, num_buckets=8, sample_per_chunk=512)
    outs = list(ext.sort_chunks(chunks()))
    got_k = np.concatenate([k for k, _ in outs])
    got_v = np.concatenate([v for _, v in outs])
    keys = np.concatenate(all_k)
    vals = np.concatenate(all_v)
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(got_k, keys[order])
    assert sorted(zip(got_k.tolist(), got_v.tolist())) == sorted(
        zip(keys.tolist(), vals.tolist())
    )
    assert ext.chunks_in == 10
    assert ext.bytes_spilled == keys.nbytes + vals.nbytes
    # memory bound: no bucket anywhere near the whole dataset
    assert ext.max_bucket_records < len(keys) // 2


def test_external_sort_empty_and_single(mesh, devices):
    from sparkrdma_tpu.models.external_sort import ExternalTeraSorter

    ext = ExternalTeraSorter(mesh, num_buckets=4)
    k, v = ext.sort(np.zeros(0, np.int32), np.zeros(0, np.int32))
    assert len(k) == 0 and len(v) == 0
    k, v = ExternalTeraSorter(mesh, num_buckets=4).sort(
        np.array([5], np.int32), np.array([7], np.int32)
    )
    assert k.tolist() == [5] and v.tolist() == [7]


def test_external_sort_resplits_sorted_input(mesh, devices):
    """Adversarial (already sorted) input freezes the first-chunk
    splitters on an unrepresentative sample; pass 2 must re-split the
    oversized bucket instead of loading it whole (advisor finding)."""
    from sparkrdma_tpu.models.external_sort import ExternalTeraSorter

    n_chunk, n_chunks = 2000, 8
    keys = np.arange(n_chunk * n_chunks, dtype=np.int32)
    vals = keys[::-1].copy()

    def chunks():
        for c in range(n_chunks):
            sl = slice(c * n_chunk, (c + 1) * n_chunk)
            yield keys[sl], vals[sl]

    ext = ExternalTeraSorter(mesh, num_buckets=8, sample_per_chunk=256)
    outs = list(ext.sort_chunks(chunks()))
    got_k = np.concatenate([k for k, _ in outs])
    got_v = np.concatenate([v for _, v in outs])
    np.testing.assert_array_equal(got_k, keys)
    np.testing.assert_array_equal(got_v, vals)
    # sorted input routes chunks 2..N into the last range bucket; the
    # re-split must both trigger and restore the working-set bound
    assert ext.buckets_resplit >= 1
    assert ext.max_bucket_records <= n_chunk


def test_external_sort_balanced_input_no_resplit(mesh, devices):
    """Balanced buckets larger than one chunk must NOT trigger the
    re-split path (the bound is max(chunk, balanced bucket))."""
    from sparkrdma_tpu.models.external_sort import ExternalTeraSorter

    rng = np.random.default_rng(51)
    # 16 chunks of 1000 into 4 buckets: balanced buckets hold ~4000
    # records, well over one chunk — still no re-split
    ext = ExternalTeraSorter(mesh, num_buckets=4, sample_per_chunk=512)
    ks = rng.integers(0, 1 << 30, (16, 1000)).astype(np.int32)
    outs = list(ext.sort_chunks((k, k.copy()) for k in ks))
    got = np.concatenate([k for k, _ in outs])
    np.testing.assert_array_equal(got, np.sort(ks.reshape(-1)))
    assert ext.buckets_resplit == 0


def test_external_sort_duplicate_heavy_bucket(mesh, devices):
    """An all-one-key bucket cannot be split by key; the re-split must
    detect no-progress and fall back to a whole load instead of
    recursing max_split_depth times over the same file."""
    from sparkrdma_tpu.models.external_sort import ExternalTeraSorter

    keys = np.concatenate([
        np.arange(2000, dtype=np.int32),          # chunk 1: spread
        np.full(14000, 7_000_000, np.int32),      # chunks 2..8: one key
    ])
    vals = np.arange(len(keys), dtype=np.int32)
    ext = ExternalTeraSorter(mesh, num_buckets=8, sample_per_chunk=128)
    outs = list(ext.sort_chunks(
        (keys[i:i + 2000], vals[i:i + 2000])
        for i in range(0, len(keys), 2000)
    ))
    got_k = np.concatenate([k for k, _ in outs])
    got_v = np.concatenate([v for _, v in outs])
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(got_k, keys[order])
    assert sorted(got_v.tolist()) == sorted(vals.tolist())
    # the degenerate bucket loaded whole exactly once (no useless churn)
    assert ext.buckets_resplit == 0


def test_join_int64_keys_under_x64():
    """64-bit keys/values must survive the packed transport when
    jax_enable_x64 is on: keys differing only in their high 32 bits
    must NOT collide (regression: the uint32 transport collapsed
    2**32+1 onto 1).  Runs in a subprocess because x64 is a global
    startup flag."""
    import subprocess
    import sys

    code = """
import os
os.environ["JAX_ENABLE_X64"] = "1"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import numpy as np
from sparkrdma_tpu.models.join import BroadcastJoiner, HashJoiner
from sparkrdma_tpu.parallel.mesh import make_mesh

mesh = make_mesh(1)
fact_keys = np.array([1, 2**32 + 1, 5], dtype=np.int64)
fact_vals = np.array([10, 20, 30], dtype=np.int64)
dim_keys = np.array([1, 5], dtype=np.int64)
dim_vals = np.array([100, 2**33 + 7], dtype=np.int64)
for joiner in (HashJoiner(mesh), BroadcastJoiner(mesh)):
    k, fv, dv = joiner.join(fact_keys, fact_vals, dim_keys, dim_vals)
    rows = sorted(zip(k.tolist(), fv.tolist(), dv.tolist()))
    assert rows == [(1, 10, 100), (5, 30, 2**33 + 7)], (
        type(joiner).__name__, rows)
print("OK")
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=240,
    )
    assert out.returncode == 0 and "OK" in out.stdout, (
        out.stdout + out.stderr
    )


def _join_aggregate_oracle(fk, fv, dk, dv, gk_fn, val_fn):
    """numpy oracle for the fused broadcast-join + aggregate."""
    lookup = dict(zip(dk.tolist(), dv.tolist()))
    groups = {}
    for k, pv in zip(fk.tolist(), fv.tolist()):
        if k not in lookup:
            continue
        g = gk_fn(k)
        v = val_fn(k, pv, lookup[k])
        s, c, mn, mx = groups.get(g, (0, 0, None, None))
        groups[g] = (
            s + v, c + 1,
            v if mn is None else min(mn, v),
            v if mx is None else max(mx, v),
        )
    return groups


def test_broadcast_join_aggregate_fused(mesh, devices):
    import jax.numpy as jnp

    from sparkrdma_tpu.models.join_aggregate import BroadcastJoinAggregator

    fk, fv, dk, dv, _ = _join_case(11, 4096, 300, 1000)
    # negative dim values exercise min/max over the signed decode
    dv = dv - (1 << 29)

    def gk_fn(ku):
        return ku % jnp.asarray(17, ku.dtype)

    def val_fn(ku, fact_pay_u, dim_val_u):
        import jax.lax as lax

        return lax.bitcast_convert_type(
            fact_pay_u, jnp.int32
        ) ^ lax.bitcast_convert_type(dim_val_u, jnp.int32)

    agg = BroadcastJoinAggregator(mesh)
    got = agg.join_aggregate(fk, fv, dk, dv, gk_fn, val_fn)
    want = _join_aggregate_oracle(
        fk, fv, dk, dv, lambda k: k % 17, lambda k, a, b: a ^ b
    )
    assert set(got) == set(want)
    for g, (s, c, mn, mx) in want.items():
        st = got[g]
        # sums wrap in int32 (JVM Int parity, models/aggregate.py)
        assert (st.sum - s) % (1 << 32) == 0, (g, st, s)
        assert (st.count, st.min, st.max) == (c, mn, mx), (g, st)


def test_broadcast_join_aggregate_defaults_and_edge_keys(mesh, devices):
    from sparkrdma_tpu.models.join_aggregate import BroadcastJoinAggregator

    imax = np.iinfo(np.int32).max
    # default hooks: group by the join key, aggregate the dim value;
    # imax fact key must not match padding, unmatched key 9 drops out
    fk = np.array([1, 1, 2, imax, 9], np.int32)
    fv = np.array([10, 11, 20, 30, 90], np.int32)
    dk = np.array([1, 2], np.int32)
    dv = np.array([-5, 7], np.int32)
    agg = BroadcastJoinAggregator(mesh)
    got = agg.join_aggregate(fk, fv, dk, dv)
    assert set(got) == {1, 2}
    assert got[1] == (-10, 2, -5, -5)
    assert got[2] == (7, 1, 7, 7)


def test_broadcast_join_aggregate_negative_keys(mesh, devices):
    # group keys must come back in the signed join-key domain, not the
    # unsigned transport view (code-review finding)
    from sparkrdma_tpu.models.join_aggregate import BroadcastJoinAggregator

    fk = np.array([-5, -5, 3], np.int32)
    fv = np.array([1, 2, 3], np.int32)
    dk = np.array([-5, 3], np.int32)
    dv = np.array([100, 200], np.int32)
    got = BroadcastJoinAggregator(mesh).join_aggregate(fk, fv, dk, dv)
    assert set(got) == {-5, 3}
    assert got[-5] == (200, 2, 100, 100)
    assert got[3] == (200, 1, 200, 200)


@pytest.mark.parametrize("joiner_cls", ["hash", "broadcast"])
def test_join_variants_semi_anti_outer(joiner_cls, mesh, devices):
    """left-semi (TPC-DS q16), left-anti (q94), and left-outer joins
    against dict oracles."""
    from sparkrdma_tpu.models.join import BroadcastJoiner, HashJoiner

    fk, fv, dk, dv, _ = _join_case(23, 5000, 250, 900)
    lut = dict(zip(dk.tolist(), dv.tolist()))
    j = (HashJoiner if joiner_cls == "hash" else BroadcastJoiner)(mesh)

    matched = sorted(
        (int(k), int(v)) for k, v in zip(fk, fv) if int(k) in lut
    )
    unmatched = sorted(
        (int(k), int(v)) for k, v in zip(fk, fv) if int(k) not in lut
    )

    k, lv = j.join(fk, fv, dk, dv, how="semi")
    assert sorted(zip(k.tolist(), lv.tolist())) == matched

    k, lv = j.join(fk, fv, dk, dv, how="anti")
    assert sorted(zip(k.tolist(), lv.tolist())) == unmatched

    k, lv, rv, m = j.join(fk, fv, dk, dv, how="left_outer")
    assert len(k) == len(fk)
    got = sorted(
        ((int(kk), int(vv), int(rr) if mm else None)
         for kk, vv, rr, mm in zip(k, lv, rv, m)),
        key=lambda t: (t[0], t[1]),
    )
    want = sorted(
        ((int(kk), int(vv), lut.get(int(kk)))
         for kk, vv in zip(fk, fv)),
        key=lambda t: (t[0], t[1]),
    )
    assert got == want

    with pytest.raises(ValueError, match="how"):
        j.join(fk, fv, dk, dv, how="full_outer")


def test_keyed_models_single_device_fast_path(devices):
    """D == 1 with no padding engages the validity-free sort fast path
    (with_validity=False); results must match the padded general path."""
    from sparkrdma_tpu.models import KeyedAggregator, WordCounter

    m1 = make_mesh(1)
    rng = np.random.default_rng(55)
    keys = rng.integers(0, 97, 4096, dtype=np.int32)  # even n: unpadded
    vals = rng.integers(-500, 500, 4096, dtype=np.int32)
    got = WordCounter(m1).count(keys, vals)
    u = np.unique(keys)
    assert got == {
        int(k): int(vals[keys == k].sum()) for k in u
    }
    stats = KeyedAggregator(m1).aggregate(keys, vals)
    for k in u:
        sel = vals[keys == k]
        st = stats[int(k)]
        assert (st.sum, st.count, st.min, st.max) == (
            int(sel.sum()), len(sel), int(sel.min()), int(sel.max())
        )
    # dtype-max key is a REAL key on the fast path too (no sentinel
    # confusion when every slot is valid)
    imax = np.iinfo(np.int32).max
    keys2 = np.array([imax, imax, 7, 8], np.int32)
    vals2 = np.array([1, 2, 3, 4], np.int32)
    assert WordCounter(m1).count(keys2, vals2) == {imax: 3, 7: 3, 8: 4}


def test_quantized_padded_lengths_collapse_shapes(mesh, devices):
    """Arbitrary input sizes collapse onto the 8-steps-per-octave
    compile-shape ladder (≤12.5% padding), and results stay exact."""
    from sparkrdma_tpu.models._base import quantize_padded_length
    from sparkrdma_tpu.models import WordCounter

    sizes = {quantize_padded_length(n, 8) for n in range(1000, 100_000, 97)}
    # ~1000 distinct sizes collapse to ~16 per octave over ~7 octaves
    assert len(sizes) <= 130, len(sizes)
    for n in range(1000, 100_000, 97):
        m = quantize_padded_length(n, 8)
        assert m >= n and m % 8 == 0 and m <= n * 1.125 + 8, (n, m)

    wc = WordCounter(mesh)
    rng = np.random.default_rng(77)
    keys = rng.integers(0, 31, 12_345, dtype=np.int32)  # off-ladder n
    got = wc.count(keys)
    u, c = np.unique(keys, return_counts=True)
    assert got == dict(zip(u.tolist(), c.tolist()))


def test_grouped_topk(mesh, devices):
    """Grouped top-k (the q67 rank/LIMIT-per-group shape) vs a dict
    oracle, including ties, k larger than a group, and negatives."""
    from sparkrdma_tpu.models.topk import GroupedTopK

    rng = np.random.default_rng(42)
    n = 20011
    keys = rng.integers(0, 67, n, dtype=np.int32)
    vals = rng.integers(-1000, 1000, n, dtype=np.int32)
    for k in (1, 3, 500):
        got = GroupedTopK(mesh).top_k(keys, vals, k)
        for kk in np.unique(keys):
            sel = np.sort(vals[keys == kk])[::-1][:k]
            assert got[int(kk)] == sel.tolist(), (k, kk)
        assert set(got) == set(np.unique(keys).tolist())
    import pytest as _pytest

    with _pytest.raises(ValueError, match="k must be positive"):
        GroupedTopK(mesh).top_k(keys, vals, 0)


def test_terasort_wide_records_match_numpy(devices):
    """Wide-record sort (HiBench 10B+90B shape): payload rows follow
    their keys through sample/window/all_to_all/merge exactly."""
    import jax.numpy as jnp
    import numpy as np

    from sparkrdma_tpu.models.terasort import TeraSorter
    from sparkrdma_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(17)
    W = 24  # 96B payload
    for n in (8 * 512, 8 * 2048):
        keys = rng.integers(0, 1 << 31, n).astype(np.int32)
        payload = rng.integers(0, 1 << 31, (n, W)).astype(np.int32)
        # make payload row 0 a fingerprint of the key so row identity
        # survives duplicate keys
        payload[:, 0] = keys
        sorter = TeraSorter(make_mesh())
        (sk, sp, n_valid, max_fill), cap = sorter.sort_device_wide(
            jnp.asarray(keys), jnp.asarray(payload)
        )
        assert int(np.max(np.asarray(max_fill))) <= cap
        D = sorter.n_devices
        sk_h = np.asarray(sk).reshape(D, -1)
        sp_h = np.asarray(sp).reshape(D, D * cap, W)
        nv = np.asarray(n_valid).reshape(-1)
        out_k = np.concatenate([sk_h[d, : nv[d]] for d in range(D)])
        out_p = np.concatenate([sp_h[d, : nv[d]] for d in range(D)])
        assert out_k.shape[0] == n
        np.testing.assert_array_equal(out_k, np.sort(keys))
        # every payload row still sits next to its key...
        np.testing.assert_array_equal(out_p[:, 0], out_k)
        # ...and the multiset of payload rows is exactly preserved
        order_in = np.lexsort(payload.T[::-1])
        order_out = np.lexsort(out_p.T[::-1])
        np.testing.assert_array_equal(
            payload[order_in], out_p[order_out]
        )


def no_global_host_array(monkeypatch):
    """Make ``np.asarray`` of a sharded (not fully replicated) device
    array raise: JAX would assemble it into one global host array."""
    from jax._src.array import ArrayImpl

    value = ArrayImpl._value

    def whole_or_raise(self):
        if not self.is_fully_replicated:
            raise AssertionError(f"global host array of {self.shape}")
        return value.fget(self)

    monkeypatch.setattr(ArrayImpl, "_value", property(whole_or_raise))


@pytest.mark.parametrize("d", [1, 4])
@pytest.mark.parametrize("wide", [True, False])
def test_terasort_exact_from_per_device_runs(devices, monkeypatch, d, wide):
    """The sort is exact from the devices' runs alone, fetched shard by
    shard (no global host array); its result is read-only and stays as
    it is while a second sort of the same shape runs."""
    no_global_host_array(monkeypatch)
    sorter = TeraSorter(make_mesh(d))
    rng = np.random.default_rng(40 + d)
    # wide rows must divide D; narrow rows sit off the shape ladder
    n = 4096 if wide else 4001

    def job():
        keys = rng.integers(-50, 50, n, dtype=np.int32)  # many ties
        rid = np.arange(n, dtype=np.int32)
        vals = rng.integers(0, 1 << 30, (n, 24), dtype=np.int32) \
            if wide else rid
        if wide:
            vals[:, 0] = rid
        return keys, vals, sorter.sort(keys, vals)

    keys, vals, (sk, sv) = job()
    ids = sv[:, 0] if wide else sv
    np.testing.assert_array_equal(sk, np.sort(keys))
    np.testing.assert_array_equal(np.sort(ids), np.arange(n))
    np.testing.assert_array_equal(keys[ids], sk)
    np.testing.assert_array_equal(vals[ids], sv)
    for out in (sk, sv):
        assert not out.flags.writeable
        with pytest.raises(ValueError, match="read-only"):
            out[0] = 0
    first = sk.copy(), sv.copy()
    job()
    np.testing.assert_array_equal(sk, first[0])
    np.testing.assert_array_equal(sv, first[1])
    empty = sorter.sort(keys[:0], vals[:0])
    assert [e.shape[0] for e in empty] == [0, 0]
    assert not any(e.flags.writeable for e in empty)


@pytest.mark.parametrize("chunk_bytes", [1 << 10, 32 << 20])
@pytest.mark.parametrize("d", [1, 4])
@pytest.mark.parametrize("width", [None, 24])
def test_stitch_joins_the_valid_rows_exactly(devices, monkeypatch,
                                             chunk_bytes, d, width):
    """The stitch's result holds the bytes ``np.concatenate`` gives of
    the devices' valid prefixes, C-ordered and read-only: copied in
    chunks on the stitch pool when the result is more than one chunk
    (valid counts off the chunk, a device with none), on the calling
    thread otherwise; on one device a view of the run, no copy."""
    from sparkrdma_tpu.models import terasort

    monkeypatch.setattr(terasort, "STITCH_CHUNK_BYTES", chunk_bytes)
    rng = np.random.default_rng(d * 7 + (width or 1))
    cap = 3001
    nv = np.array([2999, 0, 1031, cap][:d], np.int32)
    keys = [rng.integers(-1 << 31, 1 << 31, cap, dtype=np.int64)
            .astype(np.int32) for _ in range(d)]
    vals = [rng.integers(-1 << 31, 1 << 31, (cap, width), dtype=np.int64)
            .astype(np.int32) if width else rng.standard_normal(cap)
            for _ in range(d)]
    runs = [keys, vals]
    for run in keys + vals:
        run.flags.writeable = False  # as the fetch hands them over
    pool_calls = terasort._stitch_pool.cache_info()
    out = TeraSorter(make_mesh(d))._stitch(runs, nv)
    pool_calls = sum(terasort._stitch_pool.cache_info()[:2]) - sum(
        pool_calls[:2])
    for o, r in zip(out, runs):
        ref = np.concatenate([r[i][:nv[i]] for i in range(d)])
        assert (o.dtype, o.shape) == (ref.dtype, ref.shape)
        assert o.tobytes() == ref.tobytes()
        assert o.flags.c_contiguous and not o.flags.writeable
        assert np.shares_memory(o, r[0]) == (d == 1)
    pooled = d > 1 and chunk_bytes < sum(o.nbytes for o in out)
    assert pool_calls == pooled
    if d > 1:
        _, chunks, workers = terasort._join_runs(runs, nv)
        rows = [max(1, chunk_bytes // r[0][:1].nbytes) for r in runs]
        assert chunks == sum(-(-int(n) // c) for c in rows for n in nv)
        assert 1 <= workers <= (
            terasort._stitch_pool()._max_workers if pooled else 1)


@pytest.mark.parametrize("model", ["count", "aggregate", "top_k"])
def test_keyed_models_exact_from_per_device_runs(devices, monkeypatch,
                                                 model):
    """The keyed models stitch their result from the per-device runs
    at D == 4, fetched shard by shard: no global host array."""
    from sparkrdma_tpu.models import KeyedAggregator
    from sparkrdma_tpu.models.topk import GroupedTopK

    no_global_host_array(monkeypatch)
    m4 = make_mesh(4)
    rng = np.random.default_rng(61)
    keys = rng.integers(0, 300, 20_011, dtype=np.int32)
    vals = rng.integers(-1000, 1000, 20_011, dtype=np.int32)
    groups = {int(k): vals[keys == k] for k in np.unique(keys)}
    if model == "count":
        assert WordCounter(m4).count(keys, vals) == {
            k: int(v.sum()) for k, v in groups.items()}
    elif model == "aggregate":
        stats = KeyedAggregator(m4).aggregate(keys, vals)
        assert {k: tuple(s) for k, s in stats.items()} == {
            k: (int(v.sum()), len(v), int(v.min()), int(v.max()))
            for k, v in groups.items()}
    else:
        assert GroupedTopK(m4).top_k(keys, vals, 3) == {
            k: np.sort(v)[::-1][:3].tolist() for k, v in groups.items()}


@pytest.mark.parametrize("d", [1, 4])
@pytest.mark.parametrize("n_local,width", [
    (1, 24), (127, 24), (128, 24), (129, 24), (2048, 24), (5003, 24),
    (40_000, 24), (3000, 3),
])
def test_placed_rows_are_each_devices_own_rows(devices, d, n_local,
                                                 width):
    """The payload placed as flat words, in pieces, and made rows on
    the mesh gives each device its own rows: whole pieces, a short last
    piece, and a short last piece alone."""
    mesh = make_mesh(d)
    rng = np.random.default_rng(n_local + width)
    rows = rng.integers(-(1 << 31), 1 << 31, (d * n_local, width),
                        dtype=np.int64).astype(np.int32)
    out = TeraSorter(mesh)._place_rows(rows)
    assert out.shape == rows.shape
    for s in out.addressable_shards:
        assert s.data.shape == (n_local, width)
    np.testing.assert_array_equal(np.asarray(out), rows)


@pytest.mark.parametrize("d", [1, 4])
def test_wide_sort_places_host_rows_as_flat_words(devices, monkeypatch, d):
    """No 2-D host array is handed to the runtime: the chip lays [n, W]
    rows out column-major, so placing them whole would transpose them
    on the host.  Every word of the payload is copied once."""
    import jax
    from jax._src.interpreters import pxla

    placed = []
    put = pxla.batched_device_put

    def spy(aval, sharding, xs, devices, *args, **kwargs):
        placed.extend(np.shape(x) for x in xs
                      if not isinstance(x, jax.Array))
        return put(aval, sharding, xs, devices, *args, **kwargs)

    monkeypatch.setattr(pxla, "batched_device_put", spy)
    rng = np.random.default_rng(7)
    n = 8192
    keys = rng.permutation(n).astype(np.int32)  # distinct: one order
    rows = rng.integers(0, 1 << 30, (n, 24), dtype=np.int32)
    sk, sp = TeraSorter(make_mesh(d)).sort(keys, rows)
    np.testing.assert_array_equal(sk, np.arange(n))
    np.testing.assert_array_equal(sp, rows[np.argsort(keys)])
    assert all(len(s) == 1 for s in placed), placed
    assert sum(s[0] for s in placed) == n + n * 24
