"""Experimental Pallas in-block bitonic sort for (key, value) pairs.

XLA's ``lax.sort`` is the cost ceiling of every sort-bound bench
(terasort, the join probes, the keyed reductions).  This kernel sorts
fixed-size blocks entirely in VMEM with a bitonic network — one HBM
read + one write per block — as the building block of a two-phase
(sort blocks → range-bucket → sort buckets) full sort.

Pairing uses the standard XOR network: at distance ``d`` element ``i``
exchanges with ``i ^ d``.  On the [R, 128] row-major block layout a
distance below 128 is a lane XOR (two ``pltpu.roll``s along lanes +
select) and a distance that is a multiple of 128 is a row XOR (rolls
along sublanes), so no general permutes are needed.  Direction bits and
pair order come from 2-D ``broadcasted_iota``.  Ties break by flat
index, which keeps the two sides of every compare-exchange consistent
(the pair moves key and value together).

Blocks default to 512 rows: at 1024 the network's temporaries
exceed the 16 MiB of scoped VMEM and the TPU compiler refuses the
kernel (tests/test_chip_compile.py compiles it for v5e).  Interpret
mode pins its semantics (tests).  Nothing dispatches to it by default
— call sites must opt in after the chip benchmark (``shufflebench/``)
shows it beating ``lax.sort`` on chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

LANES = 128


class BucketOverflowError(RuntimeError):
    """A bucket exceeded its capacity in sort_pairs_full: the sorted
    output is garbage (see the overflow contract in its docstring)."""


def bucket_cap(n: int, n_buckets: int = 16,
               cap_factor: float = 1.4) -> int:
    """Per-bucket row capacity sort_pairs_full allocates for ``n``
    rows; a bucket fill above this invalidates the whole result."""
    cap = int(np.ceil(n / n_buckets * cap_factor))
    return (cap + LANES - 1) // LANES * LANES


def _partner(x, d, R, interpret):
    """partner[i] = x[i ^ d] over the flat row-major [R, 128] order."""
    if d < LANES:
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, LANES), 1)
        take_fwd = (lane & d) == 0
        if interpret:
            fwd = jnp.roll(x, LANES - d, axis=1)
            bwd = jnp.roll(x, d, axis=1)
        else:
            from jax.experimental.pallas import tpu as pltpu

            # pltpu.roll requires non-negative shifts: a circular
            # backward roll by d is a forward roll by size - d
            fwd = pltpu.roll(x, LANES - d, 1)
            bwd = pltpu.roll(x, d, 1)
        return jnp.where(take_fwd, fwd, bwd)
    m = d // LANES
    row = jax.lax.broadcasted_iota(jnp.int32, (R, LANES), 0)
    take_fwd = (row & m) == 0
    if interpret:
        fwd = jnp.roll(x, R - m, axis=0)
        bwd = jnp.roll(x, m, axis=0)
    else:
        from jax.experimental.pallas import tpu as pltpu

        fwd = pltpu.roll(x, R - m, 0)
        bwd = pltpu.roll(x, m, 0)
    return jnp.where(take_fwd, fwd, bwd)


def _block_sort_body(R, interpret, k_ref, v_ref, ok_ref, ov_ref):
    B = R * LANES
    k = k_ref[...]
    v = v_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (R, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, LANES), 1)
    flat = row * LANES + lane
    n_stages = B.bit_length() - 1
    for stage in range(1, n_stages + 1):
        # ascending iff bit ``stage`` of the flat index is clear; the
        # final stage has that bit clear everywhere → fully ascending
        up = (flat & (1 << stage)) == 0 if stage < n_stages else (
            jnp.ones((R, LANES), bool)
        )
        for j in range(stage - 1, -1, -1):
            d = 1 << j
            pk = _partner(k, d, R, interpret)
            pv = _partner(v, d, R, interpret)
            is_lower = (flat & d) == 0
            # pair-consistent "my element is the smaller": ties go to
            # the lower flat index
            mine_small = (k < pk) | ((k == pk) & is_lower)
            take_min = up == is_lower
            want_mine = take_min == mine_small
            k = jnp.where(want_mine, k, pk)
            v = jnp.where(want_mine, v, pv)
    ok_ref[...] = k
    ov_ref[...] = v


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret")
)
def sort_pairs_blocks(keys, vals, block_rows: int = 512,
                      interpret: bool = False):
    """Sort (keys, vals) within consecutive blocks of
    ``block_rows * 128`` elements (each block independently ascending
    by key).  Input length must be a multiple of the block size;
    dtypes: any 32-bit integer keys (compared in their own dtype).
    """
    n = int(keys.shape[0])
    B = block_rows * LANES
    if n % B:
        raise ValueError(f"length {n} not a multiple of block {B}")
    if B & (B - 1):
        raise ValueError(f"block size {B} must be a power of two")
    R = block_rows
    k2 = keys.reshape(-1, LANES)
    v2 = vals.reshape(-1, LANES)
    grid = (n // B,)
    blk = pl.BlockSpec((R, LANES), lambda i: (i, 0))
    kernel = functools.partial(_block_sort_body, R, interpret)
    ok, ov = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[blk, blk],
        out_specs=[blk, blk],
        out_shape=[
            jax.ShapeDtypeStruct(k2.shape, k2.dtype),
            jax.ShapeDtypeStruct(v2.shape, v2.dtype),
        ],
        interpret=interpret,
    )(k2, v2)
    return ok.reshape(-1), ov.reshape(-1)


def sort_pairs_full(keys, vals, block_rows: int = 512,
                    n_buckets: int = 16, cap_factor: float = 1.4,
                    interpret: bool = False):
    """Full (key, value) sort: Pallas block sorts → equal-frequency
    splitters from block quantiles → window-copy bucket assembly (the
    terasort pattern on one chip) → batched bucket sort.  Returns
    ``(keys', vals', valid, fn, overflow)`` of padded length
    ``n_buckets * cap`` with ``valid`` marking real slots (padding
    sorts to each bucket's tail).

    OVERFLOW CONTRACT: when splitters are badly skewed a bucket can
    receive more than ``cap = bucket_cap(n, n_buckets, cap_factor)``
    rows; the assembly then clamps its writes and ALL outputs are
    garbage (earlier rows silently overwritten, invalid slots marked
    valid).  Callers MUST verify ``overflow <= bucket_cap(...)``
    (device-side, no sync needed: it is the max per-bucket fill) and
    discard the result or retry with a higher ``cap_factor`` when it
    fails — or call :func:`sort_pairs_full_checked`, which raises
    ``BucketOverflowError``.

    Exactness is pinned by tests vs numpy; wire into the sorter only
    after on-chip profiling (module docstring).
    """
    n = int(keys.shape[0])
    B = block_rows * LANES
    if n % B or n == 0:
        raise ValueError(f"length {n} must be a positive multiple of {B}")
    nb = n // B
    sk, sv = sort_pairs_blocks(
        keys, vals, block_rows=block_rows, interpret=interpret
    )
    kb = sk.reshape(nb, B)
    vb = sv.reshape(nb, B)
    # equal-frequency splitters from exact per-block quantiles
    S = min(512, B)
    sample = kb[:, (jnp.arange(S) * B) // S].reshape(-1)
    ssorted = jnp.sort(sample)
    idx = (jnp.arange(1, n_buckets) * ssorted.shape[0]) // n_buckets
    splitters = ssorted[idx]
    edges = jax.vmap(
        lambda row: jnp.searchsorted(row, splitters, side="right")
    )(kb).astype(jnp.int32)                       # [nb, n_buckets-1]
    zeros = jnp.zeros((nb, 1), jnp.int32)
    fulls = jnp.full((nb, 1), B, jnp.int32)
    edges = jnp.concatenate([zeros, edges, fulls], axis=1)
    counts = edges[:, 1:] - edges[:, :-1]         # [nb, n_buckets]
    starts = edges[:, :-1]
    cap = bucket_cap(n, n_buckets, cap_factor)
    sentinel = jnp.array(jnp.iinfo(keys.dtype).max, keys.dtype)
    bucket_off = jnp.cumsum(counts, axis=0) - counts  # offset of block b
    kp = jnp.concatenate(
        [kb, jnp.full((nb, cap), sentinel, kb.dtype)], axis=1
    )
    vp = jnp.concatenate([vb, jnp.zeros((nb, cap), vb.dtype)], axis=1)

    def fill(i, bufs):
        fk, fv, fn = bufs
        b = i // n_buckets
        dst = i % n_buckets
        wk = jax.lax.dynamic_slice(kp[b], (starts[b, dst],), (cap,))
        wv = jax.lax.dynamic_slice(vp[b], (starts[b, dst],), (cap,))
        off = bucket_off[b, dst]
        c = counts[b, dst]
        slot = jnp.arange(cap, dtype=jnp.int32)
        old_k = jax.lax.dynamic_slice(fk[dst], (off,), (cap,))
        old_v = jax.lax.dynamic_slice(fv[dst], (off,), (cap,))
        take = slot < c
        fk = jax.lax.dynamic_update_slice(
            fk, jnp.where(take, wk, old_k)[None], (dst, off)
        )
        fv = jax.lax.dynamic_update_slice(
            fv, jnp.where(take, wv, old_v)[None], (dst, off)
        )
        fn = fn.at[dst].add(c)
        return fk, fv, fn

    fk0 = jnp.full((n_buckets, cap + cap), sentinel, kb.dtype)
    fv0 = jnp.zeros((n_buckets, cap + cap), vb.dtype)
    fn0 = jnp.zeros((n_buckets,), jnp.int32)
    fk, fv, fn = jax.lax.fori_loop(
        0, nb * n_buckets, fill, (fk0, fv0, fn0)
    )
    overflow = jnp.max(fn)
    fk = fk[:, :cap]
    fv = fv[:, :cap]
    # bucket sort: padding carries the sentinel and a validity tiebreak
    slot = jnp.arange(cap, dtype=jnp.int32)
    invalid = (slot[None, :] >= fn[:, None]).astype(jnp.int32)
    fk = jnp.where(invalid > 0, sentinel, fk)
    fv = jnp.where(invalid > 0, jnp.zeros((), fv.dtype), fv)
    ok, oinv, ov = jax.lax.sort(
        (fk, invalid, fv), num_keys=2, is_stable=False, dimension=1
    )
    valid = jnp.int32(1) - oinv
    return (
        ok.reshape(-1), ov.reshape(-1), valid.reshape(-1),
        fn, overflow,
    )


def sort_pairs_full_checked(keys, vals, block_rows: int = 512,
                            n_buckets: int = 16,
                            cap_factor: float = 1.4,
                            interpret: bool = False):
    """sort_pairs_full with the overflow contract enforced: syncs the
    per-bucket max fill to the host and raises
    :class:`BucketOverflowError` instead of returning garbage.  Use the
    raw function + a device-side ``overflow <= bucket_cap(...)`` check
    when the sync is too expensive."""
    ok, ov, valid, fn, overflow = sort_pairs_full(
        keys, vals, block_rows=block_rows, n_buckets=n_buckets,
        cap_factor=cap_factor, interpret=interpret,
    )
    cap = bucket_cap(int(keys.shape[0]), n_buckets, cap_factor)
    ovf = int(jax.device_get(overflow))
    if ovf > cap:
        raise BucketOverflowError(
            f"bucket fill {ovf} > cap {cap} "
            f"(n={int(keys.shape[0])}, n_buckets={n_buckets}, "
            f"cap_factor={cap_factor}) — retry with a higher cap_factor"
        )
    return ok, ov, valid, fn, overflow
