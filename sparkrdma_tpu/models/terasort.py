"""TeraSort on the mesh: the flagship workload.

The reference's headline benchmark is HiBench TeraSort 175 GB — a
``sortByKey`` whose shuffle moves every record once over the NIC
(README.md:7-19).  Here the whole job is ONE jitted SPMD program per
step:

    local sort → quantile sample → splitters → contiguous destination
    windows → all_to_all → merge received sorted runs

Each device sorts its local pairs first (so the sample is an exact local
quantile sketch and destination windows are contiguous — bucketing is
pure sequential gathers, zero scatters), the sample is all-gathered to
derive global equal-frequency splitters, windows are exchanged with a
single ``all_to_all`` riding ICI, and the received runs are merged.
The concatenation of the devices' outputs (trimmed by the true counts)
is the global sort.

Validity is tracked as an explicit 0/1 column ordered as a secondary
sort key, so padding always sorts strictly after real records — real
keys equal to the dtype max are NOT confused with padding.

Skew handling: buckets are capacity-padded (static shapes); true counts
travel with the exchange, and overflow (count > capacity) is detected on
the host, which re-runs with a larger capacity factor — the SPMD analog
of the reference's maxAggBlock fetch cap (SURVEY.md §7 hard parts).
"""

from __future__ import annotations

import functools
import math
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from sparkrdma_tpu.models._base import ExchangeModel, faulting_span
from sparkrdma_tpu.ops.partition import make_range_splitters
from sparkrdma_tpu.parallel.mesh import EXCHANGE_AXIS
from sparkrdma_tpu.utils.trace import get_tracer


def _read_only(*arrays: np.ndarray) -> Tuple[np.ndarray, ...]:
    for a in arrays:
        a.flags.writeable = False
    return arrays


STITCH_CHUNK_BYTES = 32 << 20  # most bytes of a column one stitch copy moves
STITCH_WORKERS = 16  # most host threads the stitch copies on


@functools.cache
def _stitch_pool() -> ThreadPoolExecutor:
    """The host threads the stitch copies on: one pool a process, made
    on first use (``device_sort`` makes a TeraSorter a call), a thread a
    usable CPU up to STITCH_WORKERS.  They make no JAX call."""
    return ThreadPoolExecutor(
        min(STITCH_WORKERS, len(os.sched_getaffinity(0))),
        thread_name_prefix="terasort-stitch")


# a forked child inherits the pool but none of its threads
os.register_at_fork(after_in_child=_stitch_pool.cache_clear)


def _join_runs(runs, nv):
    """Each column's per-device runs trimmed to their valid counts
    ``nv`` and joined into one fresh C-ordered array, the bytes
    ``np.concatenate`` gives.  Each device's rows are copied in row
    chunks of at most STITCH_CHUNK_BYTES into their slice of the result;
    a result of more than one chunk is copied on the stitch pool, so the
    first touch of its fresh pages is spread over host threads.  Returns
    (read-only columns, copies made, threads that made them)."""
    nv = np.asarray(nv).tolist()
    offs = np.cumsum([0] + nv).tolist()  # where each device's rows go
    outs, copies = [], []
    for r in runs:
        out = np.empty((offs[-1],) + r[0].shape[1:], r[0].dtype)
        step = max(1, STITCH_CHUNK_BYTES // max(
            1, out.itemsize * math.prod(out.shape[1:])))
        for run, at, n in zip(r, offs, nv):
            for lo in range(0, n, step):
                hi = min(lo + step, n)
                copies.append((out[at + lo:at + hi], run[lo:hi]))
        outs.append(out)

    def copy(dst_src):
        dst, src = dst_src
        dst[...] = src  # numpy lets go of the GIL for the copy
        return threading.get_ident()

    if sum(o.nbytes for o in outs) <= STITCH_CHUNK_BYTES:
        threads = set(map(copy, copies))
    else:
        threads = set(_stitch_pool().map(copy, copies))
    return _read_only(*outs), len(copies), len(threads)


def _sample_positions(n_local: int, sample_size: int) -> np.ndarray:
    """Exact local quantile positions i*n/S, computed on the host in
    64 bits: in int32, i*n overflows once S*n reaches 2^31 (n_local
    2^21 at S=1024), which skews the splitters and overflows buckets."""
    i = np.arange(sample_size, dtype=np.int64)
    return (i * n_local // sample_size).astype(np.int32)


def _local_sort_step(keys, vals, valid, n_devices, capacity, sample_size):
    """Per-device body (runs under shard_map).  keys/vals: [n_local];
    ``valid`` is int32 0/1 or None (= everything valid, skips the column).

    Invalid (padding) slots sort after every real slot of the same key
    via the secondary sort key, and are excluded from counts.
    """
    n_local = keys.shape[0]
    if n_devices == 1:
        # degenerate mesh: a distributed sort on one device IS the local
        # sort — skip sampling, windowing, the all_to_all, and the merge
        # re-sort entirely (they would re-sort the same data)
        sentinel = jnp.array(jnp.iinfo(keys.dtype).max, keys.dtype)
        if valid is None:
            k, v = jax.lax.sort((keys, vals), num_keys=1, is_stable=False)
            n_real = jnp.int32(n_local)
        else:
            inv = jnp.int32(1) - valid
            keys = jnp.where(valid > 0, keys, sentinel)
            k, _, v = jax.lax.sort(
                (keys, inv, vals), num_keys=2, is_stable=False
            )
            n_real = jnp.sum(valid).astype(jnp.int32)
        pad = capacity - n_local
        if pad < 0:
            k, v = k[:capacity], v[:capacity]
        else:
            k = jnp.concatenate([k, jnp.full((pad,), sentinel, k.dtype)])
            v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
        n_valid = jnp.minimum(n_real, jnp.int32(capacity))
        return k, v, n_valid, jnp.int32(n_local)
    if valid is None:
        # fast path: every input slot is real
        k, v = jax.lax.sort((keys, vals), num_keys=1, is_stable=False)
        n_real = jnp.int32(n_local)
    else:
        # force invalid slots onto the dtype-max key, then the
        # (key, invalid) two-key sort puts every invalid slot at the
        # global tail (max-key group, ordered after real max-keyed
        # records within it), so validity per destination window is
        # always a SUFFIX — a per-window valid count replaces a whole
        # per-element column.  The rewrite makes the suffix property
        # hold for ARBITRARY caller-supplied (keys, valid), not just
        # inputs whose invalid slots already carry the sentinel.
        inv = jnp.int32(1) - valid
        keys = jnp.where(
            valid > 0, keys, jnp.array(jnp.iinfo(keys.dtype).max, keys.dtype)
        )
        k, _, v = jax.lax.sort((keys, inv, vals), num_keys=2, is_stable=False)
        n_real = jnp.sum(valid).astype(jnp.int32)
    # exact local quantiles (k is sorted): positions i*n/S
    sample = k[_sample_positions(n_local, sample_size)]
    all_samples = jax.lax.all_gather(sample, EXCHANGE_AXIS)  # [D, S]
    splitters = make_range_splitters(all_samples.reshape(-1), n_devices)
    # destination windows: device p gets keys in [splitters[p-1], splitters[p])
    edges = jnp.concatenate([
        jnp.zeros(1, jnp.int32),
        jnp.searchsorted(k, splitters, side="right").astype(jnp.int32),
        jnp.full((1,), n_local, jnp.int32),
    ])
    counts = edges[1:] - edges[:-1]                       # shipped counts [D]
    starts = edges[:-1]
    # valid records in window [start, end): everything before the global
    # invalid tail at position n_real
    valid_counts = jnp.clip(
        jnp.minimum(edges[1:], n_real) - starts, 0, capacity
    )
    slot = jnp.arange(capacity, dtype=jnp.int32)
    window_valid = slot[None, :] < jnp.minimum(counts, capacity)[:, None]
    sentinel = jnp.array(jnp.iinfo(k.dtype).max, k.dtype)
    # windows are CONTIGUOUS runs of the locally-sorted arrays, so copy
    # them with dynamic_slice (sequential HBM reads) rather than k[idx]
    # fancy indexing — the latter lowers to a general gather, which on
    # TPU costs ~30× the bandwidth-bound copy for these shapes
    kp = jnp.concatenate([k, jnp.full((capacity,), sentinel, k.dtype)])
    vp = jnp.concatenate([v, jnp.zeros((capacity,), v.dtype)])

    def fill(p, bufs):
        fk, fv = bufs
        wk = jax.lax.dynamic_slice(kp, (starts[p],), (capacity,))
        wv = jax.lax.dynamic_slice(vp, (starts[p],), (capacity,))
        fk = jax.lax.dynamic_update_slice(fk, wk[None], (p, 0))
        fv = jax.lax.dynamic_update_slice(fv, wv[None], (p, 0))
        return fk, fv

    # pcast-to-varying: the loop carry must be device-varying like the
    # filled windows, or shard_map rejects the replicated zeros init
    bk0 = jax.lax.pcast(
        jnp.zeros((n_devices, capacity), k.dtype), EXCHANGE_AXIS, to="varying"
    )
    bv0 = jax.lax.pcast(
        jnp.zeros((n_devices, capacity), v.dtype), EXCHANGE_AXIS, to="varying"
    )
    bk, bv = jax.lax.fori_loop(0, n_devices, fill, (bk0, bv0))
    bk = jnp.where(window_valid, bk, sentinel)            # [D, cap]
    bv = jnp.where(window_valid, bv, jnp.zeros((), v.dtype))
    # exchange: device d keeps row d of every source
    rk = jax.lax.all_to_all(bk, EXCHANGE_AXIS, split_axis=0, concat_axis=0)
    rv = jax.lax.all_to_all(bv, EXCHANGE_AXIS, split_axis=0, concat_axis=0)
    rvalid = jax.lax.all_to_all(
        valid_counts.reshape(n_devices, 1), EXCHANGE_AXIS,
        split_axis=0, concat_axis=0,
    ).reshape(n_devices)
    n_valid = jnp.sum(rvalid).astype(jnp.int32)
    # reconstruct per-slot validity from the suffix property, then merge
    # the D received runs with validity as tiebreak so padding (incl.
    # pads whose key equals a real max-valued key) sorts strictly last
    riv = (slot[None, :] >= rvalid[:, None]).astype(jnp.int32).reshape(-1)
    sorted_k, sorted_iv, sorted_v = jax.lax.sort(
        (rk.reshape(-1), riv, rv.reshape(-1)),
        num_keys=2, is_stable=False,
    )
    # overflow indicator: true pre-clamp counts, maxed over destinations
    overflow = jnp.max(counts).astype(jnp.int32)
    return sorted_k, sorted_v, n_valid, overflow


def _local_sort_wide_step(keys, payload, n_devices, capacity,
                          sample_size):
    """Wide-record variant (the HiBench TeraSort shape: 10B key + 90B
    value, README.md:7-19): keys [n_local] ride the sort/sample/window
    machinery with a row INDEX as the carried operand, and the payload
    matrix [n_local, W] follows via two batched row gathers plus the
    same all_to_all — the sort cost is unchanged while every exchanged
    record carries ``8 + 4W`` bytes."""
    n_local = keys.shape[0]
    W = payload.shape[1]
    sentinel = jnp.array(jnp.iinfo(keys.dtype).max, keys.dtype)
    iota = jnp.arange(n_local, dtype=jnp.int32)
    if n_devices == 1:
        k, perm = jax.lax.sort((keys, iota), num_keys=1, is_stable=False)
        p = jnp.take(payload, perm, axis=0)
        pad = capacity - n_local
        if pad < 0:
            k, p = k[:capacity], p[:capacity]
        elif pad:
            k = jnp.concatenate([k, jnp.full((pad,), sentinel, k.dtype)])
            p = jnp.concatenate([p, jnp.zeros((pad, W), p.dtype)], axis=0)
        n_valid = jnp.minimum(jnp.int32(n_local), jnp.int32(capacity))
        return k, p, n_valid, jnp.int32(n_local)
    k, perm = jax.lax.sort((keys, iota), num_keys=1, is_stable=False)
    ps = jnp.take(payload, perm, axis=0)
    sample = k[_sample_positions(n_local, sample_size)]
    all_samples = jax.lax.all_gather(sample, EXCHANGE_AXIS)
    splitters = make_range_splitters(all_samples.reshape(-1), n_devices)
    edges = jnp.concatenate([
        jnp.zeros(1, jnp.int32),
        jnp.searchsorted(k, splitters, side="right").astype(jnp.int32),
        jnp.full((1,), n_local, jnp.int32),
    ])
    counts = edges[1:] - edges[:-1]
    starts = edges[:-1]
    clamped = jnp.minimum(counts, capacity)
    slot = jnp.arange(capacity, dtype=jnp.int32)
    window_valid = slot[None, :] < clamped[:, None]
    kp = jnp.concatenate([k, jnp.full((capacity,), sentinel, k.dtype)])
    pp = jnp.concatenate(
        [ps, jnp.zeros((capacity, W), ps.dtype)], axis=0
    )

    def fill(p_, bufs):
        fk, fp = bufs
        wk = jax.lax.dynamic_slice(kp, (starts[p_],), (capacity,))
        wp = jax.lax.dynamic_slice(pp, (starts[p_], 0), (capacity, W))
        fk = jax.lax.dynamic_update_slice(fk, wk[None], (p_, 0))
        fp = jax.lax.dynamic_update_slice(fp, wp[None], (p_, 0, 0))
        return fk, fp

    bk0 = jax.lax.pcast(
        jnp.zeros((n_devices, capacity), k.dtype), EXCHANGE_AXIS,
        to="varying",
    )
    bp0 = jax.lax.pcast(
        jnp.zeros((n_devices, capacity, W), ps.dtype), EXCHANGE_AXIS,
        to="varying",
    )
    bk, bp = jax.lax.fori_loop(0, n_devices, fill, (bk0, bp0))
    bk = jnp.where(window_valid, bk, sentinel)
    rk = jax.lax.all_to_all(bk, EXCHANGE_AXIS, split_axis=0, concat_axis=0)
    rp = jax.lax.all_to_all(bp, EXCHANGE_AXIS, split_axis=0, concat_axis=0)
    rvalid = jax.lax.all_to_all(
        clamped.reshape(n_devices, 1), EXCHANGE_AXIS,
        split_axis=0, concat_axis=0,
    ).reshape(n_devices)
    n_valid = jnp.sum(rvalid).astype(jnp.int32)
    riv = (slot[None, :] >= rvalid[:, None]).astype(jnp.int32).reshape(-1)
    iota2 = jnp.arange(n_devices * capacity, dtype=jnp.int32)
    sorted_k, _siv, perm2 = jax.lax.sort(
        (rk.reshape(-1), riv, iota2), num_keys=2, is_stable=False
    )
    sorted_p = jnp.take(
        rp.reshape(n_devices * capacity, W), perm2, axis=0
    )
    overflow = jnp.max(counts).astype(jnp.int32)
    return sorted_k, sorted_p, n_valid, overflow


@functools.lru_cache(maxsize=16)
def make_wide_sort_step(mesh: Mesh, n_local: int, payload_words: int,
                        capacity: int, sample_size: int = 1024):
    """Jitted wide-record sort step: fn(keys [D*n_local], payload
    [D*n_local, W]) → (keys' [D, D*cap], payload' [D, D*cap, W],
    valid counts [D], max bucket fill [D])."""
    D = len(list(mesh.devices.flat))
    from jax.sharding import PartitionSpec as P

    spec = P(EXCHANGE_AXIS)
    spec2 = P(EXCHANGE_AXIS, None)

    def terasort_wide_step(k, p):
        sk, sp, n_valid, overflow = _local_sort_wide_step(
            k, p, D, capacity, sample_size
        )
        return sk, sp, n_valid[None], overflow[None]

    mapped = jax.shard_map(
        terasort_wide_step, mesh=mesh, in_specs=(spec, spec2),
        out_specs=(spec, spec2, spec, spec),
    )
    return jax.jit(mapped)


PIECES = 16  # equal pieces a device's payload words are placed in
PIECE_GROUP = 128  # their rows are a multiple of this; the short last's not


def piece_rows(n_local: int) -> Tuple[int, ...]:
    """Rows of each piece a device's payload is placed in: PIECES equal
    runs of a multiple of PIECE_GROUP rows, then what is left (fewer
    than PIECES * PIECE_GROUP rows), if anything."""
    c = n_local // PIECES // PIECE_GROUP * PIECE_GROUP
    tail = n_local - PIECES * c
    return (c,) * PIECES * (c > 0) + (tail,) * (tail > 0)


def _rows_from_words(*pieces, width: int):
    """Per-device body (runs under shard_map): pieces of [rows*width]
    row-major words, in row order → [n, width] rows.

    XLA:TPU lays a narrow [n, width] array out column-major (n minor,
    tiled), so rows handed to ``device_put`` as they sit in host memory
    are first transposed on the host by the runtime.  Placing flat
    words is a plain copy (in pieces, which the runtime copies side by
    side); this transposes them on the chip instead, a piece at a time:
    through [rows/128, 128*width] and [width, rows] views, whose minor
    dimensions are multiples of 128, so nothing is padded out to 128
    lanes, and a piece bounds what the transpose holds besides its
    input and output."""
    g = PIECE_GROUP
    n = sum(p.shape[0] for p in pieces) // width
    out = jax.lax.pcast(
        jnp.zeros((width, n), pieces[0].dtype), EXCHANGE_AXIS, to="varying"
    )
    start = 0
    for p in pieces:
        c = p.shape[0] // width
        if c % g:  # the short last piece: a padded [c, 128] is small
            cols = p.reshape(c, width).T
        else:
            cols = (p.reshape(c // g, g * width).T
                    .reshape(g, width, c // g).transpose(1, 2, 0)
                    .reshape(width, c))
        out = jax.lax.dynamic_update_slice(out, cols, (0, start))
        start += c
    return out.T


@functools.lru_cache(maxsize=16)
def make_rows_from_words(mesh: Mesh, width: int, pieces: int):
    """Jitted fn(*pieces, each [D*rows*width] sharded on the mesh) →
    rows [D*n_local, width], each device's rows from its own pieces of
    words (:func:`piece_rows`)."""
    from jax.sharding import PartitionSpec as P

    def terasort_rows_from_words(*words):
        return _rows_from_words(*words, width=width)

    return jax.jit(jax.shard_map(
        terasort_rows_from_words, mesh=mesh,
        in_specs=(P(EXCHANGE_AXIS),) * pieces,
        out_specs=P(EXCHANGE_AXIS, None),
    ))


@functools.lru_cache(maxsize=16)
def make_sort_step(
    mesh: Mesh, n_local: int, capacity: int, sample_size: int = 1024,
    with_validity: bool = True,
):
    """Build the jitted distributed-sort step for a fixed local size.

    With ``with_validity`` the step is fn(keys, vals, valid) where
    ``valid`` int32 0/1 marks real records; without, fn(keys, vals)
    treats every slot as real (the no-padding fast path).  Arrays are
    GLOBAL [D * n_local] sharded on the mesh axis; outputs are
    per-device sorted runs
    (keys' [D, D*capacity], vals', valid counts [D], max bucket fill [D]).
    """
    D = len(list(mesh.devices.flat))
    from jax.sharding import PartitionSpec as P

    spec = P(EXCHANGE_AXIS)

    if with_validity:
        def terasort_step(k, v, valid):  # local [n_local]
            sk, sv, n_valid, overflow = _local_sort_step(
                k, v, valid, D, capacity, sample_size
            )
            return sk, sv, n_valid[None], overflow[None]

        in_specs = (spec, spec, spec)
    else:
        def terasort_step(k, v):  # local [n_local]
            sk, sv, n_valid, overflow = _local_sort_step(
                k, v, None, D, capacity, sample_size
            )
            return sk, sv, n_valid[None], overflow[None]

        in_specs = (spec, spec)

    mapped = jax.shard_map(
        terasort_step, mesh=mesh, in_specs=in_specs,
        out_specs=(spec, spec, spec, spec),
    )
    return jax.jit(mapped)


class TeraSorter(ExchangeModel):
    """Host-facing driver for the distributed sort (the sortByKey job).

    ``sort(keys, vals)`` pads to the mesh, runs the SPMD step, re-runs
    with doubled capacity on overflow, and returns globally sorted
    host arrays.
    """

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        capacity_factor: float = 1.3,
        sample_size: int = 1024,
    ):
        super().__init__(mesh, capacity_factor)
        self.sample_size = sample_size

    def sort_device(
        self, keys: jax.Array, vals: jax.Array,
        valid: Optional[jax.Array] = None, capacity: Optional[int] = None,
    ):
        """One SPMD sort step on device-resident global arrays whose
        length is a multiple of D.  Returns device results unfetched
        (async) — the jittable hot path."""
        n = keys.shape[0]
        if n % self.n_devices:
            raise ValueError(f"length {n} not divisible by D={self.n_devices}")
        n_local = n // self.n_devices
        cap = capacity or self._capacity(n_local)
        step = make_sort_step(
            self.mesh, n_local, cap, min(self.sample_size, max(1, n_local)),
            with_validity=valid is not None,
        )
        keys = jax.device_put(keys, self.sharding)
        vals = jax.device_put(vals, self.sharding)
        if valid is None:
            return step(keys, vals), cap
        valid = jax.device_put(valid, self.sharding)
        return step(keys, vals, valid), cap

    def sort_device_wide(
        self, keys: jax.Array, payload: jax.Array,
        capacity: Optional[int] = None,
    ):
        """Wide-record sort step (HiBench shape): ``payload`` is
        [n, W] int32 rows that follow their keys through the exchange.
        Length must divide D; returns device results unfetched."""
        n = keys.shape[0]
        if n % self.n_devices:
            raise ValueError(
                f"length {n} not divisible by D={self.n_devices}"
            )
        if payload.ndim != 2 or payload.shape[0] != n:
            raise ValueError(
                f"payload must be [n, W], got {payload.shape}"
            )
        n_local = n // self.n_devices
        cap = capacity or self._capacity(n_local)
        step = make_wide_sort_step(
            self.mesh, n_local, int(payload.shape[1]), cap,
            min(self.sample_size, max(1, n_local)),
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        keys = jax.device_put(keys, self.sharding)
        payload = jax.device_put(
            payload, NamedSharding(self.mesh, P(EXCHANGE_AXIS, None))
        )
        return step(keys, payload), cap

    def sort(self, keys, vals=None) -> Tuple[np.ndarray, np.ndarray]:
        """Full host-facing sortByKey: returns (sorted_keys, sorted_vals).
        ``vals`` of shape [n, W] are wide-record payload rows (the
        HiBench shape) that ride their keys (:meth:`sort_device_wide`).

        The returned arrays are read-only: on one device they are views
        of the buffers the devices' runs were fetched into (which hold
        the capacity-padded run until the result is dropped), so copy
        them to write."""
        keys = np.asarray(keys)
        with get_tracer().span("shuffle.device.sort", rows=keys.size):
            return self._sort(keys, vals)

    def _sort(self, keys: np.ndarray, vals):
        if vals is None:
            vals = np.zeros_like(keys)
        vals = np.asarray(vals)
        if keys.ndim != 1 or vals.shape[:1] != keys.shape \
                or vals.ndim > 2:
            raise ValueError(
                "keys must be 1-D and vals [n] or [n, W] rows"
            )
        n = keys.shape[0]
        if n == 0:
            return _read_only(keys.copy(), vals.copy())
        if vals.ndim == 2:
            return self._sort_wide(keys, vals)
        # pad to a multiple of D on the compile-shape ladder
        # (_base.quantize_padded_length); padding is tracked by the
        # validity column (NOT by key value), so max-valued real keys
        # are safe
        with get_tracer().span("shuffle.device.pad") as sp:
            n_pad = self._padded_length(n) - n
            sentinel = np.array(np.iinfo(keys.dtype).max, keys.dtype)
            cols = (keys, vals)
            if n_pad:
                keys = np.concatenate(
                    [keys, np.full(n_pad, sentinel, keys.dtype)])
                vals = np.concatenate([vals, np.zeros(n_pad, vals.dtype)])
                valid = np.ones(n + n_pad, np.int32)
                valid[n:] = 0
                cols = (valid, keys, vals)
            sp.set(bytes=sum(c.nbytes for c in cols))
        *jval, jk, jv = self._place(*cols)
        # no padding: the fast path needs no validity column
        jval = jval[0] if jval else None

        def run(cap):
            (sk, sv, n_valid, max_fill), _ = self.sort_device(
                jk, jv, jval, capacity=cap
            )
            return (sk, sv, n_valid), max_fill

        sk, sv, n_valid = self._run_with_overflow_retry(n + n_pad, run)
        # padding always sorts to each run's tail via the validity key
        return self._stitch(*self._fetch(
            sk, sv, n_valid, row_bytes=keys.itemsize + vals.itemsize))

    def _stitch(self, runs, nv):
        """The sorted result from the per-device runs, each trimmed to
        its valid count: on one device a view of the fetched run, on
        several one copy of the valid rows, made on host threads
        (:func:`_join_runs`).  Read-only either way."""
        with faulting_span("shuffle.device.stitch") as sp:
            if self.n_devices == 1:
                out = tuple(r[0][: nv[0]] for r in runs)
                copied = chunks = workers = 0
            else:
                out, chunks, workers = _join_runs(runs, nv)
                copied = sum(o.nbytes for o in out)
            sp.set(bytes=copied, chunks=chunks, workers=workers)
        return out

    def _sort_wide(self, keys: np.ndarray, payload: np.ndarray):
        """Host-facing wide-record sort: rows are placed once, as flat
        words (:meth:`_place_rows`), sorted on the mesh under the
        overflow-retry policy, and stitched from the per-device runs.
        The wide step carries no validity column, so the length must
        divide D."""
        n, D = keys.shape[0], self.n_devices
        if n % D:
            raise ValueError(
                f"wide rows: length {n} not divisible by D={D}"
            )
        (jk,) = self._place(keys)
        jp = self._place_rows(payload)

        def run(cap):
            (sk, sp, n_valid, max_fill), _ = self.sort_device_wide(
                jk, jp, capacity=cap
            )
            return (sk, sp, n_valid), max_fill

        sk, sp, n_valid = self._run_with_overflow_retry(n, run)
        return self._stitch(*self._fetch(
            sk, sp, n_valid, row_bytes=keys.itemsize + payload[0].nbytes))

    def _place_rows(self, rows: np.ndarray) -> jax.Array:
        """Place [n, W] rows, in one ``place`` span, as their row-major
        words: each device's rows in pieces (:func:`piece_rows`), every
        copy started before any is waited for, then made rows on the
        mesh (:func:`make_rows_from_words`).  The span waits for the
        rows, so the words are freed before the step allocates."""
        D, W = self.n_devices, rows.shape[1]
        words = rows.reshape(D, -1)  # device d's rows, row-major
        with faulting_span("shuffle.device.place", bytes=rows.nbytes,
                           shards=D):
            placed, lo = [], 0
            for c in piece_rows(rows.shape[0] // D):
                placed.append(jax.make_array_from_callback(
                    (D * c * W,), self.sharding,
                    lambda idx, lo=lo, c=c: words[
                        (idx[0].start or 0) // (c * W), lo:lo + c * W]))
                lo += c * W
            return make_rows_from_words(self.mesh, W, len(placed))(
                *placed).block_until_ready()
