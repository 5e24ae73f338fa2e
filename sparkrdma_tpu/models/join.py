"""Device-native equi-joins on the mesh: the SQL-exchange workloads.

The reference's benchmark list ends with Spark SQL TPC-DS q64/q72 —
"broadcast + exchange shuffle" joins (BASELINE.md configs).  These are
the corresponding device-native models, for the star-schema shape those
queries have: a large FACT table joined to a DIMENSION table whose join
keys are unique.

- :class:`HashJoiner` — the exchange-shuffle join: BOTH sides merge
  into one packed (key, role, payload) stream that is hash-partitioned
  and moved with ONE ``all_to_all`` (round 1 ran one exchange per side —
  two bucket sorts and six collectives; the fused stream halves that),
  then every device probes its co-partitioned rows locally.
- :class:`BroadcastJoiner` — the broadcast join: the dimension side is
  small, so it is replicated to every device (``in_specs=P(None)``, the
  all-gather XLA inserts for a replicated operand) and only the fact
  side is sharded; no exchange at all.

The local probe is ONE unstable multi-operand sort keyed ``(key,
role)`` — role 0 = valid dimension, 1 = valid fact, 2 = invalid — so
each key run opens with its (unique) dimension row, followed by a
log-step forward fill that propagates the latest dimension (key, value)
rightward; a fact row matches iff the filled key equals its own.  Both
sides' values ride ONE unsigned payload column (bitcast; uint32, or
uint64 when any column is 64-bit under ``jax_enable_x64`` — narrower
ints/floats widen losslessly) — a row is either a fact or a dimension,
never both.  Alternatives measured on real hardware: the
round-1 formulation (2-key sort + 2 cummax + cumsum + compact-table
gather) ran 54 ms at 4.2M rows because the value gather alone costs
~43 ms (TPU gathers run ~10 cycles/element); the forward fill does the
same fill in ~7 ms, for 17.6 ms total (3.1x).  ``jnp.searchsorted``
lowers to a gather per binary-search step (worse), and a general
``associative_scan`` fill compiles pathologically at multi-million
element sizes.

Output rows are the probe layout with a found mask (1 only on matched
fact rows); unmatched/dimension rows are dropped host-side (inner
join).  Static shapes throughout (SURVEY.md §7 "variable-length blocks"
hard part does not arise).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.models._base import (
    ExchangeModel,
    check_no_silent_truncation,
)
from sparkrdma_tpu.ops.partition import (
    hash_partition_ids,
    partition_to_buckets_dropping,
)
from sparkrdma_tpu.parallel.mesh import EXCHANGE_AXIS

# role column: dimension rows sort before fact rows of the same key,
# invalid (padding / bucket-fill) rows sort last and never match
_ROLE_DIM = 0
_ROLE_FACT = 1
_ROLE_INVALID = 2


def _transport_width(*cols) -> int:
    """Transport word size for the packed stream: 8 bytes as soon as
    any key/value column is 64-bit (only reachable under
    ``jax_enable_x64`` — check_no_silent_truncation rejects int64
    without it), else 4."""
    return 8 if any(np.dtype(c.dtype).itemsize == 8 for c in cols) else 4


def _key_u(k: jax.Array, width: int) -> jax.Array:
    """Injective unsigned view of an integer key column (grouping is
    all the probe needs, so any bijection works)."""
    return k.astype(jnp.uint64 if width == 8 else jnp.uint32)


def _pay_u(v: jax.Array, width: int) -> jax.Array:
    """Lossless unsigned transport view of a value column: same-width
    dtypes bitcast, narrower ints/floats widen first."""
    ut = jnp.uint64 if width == 8 else jnp.uint32
    if v.dtype.itemsize == width:
        return jax.lax.bitcast_convert_type(v, ut)
    if jnp.issubdtype(v.dtype, jnp.floating):
        ft = jnp.float64 if width == 8 else jnp.float32
        return jax.lax.bitcast_convert_type(v.astype(ft), ut)
    it = jnp.int64 if width == 8 else jnp.int32
    return jax.lax.bitcast_convert_type(v.astype(it), ut)


def _pay_from_u(u: jax.Array, dtype, width: int) -> jax.Array:
    """Inverse of :func:`_pay_u`."""
    if np.dtype(dtype).itemsize == width:
        return jax.lax.bitcast_convert_type(u, dtype)
    if jnp.issubdtype(np.dtype(dtype), np.floating):
        ft = jnp.float64 if width == 8 else jnp.float32
        return jax.lax.bitcast_convert_type(u, ft).astype(dtype)
    it = jnp.int64 if width == 8 else jnp.int32
    return jax.lax.bitcast_convert_type(u, it).astype(dtype)


def _pack_sides(lk, lv, l_valid, rk, rv, r_valid):
    """Merge fact and dimension columns into one (key, role, payload)
    unsigned stream (facts first)."""
    w = _transport_width(lk, rk, lv, rv)
    ku = jnp.concatenate([_key_u(lk, w), _key_u(rk, w)])
    role = jnp.concatenate([
        jnp.where(l_valid > 0, jnp.uint32(_ROLE_FACT),
                  jnp.uint32(_ROLE_INVALID)),
        jnp.where(r_valid > 0, jnp.uint32(_ROLE_DIM),
                  jnp.uint32(_ROLE_INVALID)),
    ])
    pay = jnp.concatenate([_pay_u(lv, w), _pay_u(rv, w)])
    return ku, role, pay


def _probe_fill(sk, srole, spay):
    """Log-step forward fill over an already (key, role)-sorted packed
    stream: propagate each (unique-keyed) dimension row's (key, value)
    rightward; a fact row matches iff the filled dimension key equals
    its own (runs with no dimension row inherit a previous run's fill,
    which the key test rejects; invalid rows never fill and never
    match).  Returns ``(dim_val, found)`` with found a bool mask true
    exactly on matched fact rows.  Shared with the fused
    join+aggregate (models/join_aggregate.py), whose sort key differs.
    Large TPU fills run as ONE Pallas pass (ops/scan_kernels.py)
    instead of the log-step loop.
    """
    from sparkrdma_tpu.ops.scan_kernels import (
        MIN_KERNEL_ELEMS,
        kernel_eligible,
        scan_flagged,
        use_scan_kernels,
    )

    m = int(sk.shape[0])
    if (m >= MIN_KERNEL_ELEMS and kernel_eligible(sk, spay)
            and use_scan_kernels()):
        flag, (fkey, fval) = scan_flagged(
            "fill", srole == _ROLE_DIM, (sk, spay)
        )
        found = (srole == _ROLE_FACT) & flag & (fkey == sk)
        return fval, found
    flag = srole == _ROLE_DIM
    fkey = sk
    fval = spay
    s = 1
    while s < m:
        pf = jnp.concatenate([flag[:s], flag[:-s]])
        pk = jnp.concatenate([fkey[:s], fkey[:-s]])
        pv = jnp.concatenate([fval[:s], fval[:-s]])
        need = ~flag
        fkey = jnp.where(need, pk, fkey)
        fval = jnp.where(need, pv, fval)
        flag = flag | pf
        s <<= 1
    found = (srole == _ROLE_FACT) & flag & (fkey == sk)
    return fval, found


def _probe_packed(ku, role, pay):
    """Sort-merge probe over a packed (key, role, payload) stream.

    One unstable sort keyed (key, role) groups each key's run with its
    dimension row first, then the :func:`_probe_fill` forward fill
    matches fact rows.  Returns ``(keys_u, fact_pay, dim_pay, found)``
    with found = 1 exactly on matched fact rows.
    """
    sk, srole, spay = jax.lax.sort(
        (ku, role, pay), num_keys=2, is_stable=False
    )
    fval, found_b = _probe_fill(sk, srole, spay)
    found = found_b.astype(jnp.int32)
    fval = jnp.where(found > 0, fval, jnp.zeros((), fval.dtype))
    is_fact = (srole == _ROLE_FACT).astype(jnp.int32)
    return sk, spay, fval, found, is_fact


@functools.lru_cache(maxsize=16)
def make_hash_join_step(mesh: Mesh, n_left: int, n_right: int,
                        capacity: int):
    """Jitted fused-exchange join step over global [D*n_left] fact and
    [D*n_right] dimension columns sharded on the mesh axis: both sides
    ride ONE hash exchange as a packed stream, then probe locally."""
    D = len(list(mesh.devices.flat))
    spec = P(EXCHANGE_AXIS)

    def body(lk, lv, l_valid, rk, rv, r_valid):  # local shards
        ku, role, pay = _pack_sides(lk, lv, l_valid, rk, rv, r_valid)
        if D == 1:
            eku, erole, epay = ku, role, pay
            fill = jnp.int32(0)
        else:
            # padding rides the trash bucket (consumes no real
            # capacity, excluded from overflow accounting)
            ids = hash_partition_ids(ku, D)
            (bk, br, bp), counts = partition_to_buckets_dropping(
                ids, role != _ROLE_INVALID, (ku, role, pay), D, capacity,
                fill_values=(
                    jnp.zeros((), ku.dtype), jnp.uint32(_ROLE_INVALID),
                    jnp.zeros((), pay.dtype),
                ),
            )
            eku = jax.lax.all_to_all(
                bk, EXCHANGE_AXIS, split_axis=0, concat_axis=0
            ).reshape(-1)
            erole = jax.lax.all_to_all(
                br, EXCHANGE_AXIS, split_axis=0, concat_axis=0
            ).reshape(-1)
            epay = jax.lax.all_to_all(
                bp, EXCHANGE_AXIS, split_axis=0, concat_axis=0
            ).reshape(-1)
            fill = jnp.max(counts).astype(jnp.int32)
        sk, spay, fval, found, is_fact = _probe_packed(eku, erole, epay)
        return sk, spay, fval, found, is_fact, fill[None]

    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 6, out_specs=(spec,) * 6
    )
    return jax.jit(mapped)


@functools.lru_cache(maxsize=16)
def make_broadcast_join_step(mesh: Mesh, n_left: int, n_right_total: int):
    """Jitted broadcast join: fact sharded, dimension replicated."""
    spec = P(EXCHANGE_AXIS)

    def body(lk, lv, l_valid, rk, rv, r_valid):  # rk/rv/r_valid: FULL table
        ku, role, pay = _pack_sides(lk, lv, l_valid, rk, rv, r_valid)
        return _probe_packed(ku, role, pay)

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, P(None), P(None), P(None)),
        out_specs=(spec,) * 5,
    )
    return jax.jit(mapped)


#: join variants (Spark/SQL parity): inner keeps matched fact rows with
#: the dim value; left_outer keeps EVERY fact row plus a matched mask;
#: semi keeps matched fact rows without the dim value (left-semi,
#: TPC-DS q16); anti keeps the UNmatched fact rows (left-anti, q94).
JOIN_HOWS = ("inner", "left_outer", "semi", "anti")


class HashJoiner(ExchangeModel):
    """Exchange-shuffle join of (fact_keys, fact_vals) with a
    unique-keyed (dim_keys, dim_vals); ``how`` picks the variant
    (:data:`JOIN_HOWS`)."""

    def __init__(self, mesh: Optional[Mesh] = None,
                 capacity_factor: float = 1.6):
        super().__init__(mesh, capacity_factor)

    def join(self, fact_keys, fact_vals, dim_keys, dim_vals,
             how: str = "inner"):
        """inner → (keys, fact_vals, dim_vals) for matching fact rows;
        left_outer → (keys, fact_vals, dim_vals, matched) for ALL fact
        rows (dim_vals is 0 where unmatched); semi/anti → (keys,
        fact_vals) for matched/unmatched fact rows.  Input order is not
        preserved."""
        lk, lv = _as_columns(fact_keys, fact_vals)
        rk, rv = _as_columns(dim_keys, dim_vals)
        D = self.n_devices
        lk, lv, l_valid, nl = _pad_to(lk, lv, D, self.quantize_shapes)
        rk, rv, r_valid, nr = _pad_to(rk, rv, D, self.quantize_shapes)

        # place inputs once: only the capacities change between retries
        placed = tuple(
            jax.device_put(x, self.sharding)
            for x in (lk, lv, l_valid, rk, rv, r_valid)
        )

        def run(cap):
            # one capacity for the fused fact+dim stream
            step = make_hash_join_step(self.mesh, nl // D, nr // D, cap)
            sk, spay, fval, found, is_fact, fill = step(*placed)
            return (sk, spay, fval, found, is_fact), fill

        sk, spay, fval, found, is_fact = self._run_with_overflow_retry(
            nl + nr, run)
        return _mask_output(sk, spay, fval, found, is_fact,
                            lk.dtype, lv.dtype, rv.dtype, how)


class BroadcastJoiner(ExchangeModel):
    """Broadcast join: dimension side replicated to every device;
    ``how`` picks the variant (:data:`JOIN_HOWS`)."""

    def join(self, fact_keys, fact_vals, dim_keys, dim_vals,
             how: str = "inner"):
        """Same output contract as :meth:`HashJoiner.join`."""
        lk, lv = _as_columns(fact_keys, fact_vals)
        rk, rv = _as_columns(dim_keys, dim_vals)
        D = self.n_devices
        lk, lv, l_valid, nl = _pad_to(lk, lv, D, self.quantize_shapes)
        r_valid = jnp.ones(rk.shape[0], jnp.int32)
        step = make_broadcast_join_step(self.mesh, nl // D, rk.shape[0])
        rep = NamedSharding(self.mesh, P(None))
        sk, spay, fval, found, is_fact = step(
            jax.device_put(lk, self.sharding),
            jax.device_put(lv, self.sharding),
            jax.device_put(l_valid, self.sharding),
            jax.device_put(jnp.asarray(rk), rep),
            jax.device_put(jnp.asarray(rv), rep),
            jax.device_put(r_valid, rep),
        )
        return _mask_output(sk, spay, fval, found, is_fact,
                            lk.dtype, lv.dtype, rv.dtype, how)


def _mask_output(sk, spay, fval, found, is_fact, key_dtype, lv_dtype,
                 rv_dtype, how="inner"):
    """Host-side join filter per variant, restoring the original dtypes
    from the unsigned transport views."""
    if how not in JOIN_HOWS:
        raise ValueError(f"how must be one of {JOIN_HOWS}, got {how!r}")
    width = np.dtype(sk.dtype).itemsize
    found_h = np.asarray(found) > 0
    if how == "inner":
        mask = found_h
    elif how in ("left_outer",):
        mask = np.asarray(is_fact) > 0
    elif how == "semi":
        mask = found_h
    else:  # anti: real fact rows with no dimension match
        mask = (np.asarray(is_fact) > 0) & ~found_h
    keys = np.asarray(sk).astype(np.dtype(key_dtype))[mask]
    outl = np.asarray(_pay_from_u(spay, lv_dtype, width))[mask]
    if how in ("semi", "anti"):
        return keys, outl
    outv = np.asarray(_pay_from_u(fval, rv_dtype, width))[mask]
    if how == "left_outer":
        return keys, outl, outv, found_h[mask]
    return keys, outl, outv


def _as_columns(keys, vals):
    check_no_silent_truncation(keys=keys, vals=vals)
    k = jnp.asarray(np.asarray(keys))
    v = jnp.asarray(np.asarray(vals))
    if k.shape != v.shape or k.ndim != 1:
        raise ValueError("keys/vals must be equal-length 1-D arrays")
    return k, v


def _pad_to(k, v, d, quantize=True):
    """Pad columns to a multiple of ``d`` on the compile-shape ladder
    (models/_base.quantize_padded_length) with a validity column."""
    from sparkrdma_tpu.models._base import quantize_padded_length

    n = k.shape[0]
    total = (
        quantize_padded_length(n, d) if quantize else n + ((-n) % d)
    )
    n_pad = total - n
    valid = np.ones(n + n_pad, np.int32)
    if n_pad:
        valid[n:] = 0
        k = jnp.concatenate([k, jnp.zeros(n_pad, k.dtype)])
        v = jnp.concatenate([v, jnp.zeros(n_pad, v.dtype)])
    return k, v, jnp.asarray(valid), n + n_pad
