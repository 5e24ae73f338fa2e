"""Device-native keyed aggregation: the combineByKey workload.

Generalizes WordCount's reduceByKey(+) to the full aggregation family —
sum, count, min, max, mean per key — as one SPMD program: hash exchange
(ops/exchange.py) followed by the one-pass segment aggregation
(ops/segment.py aggregate_by_key_local).  The device analog of Spark's
Aggregator running during the read path
(RdmaShuffleReader.scala:82-97); the record-plane equivalent lives in
shuffle/reader.py (arbitrary Python combiners), this one trades
generality for MXU/VPU-rate throughput on numeric columns.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from sparkrdma_tpu.models._base import ExchangeModel
from sparkrdma_tpu.ops.exchange import hash_exchange
from sparkrdma_tpu.ops.segment import aggregate_by_key_local
from sparkrdma_tpu.parallel.mesh import EXCHANGE_AXIS


class KeyStats(NamedTuple):
    """Per-key aggregates (mean derived host-side: sum / count)."""

    sum: int
    count: int
    min: int
    max: int

    @property
    def mean(self) -> float:
        return self.sum / self.count


@functools.lru_cache(maxsize=16)
def make_aggregate_step(mesh: Mesh, n_local: int, capacity: int,
                        with_validity: bool = True):
    """Jitted aggregateByKey step over global [D*n_local] columns
    sharded on the mesh axis.  ``with_validity=False`` is the D == 1
    unpadded fast path (segment.py: drops the validity sort operand)."""
    D = len(list(mesh.devices.flat))
    spec = P(EXCHANGE_AXIS)

    if not with_validity:
        if D != 1:
            raise ValueError(
                "with_validity=False requires D == 1 (bucket fills on "
                "a real exchange need the validity column)"
            )

        def body_nv(k, v):  # local [n_local], all slots real
            uniq, sums, counts, mins, maxs, n_unique = (
                aggregate_by_key_local(k, v, None)
            )
            return (uniq, sums, counts, mins, maxs, n_unique[None],
                    jnp.zeros(1, jnp.int32))

        mapped = jax.shard_map(
            body_nv, mesh=mesh, in_specs=(spec, spec),
            out_specs=(spec,) * 7,
        )
        return jax.jit(mapped)

    def body(k, v, valid):  # local [n_local]
        # (hash_exchange is the identity for D == 1 — no padded sorts)
        flat_k, flat_v, flat_m, max_fill = hash_exchange(
            k, v, valid, D, capacity
        )
        sentinel = jnp.array(jnp.iinfo(k.dtype).max, k.dtype)
        flat_k = jnp.where(flat_m > 0, flat_k, sentinel)
        flat_v = jnp.where(flat_m > 0, flat_v, jnp.zeros((), v.dtype))
        uniq, sums, counts, mins, maxs, n_unique = aggregate_by_key_local(
            flat_k, flat_v, flat_m
        )
        return uniq, sums, counts, mins, maxs, n_unique[None], max_fill[None]

    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 7
    )
    return jax.jit(mapped)


class KeyedAggregator(ExchangeModel):
    """Host-facing aggregateByKey: returns {key: KeyStats}."""

    def __init__(self, mesh: Optional[Mesh] = None, capacity_factor: float = 2.0):
        super().__init__(mesh, capacity_factor)

    def aggregate(self, keys, vals) -> Dict[int, KeyStats]:
        """Sums accumulate in the value dtype and wrap on overflow (JVM
        Int/Long parity).  For wide sums pass int64 values with
        ``jax_enable_x64`` on; without it int64 inputs would silently
        truncate, so that combination is rejected."""
        # int64-without-x64 inputs are rejected inside _run_padded_keyed
        # (shared with every keyed model)
        rows, nu = self._run_padded_keyed(keys, vals, make_aggregate_step)
        if rows is None:
            return {}
        uniq_h, sums_h, counts_h, mins_h, maxs_h = rows
        out: Dict[int, KeyStats] = {}
        for d in range(self.n_devices):
            # results live at run-end positions: extract by counts > 0
            (idx,) = (counts_h[d] > 0).nonzero()
            for i in idx:
                out[int(uniq_h[d][i])] = KeyStats(
                    int(sums_h[d][i]), int(counts_h[d][i]),
                    int(mins_h[d][i]), int(maxs_h[d][i]),
                )
        return out
