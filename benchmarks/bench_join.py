#!/usr/bin/env python
"""BASELINE config 5: TPC-DS-style broadcast + exchange joins.

The reference's final configs are Spark SQL TPC-DS q64/q72 — star-schema
joins whose physical plans mix broadcast joins (small dimension) and
exchange shuffles (large×large).  Device-native equivalents:

- exchange join: both sides hash-partitioned + all_to_all, local
  sorted probe (models/join.py HashJoiner),
- broadcast join: dimension replicated, no exchange (BroadcastJoiner).

Reported as fact-side join throughput (rows/s and GB/s per chip).
"""

import sys

import jax
import numpy as np

sys.path.insert(0, ".")
from benchmarks.common import ROCE_LINE_RATE_GBPS, emit, time_iters

from sparkrdma_tpu.models.join import (
    make_broadcast_join_step,
    make_hash_join_step,
)
from sparkrdma_tpu.models.join import HashJoiner, BroadcastJoiner
from sparkrdma_tpu.parallel.mesh import make_mesh


def main():
    log2 = int(sys.argv[1]) if len(sys.argv) > 1 else 22
    n_fact = 1 << log2
    n_dim = 1 << max(10, log2 - 6)
    mesh = make_mesh()
    rng = np.random.default_rng(11)

    dim_keys = np.arange(n_dim, dtype=np.int32)
    dim_vals = rng.integers(0, 1 << 31, n_dim, dtype=np.int32)
    fact_keys = rng.integers(0, n_dim, n_fact, dtype=np.int32)
    fact_vals = rng.integers(0, 1 << 31, n_fact, dtype=np.int32)

    for name, joiner in (
        ("exchange hash join", HashJoiner(mesh, capacity_factor=2.0)),
        ("broadcast join", BroadcastJoiner(mesh)),
    ):
        D = joiner.n_devices
        sh = joiner.sharding
        lk = jax.device_put(fact_keys, sh)
        lv = jax.device_put(fact_vals, sh)
        l_valid = jax.device_put(np.ones(n_fact, np.int32), sh)
        if isinstance(joiner, HashJoiner):
            cap = joiner._capacity((n_fact + n_dim) // D, 2.0)
            step = make_hash_join_step(
                mesh, n_fact // D, max(1, n_dim // D), cap
            )
            rk = jax.device_put(dim_keys, sh)
            rv = jax.device_put(dim_vals, sh)
            r_valid = jax.device_put(np.ones(n_dim, np.int32), sh)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            step = make_broadcast_join_step(mesh, n_fact // D, n_dim)
            rep = NamedSharding(mesh, P(None))
            rk = jax.device_put(dim_keys, rep)
            rv = jax.device_put(dim_vals, rep)
            r_valid = jax.device_put(np.ones(n_dim, np.int32), rep)

        def run():
            out = step(lk, lv, l_valid, rk, rv, r_valid)
            return out[0], out[3]

        dt = time_iters(run, iters=10)
        gbps_chip = n_fact * 8 / dt / 1e9 / D
        emit(
            f"{name} fact-side throughput per chip ({n_fact} rows, "
            f"{D} chip(s))",
            gbps_chip, "GB/s/chip", gbps_chip / ROCE_LINE_RATE_GBPS,
        )


if __name__ == "__main__":
    main()
