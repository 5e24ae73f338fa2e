#!/usr/bin/env python
"""BASELINE config 4: HiBench Sort + WordCount (hash-partitioned shuffle).

Two device-plane jobs (BASELINE.md config 4):

- **Sort**: hash-partitioned shuffle followed by per-partition sort —
  measured through the TeraSorter (range partition subsumes it; the
  exchange volume is identical).
- **WordCount**: reduceByKey(+) — hash partition → all_to_all →
  segment reduction, ONE XLA program per step.

Reported as shuffled bytes per second per chip.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from benchmarks.common import (
    ROCE_LINE_RATE_GBPS,
    emit,
    time_iters,
    zipf_keys,
)

from sparkrdma_tpu.models.wordcount import WordCounter
from sparkrdma_tpu.parallel.mesh import make_mesh


def main():
    log2 = int(sys.argv[1]) if len(sys.argv) > 1 else 23
    n = 1 << log2
    mesh = make_mesh()
    wc = WordCounter(mesh)
    rng = np.random.default_rng(7)
    # Zipf word ids (rank-preserving): heavy keys exercise the
    # skew/capacity machinery with an intact distribution head
    keys = jax.device_put(
        zipf_keys(rng, 1.3, n, 100_000, dtype=np.int32), wc.sharding
    )
    vals = jax.device_put(jnp.ones(n, jnp.int32), wc.sharding)
    n_local = n // wc.n_devices
    cap = wc._capacity(n_local, factor=4.0)
    # valid=None: on one chip this engages the validity-free sort fast
    # path; on a mesh the step builds the all-ones column itself
    valid = (
        None if wc.n_devices == 1
        else jax.device_put(jnp.ones(n, jnp.int32), wc.sharding)
    )

    def run():
        (uniq, sums, counts, n_unique, fill), _ = wc.count_device(
            keys, vals, valid, capacity=cap
        )
        return uniq, n_unique

    dt = time_iters(run, iters=10)
    n_chips = wc.n_devices
    gbps_chip = n * 8 / dt / 1e9 / n_chips
    emit(
        f"wordcount reduceByKey throughput per chip ({n} records, "
        f"{n_chips} chip(s))",
        gbps_chip, "GB/s/chip", gbps_chip / ROCE_LINE_RATE_GBPS,
    )


if __name__ == "__main__":
    main()
