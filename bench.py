#!/usr/bin/env python
"""Benchmark: distributed-sort (TeraSort-style) shuffle throughput per chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The reference's headline result is HiBench TeraSort over 100 GbE RoCE
(README.md:7-19): its shuffle data plane is bounded by the NIC line rate
of 12.5 GB/s per node.  Here the same sortByKey pipeline (sample →
range-partition → all_to_all → local sort) runs as one XLA program with
the exchange riding ICI/HBM, so the comparable per-chip figure is
end-to-end sorted bytes per second; vs_baseline divides by the
reference's 12.5 GB/s per-node line rate ceiling.

Runs on the visible TPU devices and fails without one: a CPU run
prints no per-chip number.  Any failure exits non-zero.
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from sparkrdma_tpu.models.terasort import TeraSorter
from sparkrdma_tpu.parallel.mesh import make_mesh
from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

# 100 GbE RoCE line rate, the reference data plane's per-node ceiling (GB/s)
BASELINE_GBPS = 12.5

N_RECORDS = 1 << 24  # 16.7M records x 8B (int32 key + int32 val) = 134 MB
WARMUP = 2
ITERS = 20


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py: no TPU found (platform {dev.platform!r})")
    enable_compile_cache()
    mesh = make_mesh()
    n_chips = len(list(mesh.devices.flat))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_chips}
    sorter = TeraSorter(mesh)
    rng = np.random.default_rng(42)
    keys = jax.device_put(
        rng.integers(0, 1 << 31, size=N_RECORDS, dtype=np.int32),
        sorter.sharding,
    )
    vals = jax.device_put(
        rng.integers(0, 1 << 31, size=N_RECORDS, dtype=np.int32),
        sorter.sharding,
    )

    def run_once():
        (sk, sv, n_valid, _), _cap = sorter.sort_device(keys, vals)
        return sk, n_valid

    for _ in range(WARMUP):
        sk, n_valid = run_once()
    jax.block_until_ready(n_valid)
    assert int(jnp.sum(n_valid)) == N_RECORDS, "records lost in exchange"

    # dispatch all iterations asynchronously and wait once
    t0 = time.perf_counter()
    for _ in range(ITERS):
        _, n_valid = run_once()
    jax.block_until_ready(n_valid)
    dt = (time.perf_counter() - t0) / ITERS
    per_chip = N_RECORDS * 8 / dt / 1e9 / n_chips
    print(
        f"# terasort 8B-record shape ({N_RECORDS} records, lax.sort): "
        f"{per_chip:.3f} GB/s/chip "
        f"(vs_baseline {per_chip / BASELINE_GBPS:.3f})",
        flush=True,
    )

    # headline metric: the HiBench record shape the reference's 175 GB
    # result is measured on (10B key + 90B value ≈ 100B records,
    # /root/reference/README.md:7-19) — the sort cost is per RECORD, so
    # wide values are the honest sorted-bytes/s comparison against the
    # NIC line rate
    wide_chip = _bench_wide(mesh)
    print(json.dumps({
        "metric": "terasort shuffle+sort throughput per chip, HiBench "
                  f"100B records ({N_WIDE} records, {n_chips} chip(s), "
                  "key sort + payload gather)",
        "value": round(wide_chip, 3),
        "unit": "GB/s/chip",
        "vs_baseline": round(wide_chip / BASELINE_GBPS, 3),
        "device": device,
    }), flush=True)


N_WIDE = 1 << 22       # 4.2M records
WIDE_WORDS = 24        # 96B payload + 4B key = 100B (HiBench ~100B)


def _bench_wide(mesh):
    """Time the wide-record sort (models/terasort.py wide path);
    returns GB/s per chip.  Retries once with a higher capacity factor
    on bucket overflow."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkrdma_tpu.parallel.mesh import EXCHANGE_AXIS

    rng = np.random.default_rng(7)
    # placed once, shard by shard (never whole on device 0)
    keys = jax.device_put(
        rng.integers(0, 1 << 31, N_WIDE, dtype=np.int32),
        NamedSharding(mesh, P(EXCHANGE_AXIS)),
    )
    payload = jax.device_put(
        rng.integers(0, 1 << 31, (N_WIDE, WIDE_WORDS), dtype=np.int32),
        NamedSharding(mesh, P(EXCHANGE_AXIS, None)),
    )
    n_chips = len(list(mesh.devices.flat))
    for factor in (1.3, 2.0):
        sorter = TeraSorter(mesh, capacity_factor=factor)
        (sk, sp, n_valid, max_fill), cap = sorter.sort_device_wide(
            keys, payload
        )
        jax.block_until_ready(n_valid)
        if int(np.max(np.asarray(jax.device_get(max_fill)))) > cap:
            continue  # overflow: retry with more headroom
        assert int(np.asarray(jax.device_get(n_valid)).sum()) == N_WIDE
        t0 = time.perf_counter()
        for _ in range(ITERS):
            (sk, sp, n_valid, _mf), _ = sorter.sort_device_wide(
                keys, payload
            )
        jax.block_until_ready(n_valid)
        dt = (time.perf_counter() - t0) / ITERS
        record_bytes = 4 + 4 * WIDE_WORDS
        return N_WIDE * record_bytes / dt / 1e9 / n_chips
    raise AssertionError("wide sort overflowed even at factor 2.0")


if __name__ == "__main__":
    main()
