#!/usr/bin/env python
"""BASELINE config 3: TeraSort (sortByKey) on the device mesh — plus
the OUT-OF-CORE tier bench (``--out-of-core``).

The reference's headline: HiBench TeraSort 175 GB over 100 GbE RoCE
(README.md:7-19).  The default mode is the same measurement as the
repo-root ``bench.py`` but parameterizable: sample → range-partition →
all_to_all → merge as ONE XLA program, reported as sorted bytes per
second per chip vs the reference's 12.5 GB/s NIC line rate.

``--out-of-core`` instead measures the tiered block store
(memory/tier.py) on a record-plane sort whose dataset exceeds the hot
budget: dataset sizes {1x, 4x, 8x} of ``tierHotBytes`` × prefetch
{on, off}, every map output committed file-backed (O_DIRECT data
files, cache-cold reads), sorted reduce over loopback.  Emits
``BENCH_out_of_core.json`` with per-config wall clock, a sampled
peak of every executor's resident hot bytes (the budget-bounding
census), peak process RSS, and the tier counter deltas embedded.

    python benchmarks/bench_terasort.py [log2_records]
    python benchmarks/bench_terasort.py --out-of-core
    BENCH_SMOKE=1 python benchmarks/bench_terasort.py --out-of-core
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, ".")
from benchmarks.common import (
    ROCE_LINE_RATE_GBPS,
    emit,
    time_iters,
    write_bench_json,
    zipf_keys,
)

SMOKE = bool(os.environ.get("BENCH_SMOKE"))

# tier counters whose per-config deltas the out-of-core sweep records
_TIER_COUNTERS = (
    "tier_hits_total", "tier_misses_total",
    "tier_promotes_total", "tier_promote_bytes_total",
    "tier_demotes_total", "tier_demote_bytes_total",
    "tier_evict_refusals_total", "tier_cold_read_bytes_total",
    "tier_prefetch_tasks_total", "tier_prefetch_useful_total",
    "tier_hint_msgs_total", "tier_hint_blocks_total",
    "tier_commit_bytes_total", "tier_bytes_never_read_total",
)


def _rss_kib() -> int:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss


def _ooc_cluster(base_port: int, hot_bytes: int, prefetch: bool):
    """Driver + 2 executors on loopback, every commit file-backed
    through the tier."""
    from sparkrdma_tpu.conf import TpuShuffleConf
    from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
    from sparkrdma_tpu.transport import LoopbackNetwork

    net = LoopbackNetwork()
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.driverPort": base_port,
        "spark.shuffle.tpu.partitionLocationFetchTimeout": "120s",
        "spark.shuffle.tpu.serializer": "columnar",
        "spark.shuffle.tpu.fileBackedCommitBytes": 1,
        "spark.shuffle.tpu.tierHotBytes": hot_bytes,
        "spark.shuffle.tpu.tierPrefetch": prefetch,
        "spark.shuffle.tpu.metrics": True,
    })
    driver = TpuShuffleManager(conf, is_driver=True, network=net)
    executors = [
        TpuShuffleManager(
            conf, is_driver=False, network=net,
            port=base_port + 20 + i * 10, executor_id=str(i),
            stage_to_device=False,
        )
        for i in range(2)
    ]
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if all(len(e._peers) == 2 for e in executors):
            break
        time.sleep(0.01)
    return net, driver, executors


def _ooc_run_once(base_port: int, hot_bytes: int, prefetch: bool,
                  keys: np.ndarray, vals: np.ndarray,
                  num_maps: int, num_parts: int):
    """One config: write the maps file-backed (untimed), then time the
    full sorted reduce of every partition while sampling each
    executor's resident hot bytes.  Returns the per-config record."""
    import threading
    from collections import defaultdict

    from sparkrdma_tpu.metrics import GLOBAL_REGISTRY
    from sparkrdma_tpu.shuffle.partitioner import HashPartitioner
    from sparkrdma_tpu.utils.columns import ColumnBatch

    c0 = {n: GLOBAL_REGISTRY.counter(n).value for n in _TIER_COUNTERS}
    net, driver, executors, = _ooc_cluster(base_port, hot_bytes, prefetch)
    maps_by_host = defaultdict(list)
    try:
        handle = driver.register_shuffle(
            1, num_maps, HashPartitioner(num_parts), key_ordering=True
        )
        n = len(keys) // num_maps
        written = 0
        for m in range(num_maps):
            ex = executors[m % 2]
            w = ex.get_writer(handle, m)
            w.write(ColumnBatch(keys[m * n:(m + 1) * n],
                                vals[m * n:(m + 1) * n]))
            w.stop(True)
            written += w.metrics.bytes_written
            maps_by_host[ex.local_smid].append(m)
        peak_hot = [0]
        stop = threading.Event()

        def sample():
            while not stop.is_set():
                peak_hot[0] = max(
                    peak_hot[0],
                    max(e.tier_store.stats()["hot_bytes"]
                        for e in executors),
                )
                time.sleep(0.002)

        sampler = threading.Thread(target=sample, daemon=True)
        sampler.start()
        t0 = time.perf_counter()
        records = 0
        key_sum = 0
        for pid in range(num_parts):
            reader = executors[pid % 2].get_reader(
                handle, pid, pid + 1, dict(maps_by_host)
            )
            for k, _v in reader.read():
                records += 1
                key_sum += int(k)
        wall = time.perf_counter() - t0
        stop.set()
        sampler.join(timeout=5)
        driver.unregister_shuffle(1)
        deltas = {
            n: GLOBAL_REGISTRY.counter(n).value - c0[n]
            for n in _TIER_COUNTERS
        }
        return {
            "prefetch": prefetch,
            "wall_s": round(wall, 4),
            "read_mb_s": round(written / wall / 1e6, 2),
            "written_bytes": written,
            "records": records,
            "key_sum": key_sum,
            "peak_hot_bytes": peak_hot[0],
            "hot_budget": hot_bytes,
            "hot_bounded": peak_hot[0] <= hot_bytes,
            "rss_kib": _rss_kib(),
            "tier": deltas,
        }
    finally:
        for m in executors + [driver]:
            m.stop()


def out_of_core_main():
    from sparkrdma_tpu.metrics import GLOBAL_REGISTRY

    GLOBAL_REGISTRY.enabled = True
    hot = (4 << 20) if SMOKE else (32 << 20)
    multiples = (1, 4) if SMOKE else (1, 4, 8)
    num_maps, num_parts = 4, 8
    payload = 128
    rec_bytes = 8 + payload
    rng = np.random.default_rng(42)
    results = {}
    port = 27800
    # untimed warmup: first-run import/serializer/connect costs must
    # not land on the first timed config (decode-sweep precedent)
    wk = rng.permutation((1 << 20) // rec_bytes).astype(np.int64)
    wv = np.frombuffer(
        rng.bytes(len(wk) * payload), dtype=f"S{payload}"
    )
    _ooc_run_once(port, hot, False, wk, wv, num_maps, num_parts)
    port += 40
    for mult in multiples:
        dataset = mult * hot
        n_rec = dataset // rec_bytes
        keys = rng.permutation(n_rec).astype(np.int64)
        vals = np.frombuffer(
            rng.bytes(n_rec * payload), dtype=f"S{payload}"
        )
        per_mult = {}
        for prefetch in (True, False):
            rec = _ooc_run_once(
                port, hot, prefetch, keys, vals, num_maps, num_parts
            )
            port += 40
            per_mult["on" if prefetch else "off"] = rec
            emit(
                f"out-of-core sorted reduce, dataset={mult}x hot "
                f"budget, prefetch={'on' if prefetch else 'off'}",
                rec["read_mb_s"] / 1000.0, "GB/s",
                rec["read_mb_s"] / 1000.0 / ROCE_LINE_RATE_GBPS,
            )
        on, off = per_mult["on"], per_mult["off"]
        assert on["records"] == off["records"] and \
            on["key_sum"] == off["key_sum"], \
            f"prefetch on/off outputs diverged at {mult}x"
        ratio = off["wall_s"] / on["wall_s"]
        per_mult["prefetch_speedup"] = round(ratio, 3)
        emit(
            f"prefetch-on speedup over prefetch-off at dataset={mult}x",
            ratio, "x", ratio / 1.25,  # the >=1.25x acceptance line
        )
        bounded = on["hot_bounded"] and off["hot_bounded"]
        emit(
            f"peak resident hot bytes within budget at {mult}x "
            f"(budget {hot}B)",
            max(on["peak_hot_bytes"], off["peak_hot_bytes"]),
            "bytes", 1.0 if bounded else 0.0,
        )
        results[f"{mult}x"] = per_mult
    host_note = None
    if (os.cpu_count() or 1) == 1:
        host_note = (
            "1-core bench container: warm work can only timeslice "
            "against the serves and decode it is meant to overlap, and "
            "this host's virtualized disk serves 'cold' reads from the "
            "hypervisor cache (mmap faults ~0.9 GB/s vs O_DIRECT "
            "~0.1 GB/s measured) — so prefetch pays its promotion copy "
            "with nothing to hide.  The >=1.25x criterion needs >=2 "
            "cores + genuinely cold storage; ratios recorded verbatim "
            "(PR 5 precedent), and conf tierPrefetch defaults OFF on "
            "single-core hosts for exactly this reason."
        )
    write_bench_json(
        "out_of_core",
        extra={
            "tier_hot_bytes": hot,
            "num_maps": num_maps,
            "num_partitions": num_parts,
            "record_bytes": rec_bytes,
            "host_cores": os.cpu_count(),
            "host_note": host_note,
            "configs": results,
        },
        out_dir="/tmp" if SMOKE else None,
    )


def main():
    import jax

    from sparkrdma_tpu.models.terasort import TeraSorter
    from sparkrdma_tpu.parallel.mesh import make_mesh

    zipf = "--zipf" in sys.argv
    argv = [a for a in sys.argv[1:] if not a.startswith("--")]
    log2 = int(argv[0]) if argv else 24
    n = 1 << log2
    mesh = make_mesh()
    sorter = TeraSorter(mesh)
    rng = np.random.default_rng(42)
    if zipf:
        # Zipfian key column (rank-preserving, s=1.5): the sampled
        # range partition has to cope with a head that dwarfs the
        # median — the device-plane face of the skew/ subsystem's
        # workload
        host_keys = zipf_keys(rng, 1.5, n, 1 << 20, dtype=np.int32)
    else:
        host_keys = rng.integers(0, 1 << 31, n, dtype=np.int32)
    keys = jax.device_put(host_keys, sorter.sharding)
    vals = jax.device_put(
        rng.integers(0, 1 << 31, n, dtype=np.int32), sorter.sharding
    )

    def run():
        (sk, sv, n_valid, _), _cap = sorter.sort_device(keys, vals)
        return sk, n_valid

    dt = time_iters(run, iters=20)
    n_chips = len(list(mesh.devices.flat))
    gbps_chip = n * 8 / dt / 1e9 / n_chips
    label = "zipf s=1.5 keys" if zipf else "uniform keys"
    emit(
        f"terasort shuffle+sort throughput per chip ({n} records, "
        f"{label}, {n_chips} chip(s))",
        gbps_chip, "GB/s/chip", gbps_chip / ROCE_LINE_RATE_GBPS,
    )


if __name__ == "__main__":
    if "--out-of-core" in sys.argv:
        out_of_core_main()
    else:
        main()
