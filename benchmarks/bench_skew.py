#!/usr/bin/env python
"""Skewed-workload bench tier: hot-key sweep over the host shuffle
plane (ISSUE 14).

Workloads: rank-preserving bounded Zipf keys at s ∈ {1.1, 1.5} plus a
uniform control, each run with skew-adaptive splitting ON and OFF
(the OFF runs ARE the unsplit baseline, embedded in the output).
Each run is a fresh loopback cluster (driver + 2 executors), columnar
serializer, untimed map writes, then a timed sorted reduce of every
partition.  Per run the bench records wall clock, the skew registry's
commit accounting (partitions split / sub-blocks / split bytes), the
largest single block any fetch serves (markers excluded — on a split
map output that is the largest SUB-block), and the reader's merge
fan-in histogram delta.

On/off runs of the same workload must agree on record count and key
checksum — the bit-exactness line the test suite proves, re-checked
here on bench-sized data.

Emits ``BENCH_skew.json``.  Acceptance (ISSUE 14): s=1.5 split-on
wall ≥ 1.3x faster than split-off, or on a 1-core host (where serves
cannot overlap) the hot partition's fetch serialization measurably
broken up: max single-block serve ≤ skewSplitThreshold and merge
fan-in > 1, with the host note recorded.  Uniform with skew on stays
≥ 0.95x of off.

    BENCH_SMOKE=1 python benchmarks/bench_skew.py
"""

import os
import sys
import time
from collections import defaultdict

import numpy as np

sys.path.insert(0, ".")
from benchmarks.common import emit, write_bench_json, zipf_keys

SMOKE = bool(os.environ.get("BENCH_SMOKE"))

NUM_MAPS = 4
NUM_PARTS = 8
PAYLOAD = 64
N_KEYS = 1000
THRESHOLD = "128k"
THRESHOLD_BYTES = 128 << 10


def _cluster(base_port: int, skew_on: bool):
    from sparkrdma_tpu.conf import TpuShuffleConf
    from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
    from sparkrdma_tpu.transport import LoopbackNetwork

    net = LoopbackNetwork()
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.driverPort": base_port,
        "spark.shuffle.tpu.partitionLocationFetchTimeout": "120s",
        "spark.shuffle.tpu.serializer": "columnar",
        "spark.shuffle.tpu.skewEnabled": skew_on,
        "spark.shuffle.tpu.skewSplitThreshold": THRESHOLD,
        # full-size hot buckets need ~32 sub-blocks at the 128k
        # target; the default cap (16) would fold the tail into one
        # oversized final sub
        "spark.shuffle.tpu.skewMaxSubBlocks": 64,
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


def _max_serve_bytes(mto) -> int:
    """Largest single block a fetch of this map output can serve:
    every non-marker entry is served whole, so on a split output this
    is the largest SUB-block, not the hot partition's total."""
    from sparkrdma_tpu.skew import is_split_marker

    best = 0
    for r in range(mto.num_partitions):
        loc = mto.get_location(r)
        if loc.is_empty or is_split_marker(loc):
            continue
        best = max(best, loc.length)
    return best


def _run_once(base_port: int, shuffle_id: int, skew_on: bool,
              keys: np.ndarray, vals: np.ndarray):
    """One cluster, one shuffle: untimed chunked map writes, timed
    sorted reduce of all partitions.  Returns the per-run record."""
    from sparkrdma_tpu.metrics import GLOBAL_REGISTRY
    from sparkrdma_tpu.shuffle.partitioner import HashPartitioner
    from sparkrdma_tpu.skew import get_skew
    from sparkrdma_tpu.utils.columns import ColumnBatch

    fanin = GLOBAL_REGISTRY.histogram("skew_merge_fanin")
    f_count0, f_sum0 = fanin.count, fanin.sum
    net, driver, executors = _cluster(base_port, skew_on)
    maps_by_host = defaultdict(list)
    max_serve = 0
    try:
        handle = driver.register_shuffle(
            shuffle_id, NUM_MAPS, HashPartitioner(NUM_PARTS),
            key_ordering=True,
        )
        n = len(keys) // NUM_MAPS
        written = 0
        chunk = 2048  # many serializer frames per bucket => splittable
        for m in range(NUM_MAPS):
            ex = executors[m % 2]
            w = ex.get_writer(handle, m)
            mk, mv = keys[m * n:(m + 1) * n], vals[m * n:(m + 1) * n]
            for a in range(0, len(mk), chunk):
                w.write(ColumnBatch(mk[a:a + chunk], mv[a:a + chunk]))
            mto = w.stop(True)
            written += w.metrics.bytes_written
            max_serve = max(max_serve, _max_serve_bytes(mto))
            maps_by_host[ex.local_smid].append(m)
        stats = dict(get_skew().shuffle_stats(shuffle_id))
        t0 = time.perf_counter()
        records = 0
        key_sum = 0
        for pid in range(NUM_PARTS):
            reader = executors[pid % 2].get_reader(
                handle, pid, pid + 1, dict(maps_by_host)
            )
            for k, _v in reader.read():
                records += 1
                key_sum += int(k)
        wall = time.perf_counter() - t0
        driver.unregister_shuffle(shuffle_id)
        return {
            "skew_enabled": skew_on,
            "wall_s": round(wall, 4),
            "read_mb_s": round(written / wall / 1e6, 2),
            "written_bytes": written,
            "records": records,
            "key_sum": key_sum,
            "max_serve_bytes": max_serve,
            "partitions_split": stats.get("partitions_split", 0),
            "sub_blocks": stats.get("sub_blocks", 0),
            "split_bytes": stats.get("split_bytes", 0),
            "merge_fanin_count": fanin.count - f_count0,
            "merge_fanin_sum": fanin.sum - f_sum0,
        }
    finally:
        for m in executors + [driver]:
            m.stop()


def main():
    from sparkrdma_tpu.metrics import GLOBAL_REGISTRY
    from sparkrdma_tpu.skew import get_skew

    GLOBAL_REGISTRY.enabled = True
    get_skew().reset()
    n_rec = 120_000 if SMOKE else 600_000
    rng = np.random.default_rng(14)
    vals = np.frombuffer(rng.bytes(n_rec * PAYLOAD), dtype=f"S{PAYLOAD}")
    workloads = {
        "zipf_s1.1": zipf_keys(rng, 1.1, n_rec, N_KEYS),
        "zipf_s1.5": zipf_keys(rng, 1.5, n_rec, N_KEYS),
        "uniform": rng.integers(0, N_KEYS, n_rec).astype(np.int64),
    }
    port = 28600
    # untimed warmup: first-run import/serializer/connect costs must
    # not land on the first timed config (decode-sweep precedent)
    _run_once(port, 99, True, workloads["zipf_s1.5"][:20_000],
              vals[:20_000])
    port += 40
    results = {}
    sid = 100
    for name, keys in workloads.items():
        per = {}
        for skew_on in (True, False):
            rec = _run_once(port, sid, skew_on, keys, vals)
            port += 40
            sid += 1
            per["on" if skew_on else "off"] = rec
            emit(
                f"sorted reduce, {name}, split="
                f"{'on' if skew_on else 'off'}",
                rec["read_mb_s"] / 1000.0, "GB/s", 1.0,
            )
        on, off = per["on"], per["off"]
        assert on["records"] == off["records"] and \
            on["key_sum"] == off["key_sum"], \
            f"split on/off outputs diverged on {name}"
        ratio = off["wall_s"] / on["wall_s"]
        per["split_speedup"] = round(ratio, 3)
        results[name] = per
        if name.startswith("zipf"):
            emit(
                f"split-on speedup over unsplit baseline, {name}",
                ratio, "x", ratio / 1.3,  # the >=1.3x acceptance line
            )
    hot = results["zipf_s1.5"]["on"]
    serial_broken = (
        hot["partitions_split"] >= 1
        and hot["max_serve_bytes"] <= THRESHOLD_BYTES
        and hot["merge_fanin_count"] > 0
        and hot["merge_fanin_sum"] > hot["merge_fanin_count"]
    )
    emit(
        "hot-partition fetch serialization broken up at zipf s=1.5 "
        f"(max single-block serve <= {THRESHOLD}, merge fan-in > 1)",
        hot["max_serve_bytes"], "bytes", 1.0 if serial_broken else 0.0,
    )
    uniform_ratio = results["uniform"]["split_speedup"]
    emit(
        "uniform control: skew-on wall vs skew-off",
        uniform_ratio, "x", uniform_ratio / 0.95,
    )
    host_note = None
    if (os.cpu_count() or 1) == 1:
        host_note = (
            "1-core bench container: the split sub-blocks of the hot "
            "partition can only timeslice — the balanced fetch plan "
            "has no second core to overlap serves on, so the >=1.3x "
            "wall-clock line is out of reach by construction (the "
            "decodeThreads/tierPrefetch precedent).  The structural "
            "claim is checked instead: the hot partition really is "
            "served as sub-blocks no larger than skewSplitThreshold "
            "and the reader really merges fan-in > 1; wall-clock "
            "ratios recorded verbatim."
        )
    write_bench_json(
        "skew",
        extra={
            "num_maps": NUM_MAPS,
            "num_partitions": NUM_PARTS,
            "records": n_rec,
            "payload_bytes": PAYLOAD,
            "n_keys": N_KEYS,
            "split_threshold": THRESHOLD,
            "host_cores": os.cpu_count(),
            "host_note": host_note,
            "unsplit_baseline": {
                name: per["off"] for name, per in results.items()
            },
            "workloads": results,
        },
        out_dir="/tmp" if SMOKE else None,
    )


if __name__ == "__main__":
    main()
