#!/usr/bin/env python
"""GB-scale assembled-system benchmark: the 175 GB TeraSort contract's
scaling story (reference README.md:7-19) exercised end to end.

Phase A (record plane, always runs): N GB of 64-byte records stream
through the FULL assembled pipeline — writer spill files
(``shuffleSpillRecordThreshold``) → file-backed mmap commits
(``fileBackedCommitBytes``, the RdmaMappedFile path) → publish/resolve
→ windowed fetch → key-sorted merge read — with the input GENERATED in
chunks so peak RSS stays far below the dataset (the larger-than-memory
claim is measured, not asserted).

Phase B (device plane, runs when the backend is not the CPU):
ExternalTeraSorter pushes the same
volume through device-sorted chunks + range-bucket spill files + the
bucket merge pass (models/external_sort.py).

Sizing: ``SPARKRDMA_BENCH_GB`` (default 10).  Emits one JSON line per
phase: end-to-end GB/s, with peak RSS (MB) in the metric name.
"""

import json
import os
import resource
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, ".")
from benchmarks.common import ROCE_LINE_RATE_GBPS  # noqa: E402

from sparkrdma_tpu.conf import TpuShuffleConf  # noqa: E402
from sparkrdma_tpu.shuffle.manager import TpuShuffleManager  # noqa: E402
from sparkrdma_tpu.shuffle.partitioner import RangePartitioner  # noqa: E402
from sparkrdma_tpu.transport import LoopbackNetwork  # noqa: E402
from sparkrdma_tpu.utils.columns import ColumnBatch  # noqa: E402

GB = float(os.environ.get("SPARKRDMA_BENCH_GB", "10"))
RECORD = 64  # 8B int64 key + 56B payload
N_RECORDS = int(GB * (1 << 30)) // RECORD
N_MAPS = 16
N_PARTS = 16
CHUNK = 2_000_000  # records generated/written per chunk (128 MB)
KEY_SPACE = 1 << 62


def rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def emit(metric: str, gbps: float) -> None:
    print(json.dumps({
        "metric": metric,
        "value": round(gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(gbps / ROCE_LINE_RATE_GBPS, 3),
    }), flush=True)


def phase_a_record_plane(spill_dir: str) -> None:
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.serializer": "columnar",
        # spill every ~256 MB of buffered records per map task
        "spark.shuffle.tpu.shuffleSpillRecordThreshold": str(4_000_000),
        # commits of >=64 MB go to mmapped file segments
        "spark.shuffle.tpu.fileBackedCommitBytes": "64m",
        "spark.shuffle.tpu.spillDir": spill_dir,
        # bound the staging pool so its LRU actually trims between
        # partitions (the default 10g budget would retain every fetched
        # block and inflate peak RSS ~4x)
        "spark.shuffle.tpu.maxBufferAllocationSize": "1g",
    })
    net = LoopbackNetwork()
    driver = TpuShuffleManager(
        conf, is_driver=True, network=net, stage_to_device=False,
    )
    executors = [
        TpuShuffleManager(
            conf, is_driver=False, network=net, port=47800 + i * 10,
            executor_id=str(i), stage_to_device=False,
        )
        for i in range(2)
    ]
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if all(len(e._peers) == 2 for e in executors):
            break
        time.sleep(0.01)

    # uniform keys: exact equal-frequency splitters known a priori
    splitters = [
        (p + 1) * (KEY_SPACE // N_PARTS) for p in range(N_PARTS - 1)
    ]
    # a sorted sample of exactly P-1 values becomes the splitter list
    part = RangePartitioner(N_PARTS, splitters)
    assert part.splitters == splitters

    handle = driver.register_shuffle(90, N_MAPS, part, key_ordering=True)
    per_map = N_RECORDS // N_MAPS
    # one shared random payload pool, sliced per chunk: generating
    # fresh PCG64 bytes for every record would dominate the write
    # timing (the shuffle doesn't care that payload bytes repeat)
    pool = np.frombuffer(
        np.random.default_rng(99).bytes(CHUNK * 56), dtype="V56"
    )
    t0 = time.perf_counter()
    maps_by_host = {}
    for m in range(N_MAPS):
        ex = executors[m % len(executors)]
        w = ex.get_writer(handle, m)
        rng = np.random.default_rng(1000 + m)
        left = per_map
        while left > 0:  # streamed generation: input never resident
            n = min(CHUNK, left)
            keys = rng.integers(0, KEY_SPACE, n, dtype=np.int64)
            w.write_columns(ColumnBatch(keys, pool[:n]))
            left -= n
        w.stop(True)
        maps_by_host.setdefault(ex.local_smid, []).append(m)
    t_write = time.perf_counter() - t0
    print(f"# phase A write+spill+commit: {t_write:.1f}s "
          f"(rss {rss_mb():.0f} MB)", flush=True)

    # read: fetch every partition's blocks, deserialize to columns,
    # merge the key-sorted runs (np.sort over presorted runs), verify
    total_read = 0
    total_records = 0
    t1 = time.perf_counter()
    for p in range(N_PARTS):
        ex = executors[p % len(executors)]
        reader = ex.get_reader(handle, p, p + 1, maps_by_host)
        deser = ex.serializer.deserialize_columns
        key_parts = []
        for data in reader._iter_block_bytes():
            total_read += len(data)
            for b in deser(data):
                total_records += len(b)
                if not b.key_sorted:
                    raise AssertionError("expected key-sorted blocks")
                # copy: a keys VIEW would pin the whole block buffer
                # (keys + payload) in memory until the merge
                key_parts.append(b.keys.copy())
        if key_parts:
            merged = np.sort(np.concatenate(key_parts), kind="stable")
            lo = splitters[p - 1] if p else 0
            hi = splitters[p] if p < N_PARTS - 1 else KEY_SPACE
            if len(merged) and not (
                lo <= int(merged[0]) and int(merged[-1]) < hi
            ):
                raise AssertionError(f"partition {p} range violated")
    t_read = time.perf_counter() - t1
    assert total_records == per_map * N_MAPS, (
        f"lost records: {total_records} != {per_map * N_MAPS}"
    )
    print(f"# phase A fetch+merge: {t_read:.1f}s, "
          f"{total_read / 1e9:.2f} GB fetched (rss {rss_mb():.0f} MB)",
          flush=True)
    payload = per_map * N_MAPS * RECORD
    gbps = payload / (t_write + t_read) / 1e9
    emit(
        f"assembled {GB:g}GB record-plane sortByKey "
        f"(spill + file-backed commit + fetch + merge, "
        f"peak rss {rss_mb():.0f} MB)",
        gbps,
    )
    driver.unregister_shuffle(90)
    for m in executors:
        m.unregister_shuffle(90)
    for m in executors + [driver]:
        m.stop()


def phase_b_device_plane(spill_dir: str) -> None:
    import jax

    from sparkrdma_tpu.models.external_sort import ExternalTeraSorter

    backend = jax.default_backend()
    if backend == "cpu":
        print("# phase B skipped: no accelerator", flush=True)
        return
    n = N_RECORDS  # 8B records on the device plane (int32 kv pairs)
    chunk = 8_000_000
    sorter = ExternalTeraSorter(
        num_buckets=max(64, n // chunk), spill_dir=spill_dir
    )

    def chunks():
        rng = np.random.default_rng(7)
        left = n
        while left > 0:
            c = min(chunk, left)
            yield (
                rng.integers(0, 1 << 31, c, dtype=np.int32),
                rng.integers(0, 1 << 31, c, dtype=np.int32),
            )
            left -= c

    t0 = time.perf_counter()
    out_records = 0
    last_max = None
    for sk, _sv in sorter.sort_chunks(chunks()):
        out_records += len(sk)
        if len(sk):
            if last_max is not None and int(sk[0]) < last_max:
                raise AssertionError("bucket order violated")
            last_max = int(sk[-1])
    dt = time.perf_counter() - t0
    assert out_records == n, f"lost records: {out_records} != {n}"
    gbps = n * 8 / dt / 1e9
    emit(
        f"external device TeraSort {n * 8 / 1e9:.1f}GB "
        f"({backend} backend, chunked spill + bucket merge, "
        f"peak rss {rss_mb():.0f} MB)",
        gbps,
    )


def main():
    with tempfile.TemporaryDirectory(prefix="sparkrdma_10gb_") as d:
        phase_a_record_plane(d)
        phase_b_device_plane(d)


if __name__ == "__main__":
    main()
