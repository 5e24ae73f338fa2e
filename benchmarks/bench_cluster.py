#!/usr/bin/env python
"""Cluster bench tier (ISSUE 17): terasort/wordcount end-to-end across
REAL executor processes (transport/simfleet.ProcessCluster), plus the
native hot-path kernel microbench.

Per process count (2..8, ``clusters`` section of the output, keyed by
count so tools/bench_gate.py gates each tier independently):

- terasort and wordcount wall clock + rows/s through the full
  write → publish → fetch → read cycle over real TCP sockets,
- bit-exactness: every partition digest must equal the single-process
  loopback reference run of the SAME generated workload,
- per-process census (CPU seconds, fds, threads) summed fleet-wide,
- fetch/decode wait split from the children's metrics registries and
  the derived read-overlap ratio (1 - wait/wall, clamped at 0),
- control-plane RPC counts (transport msgs sent/received).

Flat results carry the native-kernel microbench: frame-walk, CRC
batch, and block gather, each native vs its pure-Python fallback loop
on small-frame workloads where per-call interpreter overhead dominates
— the ISSUE 17 acceptance line is >=2x on this 1-core host.

On a 1-core host the multi-process tiers can only timeslice, so the
rows/s lines are STRUCTURAL (bit-exact results, census, RPC counts),
not a parallel speedup claim — the host note records this (the PR 14
precedent).

    BENCH_SMOKE=1 python benchmarks/bench_cluster.py
"""

import os
import sys
import time
import zlib

sys.path.insert(0, ".")
from benchmarks.common import emit, write_bench_json

SMOKE = bool(os.environ.get("BENCH_SMOKE"))

PROC_COUNTS = [2] if SMOKE else [2, 4]
NUM_PARTS = 4 if SMOKE else 8
RECORDS_PER_MAP = 1500 if SMOKE else 20_000
BASE_PORT = 25200

WORKLOADS = {
    "terasort": {"kind": "terasort", "records": RECORDS_PER_MAP,
                 "value_len": 64},
    "wordcount": {"kind": "wordcount", "records": RECORDS_PER_MAP,
                  "vocab": 997},
}


def _conf_map(extra=None):
    m = {
        "spark.shuffle.tpu.partitionLocationFetchTimeout": "60s",
        "spark.shuffle.tpu.connectTimeout": "15s",
        "spark.shuffle.tpu.metrics": True,
    }
    m.update(extra or {})
    return m


def single_process_reference(gen, num_maps, base_port):
    """The same generated workload through ONE process over loopback:
    the bit-exactness reference and the no-parallelism baseline."""
    from sparkrdma_tpu.conf import TpuShuffleConf
    from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
    from sparkrdma_tpu.shuffle.partitioner import HashPartitioner
    from sparkrdma_tpu.transport import LoopbackNetwork
    from sparkrdma_tpu.transport.simfleet import _gen_records, records_digest

    net = LoopbackNetwork()
    conf = TpuShuffleConf(_conf_map({
        "spark.shuffle.tpu.driverPort": base_port,
    }))
    driver = TpuShuffleManager(conf, is_driver=True, network=net,
                               stage_to_device=False)
    ex = TpuShuffleManager(conf, is_driver=False, network=net,
                           port=base_port + 50, executor_id="0",
                           stage_to_device=False)
    handle = ex.register_shuffle(1, num_maps, HashPartitioner(NUM_PARTS))
    t0 = time.perf_counter()
    for map_id in range(num_maps):
        w = ex.get_writer(handle, map_id)
        w.write(_gen_records(gen, map_id))
        w.stop(True)
    mbh = {ex.local_smid: list(range(num_maps))}
    digests, total = [], 0
    for p in range(NUM_PARTS):
        records = list(ex.get_reader(handle, p, p + 1, mbh).read())
        total += len(records)
        digests.append(records_digest(records))
    wall = time.perf_counter() - t0
    ex.stop()
    driver.stop()
    return digests, total, wall


def _counter_sum(snapshot, name):
    return sum(c["value"] for c in snapshot.get("counters", [])
               if c["name"] == name)


def cluster_run(n_procs, gen, base_port):
    """One workload through an n-process fleet; returns timing +
    digests + fleet census/metrics."""
    from sparkrdma_tpu.transport.simfleet import ProcessCluster

    num_maps = n_procs
    with ProcessCluster(n_procs, base_port, conf=_conf_map()) as c:
        c.register(1, num_maps=num_maps, partitioner=("hash", NUM_PARTS))
        t0 = time.perf_counter()
        # fan the map tasks out, THEN collect — per-pipe FIFO keeps
        # reply order deterministic while the fleet works in parallel
        for map_id in range(num_maps):
            c.executors[map_id % n_procs].send(
                "write", shuffle_id=1, map_id=map_id, gen=gen)
        for map_id in range(num_maps):
            c.executors[map_id % n_procs].recv(300.0)
        c.wait_published(1, num_maps)
        write_wall = time.perf_counter() - t0

        t1 = time.perf_counter()
        mbh = c.driver.maps_by_host(1)
        for p in range(NUM_PARTS):
            c.executors[p % n_procs].send(
                "read", shuffle_id=1, start=p, end=p + 1,
                maps_by_host=mbh, digest=True)
        digests, total = [], 0
        for p in range(NUM_PARTS):
            out = c.executors[p % n_procs].recv(300.0)
            digests.append(out["digest"])
            total += out["records"]
        read_wall = time.perf_counter() - t1

        census = c.census()
        fleet = {"cpu_user_s": 0.0, "cpu_sys_s": 0.0, "fds": 0,
                 "threads": 0, "fetch_wait_ms": 0, "decode_wait_ms": 0,
                 "msgs_sent": 0, "msgs_received": 0}
        for info in census["executors"].values():
            cen, snap = info["census"], info["metrics"]
            fleet["cpu_user_s"] += cen["cpu_user_s"]
            fleet["cpu_sys_s"] += cen["cpu_sys_s"]
            fleet["fds"] += cen["fds"]
            fleet["threads"] += cen["threads"]
            fleet["fetch_wait_ms"] += _counter_sum(
                snap, "shuffle_fetch_wait_ms_total")
            fleet["decode_wait_ms"] += _counter_sum(
                snap, "shuffle_decode_wait_ms_total")
            fleet["msgs_sent"] += _counter_sum(
                snap, "transport_msgs_sent_total")
            fleet["msgs_received"] += _counter_sum(
                snap, "transport_msgs_received_total")
        c.stop()
        collected = c.collect()
        return {
            "write_wall_s": write_wall,
            "read_wall_s": read_wall,
            "digests": digests,
            "records": total,
            "num_maps": num_maps,
            "fleet": fleet,
            "census_procs": 1 + len(census["executors"]),
            "obs_dumps": len(collected["dump_paths"]),
        }


# -- native hot-path kernel microbench --------------------------------------

def _time_best(fn, reps=9):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def kernel_microbench():
    """Native frame-walk / CRC-batch / gather vs their pure-Python
    fallback loops, on many-small-frame workloads where per-call
    interpreter overhead dominates (the per-process hot path)."""
    import numpy as np

    from sparkrdma_tpu.memory import staging
    from sparkrdma_tpu.utils.serde import PickleSerializer

    n_frames = 4000 if SMOKE else 8000
    body = b"x" * 72
    buf = bytearray()
    spans = []
    for _ in range(n_frames):
        start = len(buf)  # spans cover the 4B length prefix + body
        buf += len(body).to_bytes(4, "little") + body
        spans.append((start, len(buf)))
    buf = bytes(buf)
    ser = PickleSerializer()
    view = memoryview(buf)

    out = {}

    # frame walk: serde's native-first path vs its Python loop (the
    # fallback is forced by patching the staging hook, so both sides
    # run the REAL production code)
    native_walk = _time_best(lambda: ser.frame_spans(view))
    hook = staging.native_frame_spans
    staging.native_frame_spans = lambda *a, **k: None
    try:
        py_spans = ser.frame_spans(view)
        py_walk = _time_best(lambda: ser.frame_spans(view))
    finally:
        staging.native_frame_spans = hook
    assert ser.frame_spans(view) == py_spans == spans
    out["frame_walk"] = (py_walk, native_walk)

    # CRC batch: one native crc32_spans call vs the per-span zlib loop
    # (span table as an int64 array, the records_digest idiom — a
    # tuple-list would spend the win on list→ndarray conversion)
    spans_arr = np.asarray(spans, np.int64)

    def _py_crc():
        return [zlib.crc32(view[a:b]) for a, b in spans]

    native_crc = staging.native_crc32_spans(buf, spans_arr)
    if native_crc is not None:
        assert list(native_crc) == _py_crc()
        t_native_crc = _time_best(
            lambda: staging.native_crc32_spans(buf, spans_arr))
        out["crc_batch"] = (_time_best(_py_crc), t_native_crc)

    # gather: one native batched-memcpy call vs the numpy
    # slice-assignment loop bulk._assemble falls back to
    n_blocks = len(spans)
    srcs = [np.frombuffer(buf, np.uint8, b - a, a) for a, b in spans]
    lens = [len(s) for s in srcs]
    offs = [0] * n_blocks
    acc = 0
    for i, n in enumerate(lens):
        offs[i] = acc
        acc += n
    dst = np.empty(acc, np.uint8)
    addrs = [int(s.ctypes.data) for s in srcs]

    def _py_gather():
        for s, off, n in zip(srcs, offs, lens):
            dst[off:off + n] = s

    _py_gather()
    expect = dst.copy()
    if staging.native_gather_blocks(dst, addrs, lens, offs):
        dst[:] = 0
        assert staging.native_gather_blocks(dst, addrs, lens, offs)
        assert np.array_equal(dst, expect)
        out["gather"] = (
            _time_best(_py_gather),
            _time_best(
                lambda: staging.native_gather_blocks(dst, addrs, lens, offs)
            ),
        )
    return n_frames, out


def main():
    port = BASE_PORT
    clusters = {}
    bit_exact = True
    reference = {}
    for name, gen in WORKLOADS.items():
        for n_procs in PROC_COUNTS:
            ref_key = (name, n_procs)
            # reference maps == cluster maps so the workloads match
            reference[ref_key] = single_process_reference(
                gen, n_procs, port)
            port += 100
    for n_procs in PROC_COUNTS:
        # multi-process numbers are only meaningful with real cores
        # under them: tools/bench_gate.py skips the tier (with a note)
        # on hosts below min_cores instead of gating timeslice noise
        tier = {
            "results": [], "workloads": {},
            "min_cores": 2 if n_procs >= 2 else 0,
        }
        for name, gen in WORKLOADS.items():
            run = cluster_run(n_procs, gen, port)
            port += 1000
            ref_digests, ref_total, ref_wall = reference[(name, n_procs)]
            exact = (run["digests"] == ref_digests
                     and run["records"] == ref_total)
            bit_exact = bit_exact and exact
            rows = run["num_maps"] * gen["records"]
            wall = run["write_wall_s"] + run["read_wall_s"]
            fleet = run["fleet"]
            wait_ms = fleet["fetch_wait_ms"] + fleet["decode_wait_ms"]
            overlap = max(0.0, 1.0 - wait_ms / 1000.0 / run["read_wall_s"]) \
                if run["read_wall_s"] > 0 else 0.0
            tier["workloads"][name] = {
                "bit_exact": exact,
                "records": run["records"],
                "single_process_wall_s": round(ref_wall, 4),
                "fleet": fleet,
                "census_procs": run["census_procs"],
                "obs_dumps": run["obs_dumps"],
            }
            for rec in (
                (f"{name} end-to-end", rows / wall, "rows/s", 1.0),
                (f"{name} bit-exact vs single-process",
                 1.0 if exact else 0.0, "bool", 1.0),
                (f"{name} fleet cpu (user+sys)",
                 fleet["cpu_user_s"] + fleet["cpu_sys_s"], "cpu-s", 1.0),
                (f"{name} fetch wait", fleet["fetch_wait_ms"],
                 "ms.cum", 1.0),
                (f"{name} decode wait", fleet["decode_wait_ms"],
                 "ms.cum", 1.0),
                (f"{name} read overlap ratio", overlap, "ratio", 1.0),
                (f"{name} transport msgs", fleet["msgs_sent"],
                 "msgs.cum", 1.0),
            ):
                metric, value, unit, vs = rec
                emit(f"[{n_procs}proc] {metric}", value, unit, vs)
                tier["results"].append({
                    "metric": metric, "value": round(float(value), 3),
                    "unit": unit, "vs_baseline": vs,
                })
        clusters[str(n_procs)] = tier

    n_frames, kernels = kernel_microbench()
    kernel_speedups = {}
    for kname, (py_s, native_s) in kernels.items():
        speedup = py_s / native_s if native_s > 0 else 0.0
        kernel_speedups[kname] = round(speedup, 2)
        emit(f"native {kname} ({n_frames} frames) vs python loop",
             speedup, "x", speedup / 2.0)  # the >=2x acceptance line
        emit(f"native {kname} per-frame", native_s / n_frames * 1e6,
             "us", 1.0)

    ncpu = os.cpu_count() or 1
    host_note = None
    if ncpu == 1:
        host_note = (
            "1-core bench container: executor processes timeslice one "
            "core, so the multi-process tiers cannot show a parallel "
            "speedup here by construction — the rows/s lines are "
            "structural acceptance (bit-exact digests vs the "
            "single-process loopback reference, full process census, "
            "RPC counts, obs dumps from every process), the PR 14 "
            "precedent.  The native-kernel speedups ARE 1-core-"
            "measurable (pure interpreter-overhead elimination) and "
            "carry the >=2x acceptance."
        )
    assert bit_exact, "cluster digests diverged from single-process run"
    write_bench_json(
        "cluster",
        extra={
            "proc_counts": PROC_COUNTS,
            "num_partitions": NUM_PARTS,
            "records_per_map": RECORDS_PER_MAP,
            "host_cores": ncpu,
            "host_note": host_note,
            "bit_exact": bit_exact,
            "kernel_speedups": kernel_speedups,
            "clusters": clusters,
        },
        out_dir="/tmp" if SMOKE else None,
    )


if __name__ == "__main__":
    main()
