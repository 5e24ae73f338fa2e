#!/usr/bin/env python
"""BASELINE config 2: 2-executor reduceByKey over the loopback transport,
plus the striped-fetch sweep.

The reference's second measurement config is a 2-executor
RdmaShuffleManager run with the bypass serializer (BASELINE.md).  Here:
two executor managers + a driver on the loopback network, reduceByKey
with map-side combine, raw-bytes-free int payloads.  Reported as
records/s through the full control+data plane.

The striped-fetch sweep (``BENCH_striped_fetch.json``) measures the
remote block-fetch data path over REAL sockets: stripes ∈ {1, 2, 4} ×
payload sizes, all against the single-channel pre-striping wire path
(``transportScatterGather=off``, one data lane — concat+sendall serve,
whole-frame receive) as baseline, plus RPC echo latency while bulk
reads saturate the data lanes (the head-of-line-blocking check).
"""

import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, ".")
from benchmarks.common import RESULTS, emit

from sparkrdma_tpu.api import TpuShuffleContext

# BENCH_SMOKE=1: tiny tier-2 sanity config (make bench-smoke) — same
# code paths, minutes → seconds, JSON written to /tmp instead of the
# committed BENCH_*.json results
SMOKE = bool(os.environ.get("BENCH_SMOKE"))

# SPARKRDMA_TPU_BENCH_TRACE=1: run with the tracer and flight recorder
# held open and a fresh root span on every fetch — the trace-ON leg of
# the observability overhead A/B.  Traced numbers are a measurement of
# the tracer, not of the transport, so they land in /tmp and never
# overwrite the committed BENCH_*.json results.
TRACE = bool(os.environ.get("SPARKRDMA_TPU_BENCH_TRACE"))
SMOKE_DIR = "/tmp" if (SMOKE or TRACE) else None

N_RECORDS = 30_000 if SMOKE else 300_000
N_KEYS = 1024

BASE_PORT = 46300
STORE_BYTES = (4 << 20) if SMOKE else (32 << 20)
SWEEP_STRIPES = (1, 2) if SMOKE else (1, 2, 4)
SWEEP_SIZES = ((1 << 20,) if SMOKE
               else (1 << 20, 8 << 20, 32 << 20))
TARGET_MOVE = (8 << 20) if SMOKE else (192 << 20)
RPC_SAMPLES = 40 if SMOKE else 400

# fabric-scale sweep (BENCH_fabric_scale.json)
FABRIC_PEERS = (8, 32) if SMOKE else (8, 64, 256)
FABRIC_BLOCK = 256 << 10
FABRIC_CAP = 16

# decode-pipeline sweep (BENCH_decode_pipeline.json)
DECODE_THREADS = (0, 1, 2, 4)
DECODE_RECORDS = 20_000 if SMOKE else 1_500_000
DECODE_PAYLOAD = 40  # bytes per value (the classic 10-90B shuffle val)
DECODE_PARTS = 4
DECODE_REPS = 1 if SMOKE else 3


def _fetch_config(name, port, stripes, scatter_gather, extra=None):
    """One measurement config: nodes+network over real sockets, a
    registered 32 MiB store, and the per-peer read group."""
    from sparkrdma_tpu.conf import TpuShuffleConf
    from sparkrdma_tpu.memory.arena import ArenaManager
    from sparkrdma_tpu.transport import TcpNetwork
    from sparkrdma_tpu.transport.node import Node

    conf_map = {
        "spark.shuffle.tpu.transportNumStripes": stripes,
        "spark.shuffle.tpu.transportStripeThreshold": "256k",
        "spark.shuffle.tpu.transportScatterGather": scatter_gather,
    }
    conf_map.update(extra or {})
    conf = TpuShuffleConf(conf_map)
    net = TcpNetwork()
    a = Node(("127.0.0.1", port), conf)
    b = Node(("127.0.0.1", port + 5), conf)
    net.register(a)
    net.register(b)
    arena = ArenaManager()
    data = (np.arange(STORE_BYTES, dtype=np.uint32) % 251).astype(np.uint8)
    seg = arena.register(data, zero_copy_ok=True)
    b.register_block_store(seg.mkey, arena)
    group = a.get_read_group(b.address, net.connect)
    return {
        "name": name, "net": net, "a": a, "b": b, "mkey": seg.mkey,
        "group": group, "arena": arena,
    }


def _teardown_config(cfg):
    cfg["a"].stop()
    cfg["b"].stop()
    cfg["net"].unregister(cfg["a"])
    cfg["net"].unregister(cfg["b"])


def _trace_ctx():
    """Fresh per-fetch root span (None when the A/B runs trace-off)."""
    if not TRACE:
        return None
    from sparkrdma_tpu.obs import TRACING

    return TRACING.start()


def _read_once(cfg, size, timeout=120):
    from sparkrdma_tpu.transport.channel import FnCompletionListener
    from sparkrdma_tpu.utils.types import BlockLocation

    done = threading.Event()
    err = []
    cfg["group"].read_blocks(
        [BlockLocation(0, size, cfg["mkey"])],
        FnCompletionListener(
            lambda blocks: done.set(),
            lambda e: (err.append(e), done.set()),
        ),
        ctx=_trace_ctx(),
    )
    if not done.wait(timeout):
        raise RuntimeError("fetch hung")
    if err:
        raise err[0]


def _fetch_throughput(cfg, size):
    """GB/s of sequential whole-block fetches totalling TARGET_MOVE."""
    iters = max(2, TARGET_MOVE // size)
    _read_once(cfg, size)  # warmup (connects the lanes)
    t0 = time.perf_counter()
    for _ in range(iters):
        _read_once(cfg, size)
    dt = time.perf_counter() - t0
    return iters * size / dt / 1e9


def _fetch_throughput_windowed(cfg, size, window=4):
    """GB/s of WINDOWED whole-block fetches (``window`` reads in
    flight, the reader's maxBytesInFlight pipelining shape) totalling
    TARGET_MOVE — the workload the completion-driven transport core
    exists for; sequential one-at-a-time reads are latency-bound and
    measure per-read fixed hops instead."""
    from sparkrdma_tpu.transport.channel import FnCompletionListener
    from sparkrdma_tpu.utils.types import BlockLocation

    iters = max(window, TARGET_MOVE // size)
    sem = threading.BoundedSemaphore(window)
    done = threading.Event()
    left = [iters]
    err = []
    lk = threading.Lock()

    def settle(e=None):
        if e is not None:
            err.append(e)
        sem.release()
        with lk:
            left[0] -= 1
            if left[0] == 0:
                done.set()

    _read_once(cfg, size)  # warmup (connects the lanes)
    t0 = time.perf_counter()
    for _ in range(iters):
        sem.acquire()
        cfg["group"].read_blocks(
            [BlockLocation(0, size, cfg["mkey"])],
            FnCompletionListener(
                lambda blocks: settle(), lambda e: settle(e)
            ),
            ctx=_trace_ctx(),
        )
    if not done.wait(180):
        raise RuntimeError("windowed fetch hung")
    if err:
        raise err[0]
    return iters * size / (time.perf_counter() - t0) / 1e9


def _rpc_latency_under_bulk(cfg, bulk_size=None):
    """Median RPC echo RTT (ms) while a background loop keeps bulk
    striped reads saturating the data lanes."""
    if bulk_size is None:
        bulk_size = min(8 << 20, STORE_BYTES // 4)
    from sparkrdma_tpu.transport.channel import (
        ChannelType,
        FnCompletionListener,
    )

    a, b, net = cfg["a"], cfg["b"], cfg["net"]
    pong = {"event": threading.Event()}

    def echo(channel, frame):
        channel.reply_channel().send_rpc([frame], FnCompletionListener())

    def on_pong(_channel, _frame):
        pong["event"].set()

    b.set_receive_listener(echo)
    a.set_receive_listener(on_pong)
    rpc_ch = a.get_channel(b.address, ChannelType.RPC_REQUESTOR, net.connect)
    stop = threading.Event()
    bulk_reads = [0]

    def bulk_loop():
        while not stop.is_set():
            _read_once(cfg, bulk_size)
            bulk_reads[0] += 1

    t = threading.Thread(target=bulk_loop, daemon=True)
    t.start()
    time.sleep(0.05)  # bulk in flight before sampling
    lat = []
    for _ in range(RPC_SAMPLES):
        pong["event"].clear()
        t0 = time.perf_counter()
        rpc_ch.send_rpc([b"ping"], FnCompletionListener())
        if not pong["event"].wait(10):
            raise RuntimeError("rpc echo hung under bulk load")
        lat.append((time.perf_counter() - t0) * 1000)
    stop.set()
    t.join(timeout=30)
    if bulk_reads[0] == 0:
        # an unloaded link would fake the head-of-line-blocking number
        raise RuntimeError("bulk loop made no reads during RPC sampling")
    lat.sort()
    return lat[len(lat) // 2]


def striped_fetch_sweep():
    """stripes × payload-size sweep vs the single-channel baseline;
    writes BENCH_striped_fetch.json with the metrics snapshot."""
    from sparkrdma_tpu.metrics import GLOBAL_REGISTRY

    GLOBAL_REGISTRY.enabled = True
    port = BASE_PORT
    baseline = {}
    cfg = _fetch_config("single-channel baseline", port, 1, "off")
    try:
        for size in SWEEP_SIZES:
            baseline[size] = _fetch_throughput(cfg, size)
            emit(
                f"remote fetch {size >> 20}MiB single-channel baseline "
                f"(stripes=1, scatter-gather off)",
                baseline[size], "GB/s", 1.0,
            )
        base_rpc = _rpc_latency_under_bulk(cfg)
        emit(
            "RPC echo p50 under concurrent bulk reads "
            "(single-channel baseline)",
            base_rpc, "ms", 1.0,
        )
    finally:
        _teardown_config(cfg)

    best = {"ratio": 0.0, "stripes": 1, "size": 0, "gbps": 0.0}
    rpc_striped = None
    for stripes in SWEEP_STRIPES:
        port += 20
        cfg = _fetch_config(f"stripes={stripes}", port, stripes, "on")
        try:
            for size in SWEEP_SIZES:
                gbps = _fetch_throughput(cfg, size)
                ratio = gbps / baseline[size]
                emit(
                    f"remote fetch {size >> 20}MiB stripes={stripes} "
                    f"scatter-gather",
                    gbps, "GB/s", ratio,
                )
                if ratio > best["ratio"]:
                    best.update(ratio=ratio, stripes=stripes,
                                size=size, gbps=gbps)
            if stripes == max(SWEEP_STRIPES):
                rpc_striped = _rpc_latency_under_bulk(cfg)
                emit(
                    f"RPC echo p50 under concurrent bulk reads "
                    f"(stripes={stripes})",
                    rpc_striped, "ms",
                    base_rpc / rpc_striped if rpc_striped else 1.0,
                )
        finally:
            _teardown_config(cfg)

    emit(
        f"best striped fetch vs single-channel baseline "
        f"(stripes={best['stripes']}, {best['size'] >> 20}MiB)",
        best["gbps"], "GB/s", best["ratio"],
    )
    from benchmarks.common import write_bench_json

    write_bench_json("striped_fetch", extra={
        "baseline": "single TCP data channel, scatter-gather off "
                    "(pre-striping wire path)",
        "best": best,
        "rpc_p50_ms": {"baseline": base_rpc, "striped": rpc_striped},
    }, out_dir=SMOKE_DIR)
    GLOBAL_REGISTRY.enabled = False


def async_transport_sweep():
    """Async-dispatcher vs thread-per-lane A/B on the striped-fetch
    data path, plus RPC echo p50 under concurrent bulk, plus the
    transport thread census — writes BENCH_async_transport.json with
    the threaded baseline embedded.  Absolute numbers on this host
    drift run to run; the interleaved best-of ratios are the signal."""
    from sparkrdma_tpu.metrics import GLOBAL_REGISTRY

    GLOBAL_REGISTRY.enabled = True
    port = BASE_PORT + 900
    stripes = 2
    reps = 1 if SMOKE else 3
    table = {"threaded": {}, "async": {}}
    rpc = {}
    census = {}
    # INTERLEAVED reps, best-of: this 1-core bench host is noisy
    # (run-to-run throughput swings ±20%), so each mode's number is the
    # best of `reps` alternating measurements — the same denoising the
    # decode sweep uses, applied A/B-fairly
    import threading as _th

    from sparkrdma_tpu.transport.node import TRANSPORT_THREAD_PREFIXES

    for rep in range(reps):
        for mode, flag in (("threaded", "off"), ("async", "on")):
            # census by DELTA against the threads alive before this
            # config: earlier reps leak lingering threaded-engine
            # threads (a closed listener does not wake a blocked
            # accept()), which would otherwise contaminate the async
            # engine's count with the exact threads it exists to remove
            pre = {t.ident for t in _th.enumerate()}
            cfg = _fetch_config(
                f"{mode} transport", port, stripes, "on",
                {"spark.shuffle.tpu.transportAsyncDispatcher": flag},
            )
            try:
                for size in SWEEP_SIZES:
                    gbps = _fetch_throughput_windowed(cfg, size)
                    table[mode][size] = max(
                        table[mode].get(size, 0.0), gbps
                    )
                p50 = _rpc_latency_under_bulk(cfg)
                rpc[mode] = min(rpc.get(mode, float("inf")), p50)
                by_role = {}
                for t in _th.enumerate():
                    if t.ident in pre:
                        continue
                    for prefix in TRANSPORT_THREAD_PREFIXES:
                        if t.name.startswith(prefix):
                            role = prefix.rstrip("-")
                            by_role[role] = by_role.get(role, 0) + 1
                            break
                census[mode] = {
                    "transport_threads": sum(by_role.values()),
                    "by_role": by_role,
                }
            finally:
                _teardown_config(cfg)
            port += 30
    for mode in ("threaded", "async"):
        for size in SWEEP_SIZES:
            base = table["threaded"][size]
            emit(
                f"windowed striped fetch {size >> 20}MiB "
                f"({mode} transport, stripes={stripes}, best of {reps})",
                table[mode][size], "GB/s",
                table[mode][size] / base if base else 1.0,
            )
        emit(
            f"RPC echo p50 under concurrent bulk ({mode} transport, "
            f"best of {reps})",
            rpc[mode], "ms",
            rpc["threaded"] / rpc[mode] if rpc[mode] else 1.0,
        )
    ratios = {
        size: table["async"][size] / table["threaded"][size]
        for size in SWEEP_SIZES
    }
    best_size = max(ratios, key=ratios.get)
    emit(
        f"best async-vs-threaded striped fetch ({best_size >> 20}MiB)",
        table["async"][best_size], "GB/s", ratios[best_size],
    )
    # aggregate sweep throughput (total bytes / total best-case time):
    # the single headline number the acceptance criterion reads
    agg = {
        m: sum(SWEEP_SIZES)
        / sum(size / table[m][size] for size in SWEEP_SIZES)
        for m in ("threaded", "async")
    }
    emit(
        "aggregate windowed striped-fetch throughput (async, "
        "size-weighted over sweep)",
        agg["async"], "GB/s",
        agg["async"] / agg["threaded"] if agg["threaded"] else 1.0,
    )
    from benchmarks.common import write_bench_json

    write_bench_json("async_transport", extra={
        "baseline": "transportAsyncDispatcher=off — the thread-per-"
                    "lane blocking wire path (one reader thread per "
                    "channel + accept thread + serve workers blocked "
                    "through sends)",
        "stripes": stripes,
        "fetch_gbps": {
            m: {f"{s >> 20}MiB": round(v, 4) for s, v in t.items()}
            for m, t in table.items()
        },
        "fetch_ratio_async_vs_threaded": {
            f"{s >> 20}MiB": round(r, 4) for s, r in ratios.items()
        },
        "fetch_window": 4,
        "aggregate_gbps": {m: round(v, 4) for m, v in agg.items()},
        "aggregate_ratio_async_vs_threaded": round(
            agg["async"] / agg["threaded"], 4
        ) if agg.get("threaded") else None,
        "rpc_p50_ms": {m: round(v, 4) for m, v in rpc.items()},
        "rpc_p50_ratio_threaded_over_async": round(
            rpc["threaded"] / rpc["async"], 4
        ) if rpc.get("async") else None,
        "transport_census": census,
        "host_note": (
            f"bench host has {os.cpu_count()} CPU core(s) and its "
            "absolute throughput drifts 1.5-2x between runs, so only "
            "the interleaved best-of ratios are meaningful: this run "
            "measured async/threaded fetch ratios of "
            + ", ".join(
                f"{s >> 20}MiB={ratios[s]:.2f}x" for s in SWEEP_SIZES
            )
            + f" (size-weighted aggregate "
            f"{agg['async'] / agg['threaded']:.2f}x) and RPC p50 "
            f"{rpc['async']:.3f} vs {rpc['threaded']:.3f} ms.  The "
            "async engine runs the transport on one event-loop thread "
            "+ bounded pools instead of O(peers x stripes) readers; "
            "lane streaming gives busy lanes the threaded reader's "
            "syscall shape, and the residual RPC delta is per-wake "
            "loop machinery that stops timeslicing against the peers "
            "once the host has >1 core"
        ),
    }, out_dir=SMOKE_DIR)
    GLOBAL_REGISTRY.enabled = False


def fabric_scale_sweep():
    """Dry-run connect+fetch against {8, 64, 256} simulated peers
    through the pooled fabric, bounded (transportMaxCachedChannels=16)
    vs unbounded (=0, the pre-fabric behavior) — per point: sweep wall
    time, fd/thread census, cached-channel occupancy, evictions.
    Writes BENCH_fabric_scale.json."""
    import threading as _th

    from sparkrdma_tpu.conf import TpuShuffleConf
    from sparkrdma_tpu.metrics import GLOBAL_REGISTRY
    from sparkrdma_tpu.transport import TcpNetwork
    from sparkrdma_tpu.transport.channel import FnCompletionListener
    from sparkrdma_tpu.transport.node import Node, transport_census
    from sparkrdma_tpu.transport.simfleet import SimPeerFleet
    from sparkrdma_tpu.utils.types import BlockLocation

    GLOBAL_REGISTRY.enabled = True
    pattern = (np.arange(2 << 20, dtype=np.uint32) % 251).astype(np.uint8)
    connect = TcpNetwork().connect
    port = 47000
    node_port = 46990
    table = {}

    def sweep(node, addresses, window=8):
        """One striped fetch per peer, ``window`` peers in flight (the
        reader's maxBytesInFlight shape — an unbounded burst would
        just measure the tolerated-overflow path)."""
        done_all = _th.Event()
        left = [len(addresses)]
        errs = []
        lk = _th.Lock()
        sem = _th.BoundedSemaphore(window)

        def settle(e=None):
            if e is not None:
                errs.append(e)
            sem.release()
            with lk:
                left[0] -= 1
                if left[0] == 0:
                    done_all.set()

        t0 = time.perf_counter()
        for i, peer in enumerate(addresses):
            addr = (i * 7919) % (len(pattern) - FABRIC_BLOCK)
            sem.acquire()
            node.get_read_group(peer, connect).read_blocks(
                [BlockLocation(addr, FABRIC_BLOCK, 1)],
                FnCompletionListener(
                    lambda blocks: settle(), lambda e: settle(e)
                ),
            )
        if not done_all.wait(300):
            raise RuntimeError("fabric sweep hung")
        if errs:
            raise errs[0]
        return time.perf_counter() - t0

    for n_peers in FABRIC_PEERS:
        fleet = SimPeerFleet(n_peers, port, pattern)
        port += n_peers + 16
        for mode, cap in (("unbounded", 0), ("bounded", FABRIC_CAP)):
            before = transport_census()
            ev0 = GLOBAL_REGISTRY.counter(
                "transport_channel_evictions_total").value
            node = Node(("127.0.0.1", node_port), TpuShuffleConf({
                "spark.shuffle.tpu.transportMaxCachedChannels": cap,
                "spark.shuffle.tpu.transportLanePoolSize": 8,
                "spark.shuffle.tpu.transportNumStripes": 2,
                "spark.shuffle.tpu.transportStripeThreshold": "64k",
            }))
            node_port += 1
            try:
                cold = sweep(node, fleet.addresses)
                warm = sweep(node, fleet.addresses)
                census = transport_census()
                with node._active_lock:
                    cached = len(node._active)
                point = {
                    "cold_connect_fetch_s": round(cold, 4),
                    "warm_fetch_s": round(warm, 4),
                    "fetch_mb": round(
                        n_peers * FABRIC_BLOCK / 1e6, 1),
                    "cached_channels": cached,
                    "evictions": GLOBAL_REGISTRY.counter(
                        "transport_channel_evictions_total"
                    ).value - ev0,
                    "transport_threads_grown": (
                        census["transport_threads"]
                        - before["transport_threads"]),
                    "open_fds_grown": (
                        census["open_fds"] - before["open_fds"]
                        if census["open_fds"] > 0
                        and before["open_fds"] > 0 else None),
                }
                table.setdefault(n_peers, {})[mode] = point
                emit(
                    f"fabric {n_peers} peers {mode} "
                    f"(cap={cap or 'off'}): cold connect+fetch sweep",
                    cold, "s",
                    1.0 if mode == "unbounded"
                    else table[n_peers]["unbounded"][
                        "cold_connect_fetch_s"] / cold,
                )
            finally:
                node.stop()
        fleet.close()
    from benchmarks.common import write_bench_json

    write_bench_json("fabric_scale", extra={
        "baseline": "transportMaxCachedChannels=0 — the pre-fabric "
                    "unbounded channel cache (every peer keeps its "
                    "lanes forever)",
        "block_bytes": FABRIC_BLOCK,
        "cap": FABRIC_CAP,
        "sweep": {str(k): v for k, v in table.items()},
        "note": (
            "per point: one striped 256KiB fetch per peer, cold "
            "(connect+fetch) then warm; bounded mode holds cached "
            "channels at the cap via LRU eviction while unbounded "
            "grows O(peers x lanes) — the fd/thread census per point "
            "is the scaling signal, the bounded-vs-unbounded sweep "
            "time ratio is the (small) cost of paying reconnects"
        ),
    }, out_dir=SMOKE_DIR)
    GLOBAL_REGISTRY.enabled = False


def _decode_cluster(threads, mode_conf, base_port):
    """Driver + 2 executors on loopback with the decode-pipeline conf."""
    from collections import defaultdict

    from sparkrdma_tpu.conf import TpuShuffleConf
    from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
    from sparkrdma_tpu.transport import LoopbackNetwork

    net = LoopbackNetwork()
    conf_map = {
        "spark.shuffle.tpu.driverPort": base_port,
        "spark.shuffle.tpu.decodeThreads": threads,
        "spark.shuffle.tpu.partitionLocationFetchTimeout": "60s",
    }
    conf_map.update(mode_conf)
    conf = TpuShuffleConf(conf_map)
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
    return net, driver, executors, defaultdict(list)


def _decode_reduce_once(threads, mode_conf, base_port, keys, vals):
    """Write the maps (untimed), then time the reduce-side consume —
    fetch + deserialize/inflate + ordered merge — across every
    partition.  Returns (best seconds, serialized bytes, output)."""
    from sparkrdma_tpu.utils.columns import ColumnBatch

    net, driver, executors, maps_by_host = _decode_cluster(
        threads, mode_conf, base_port
    )
    try:
        from sparkrdma_tpu.shuffle.partitioner import HashPartitioner

        handle = driver.register_shuffle(
            5, 2, HashPartitioner(DECODE_PARTS), key_ordering=True
        )
        n = len(keys) // 2
        total_bytes = 0
        for m, ex in enumerate(executors):
            w = ex.get_writer(handle, m)
            w.write(ColumnBatch(keys[m * n:(m + 1) * n],
                                vals[m * n:(m + 1) * n]))
            w.stop(True)
            total_bytes += w.metrics.bytes_written
            maps_by_host[ex.local_smid].append(m)
        best = float("inf")
        out = None
        for _ in range(DECODE_REPS):
            t0 = time.perf_counter()
            got = []
            for pid in range(DECODE_PARTS):
                reader = executors[pid % 2].get_reader(
                    handle, pid, pid + 1, dict(maps_by_host)
                )
                got.append(list(reader.read()))
            dt = time.perf_counter() - t0
            best = min(best, dt)
            out = got
        return best, total_bytes, out
    finally:
        for m in executors + [driver]:
            m.stop()


def decode_pipeline_sweep():
    """Decode-bound reduce sweep: compressed + columnar payloads ×
    decodeThreads {0, 1, 2, 4}, serial (decodeThreads=0, the legacy
    task-thread decode) as the embedded baseline; verifies the
    pipelined output is bit-exact against the serial one per mode.
    Writes BENCH_decode_pipeline.json."""
    from sparkrdma_tpu.metrics import GLOBAL_REGISTRY

    GLOBAL_REGISTRY.enabled = True
    rng = np.random.default_rng(7)
    # wide-spread int64 keys (unique with overwhelming probability →
    # fully deterministic sorted output) + incompressible payloads:
    # zlib then stores rather than squeezes, the already-compressed /
    # encrypted-shuffle shape where decode is copy- not inflate-bound
    keys = rng.permutation(DECODE_RECORDS).astype(np.int64)
    vals = np.frombuffer(
        rng.bytes(DECODE_RECORDS * DECODE_PAYLOAD),
        dtype=f"S{DECODE_PAYLOAD}",
    )
    modes = {
        "compressed-columnar": {
            "spark.shuffle.tpu.serializer": "columnar",
            "spark.shuffle.tpu.compress": True,
        },
        "columnar": {"spark.shuffle.tpu.serializer": "columnar"},
    }
    port = BASE_PORT + 400
    # warmup cluster: first-run costs (codec/native-lib loading, pool
    # page faults) must not land on the serial baseline's measurement
    _decode_reduce_once(
        0, modes["compressed-columnar"], port,
        keys[: max(DECODE_RECORDS // 20, 256)],
        vals[: max(DECODE_RECORDS // 20, 256)],
    )
    table = {}
    best = {"ratio": 0.0, "mode": "", "threads": 0, "mbps": 0.0}
    for mode, conf in modes.items():
        serial_out = None
        for threads in DECODE_THREADS:
            port += 50
            dt, nbytes, out = _decode_reduce_once(
                threads, conf, port, keys, vals
            )
            if threads == 0:
                serial_out = out
            else:
                assert out == serial_out, (
                    f"{mode}: decodeThreads={threads} output diverged "
                    f"from the serial baseline"
                )
            mbps = nbytes / dt / 1e6
            table.setdefault(mode, {})[threads] = {
                "seconds": round(dt, 4),
                "serialized_mb_per_s": round(mbps, 2),
            }
            base = table[mode][0]["serialized_mb_per_s"]
            ratio = mbps / base if base else 1.0
            emit(
                f"reduce consume {mode} decodeThreads={threads} "
                f"({DECODE_RECORDS} records, key-ordered merge)",
                mbps, "MB/s", ratio,
            )
            if threads >= 2 and ratio > best["ratio"]:
                best.update(ratio=ratio, mode=mode, threads=threads,
                            mbps=mbps)
    emit(
        f"best pipelined reduce consume vs serial-decode baseline "
        f"({best['mode']}, decodeThreads={best['threads']})",
        best["mbps"], "MB/s", best["ratio"],
    )
    from benchmarks.common import write_bench_json

    write_bench_json("decode_pipeline", extra={
        "baseline": "decodeThreads=0 — the legacy serial task-thread "
                    "decode (pre-pipeline consume path)",
        "serial_baseline": {
            m: table[m][0] for m in table
        },
        "sweep": table,
        "best_pipelined": best,
        "bit_exact": True,
        "host_note": (
            f"bench host has {os.cpu_count()} CPU core(s): with one "
            "core, decode workers can only timeslice against the task "
            "thread, so decodeThreads>=2 cannot exceed serial "
            "throughput here (the conf default therefore falls back "
            "to decodeThreads=0 on single-core hosts, the "
            "bulkPipelineWindows convention); the sweep still "
            "exercises and bit-exact-verifies the full pipelined "
            "path — fetch/decode overlap needs >=2 cores to pay"
        ),
    }, out_dir=SMOKE_DIR)
    GLOBAL_REGISTRY.enabled = False


def main():
    if TRACE:
        # hold both planes open for the whole run: every read carries a
        # live span and the recorder rings absorb the event traffic,
        # the worst-case (sampleRate=1.0) tracing cost
        from sparkrdma_tpu.obs import RECORDER, TRACING

        TRACING.retain(1.0)
        RECORDER.retain(ring_size=4096)
    rng = np.random.default_rng(1)
    records = [(int(k), 1) for k in rng.integers(0, N_KEYS, N_RECORDS)]

    with TpuShuffleContext(num_executors=2, stage_to_device=False) as ctx:
        ds = ctx.parallelize(records, num_slices=4)
        t0 = time.perf_counter()
        out = ds.reduce_by_key(lambda a, b: a + b, num_partitions=4).collect()
        dt = time.perf_counter() - t0

    assert len(out) == N_KEYS
    assert sum(v for _, v in out) == N_RECORDS
    rps = N_RECORDS / dt
    # no published reference number for this config (chart image only);
    # baseline ratio is vs 1M records/s, a round figure for a 2-node
    # Spark reduceByKey on the reference's hardware class
    emit(
        f"2-executor reduceByKey record throughput ({N_RECORDS} records, "
        f"{N_KEYS} keys)",
        rps / 1e6, "Mrecords/s", rps / 1e6,
    )
    from benchmarks.common import write_bench_json

    write_bench_json("reduce_loopback", out_dir=SMOKE_DIR)
    RESULTS.clear()
    striped_fetch_sweep()
    RESULTS.clear()
    decode_pipeline_sweep()
    RESULTS.clear()
    async_transport_sweep()
    RESULTS.clear()
    fabric_scale_sweep()


if __name__ == "__main__":
    main()
