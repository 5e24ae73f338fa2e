"""Shared helpers for the benchmark suite.

Every benchmark prints one JSON line per metric, the same shape as the
repo-root ``bench.py``:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

``vs_baseline`` compares against the reference data plane's per-node
ceiling — the 100 GbE RoCE line rate of 12.5 GB/s that bounds
SparkRDMA's shuffle throughput (reference README.md:7-19) — unless a
benchmark states its own baseline.

Every emitted record is also collected in-process so
:func:`write_bench_json` can write a ``BENCH_<name>.json`` embedding
the results TOGETHER with a metrics-registry snapshot
(sparkrdma_tpu/metrics/) — a bench run carries its own transport /
shuffle / memory counters for later attribution
(``tools/metrics_report.py`` renders the embedded snapshot).
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable, Optional

import jax
import numpy as np

from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()

# 100 GbE RoCE line rate, the reference's per-node data-plane ceiling (GB/s)
ROCE_LINE_RATE_GBPS = 12.5

# every emit() record of this process, in order
RESULTS: list = []


def fence(x) -> None:
    """Wait for the device work that produced ``x`` (device execution
    is in-order, so this fences every prior dispatch too)."""
    jax.block_until_ready(x)


def time_iters(run: Callable[[], object], iters: int, warmup: int = 2) -> float:
    """Mean seconds per iteration; dispatches asynchronously and fences
    once so the host round trip is amortized out."""
    out = None
    for _ in range(warmup):
        out = run()
    fence(jax.tree.leaves(out)[-1])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = run()
    fence(jax.tree.leaves(out)[-1])
    return (time.perf_counter() - t0) / iters


def emit(metric: str, value: float, unit: str, vs_baseline: float) -> None:
    rec = {
        "metric": metric,
        "value": round(float(value), 3),
        "unit": unit,
        "vs_baseline": round(float(vs_baseline), 3),
    }
    RESULTS.append(rec)
    print(json.dumps(rec), flush=True)


def enable_metrics(conf) -> None:
    """Turn the metrics registry on for a bench's TpuShuffleConf (and
    the process-wide registry, so transport/memory instruments created
    before the manager exist too)."""
    from sparkrdma_tpu.metrics import get_registry

    conf.set("metrics", True)
    get_registry().enabled = True


def metrics_snapshot() -> dict:
    """Point-in-time snapshot of the process-wide metrics registry."""
    from sparkrdma_tpu.metrics import get_registry

    return get_registry().snapshot()


def write_bench_json(name: str, extra: Optional[dict] = None,
                     out_dir: Optional[str] = None) -> str:
    """Write ``BENCH_<name>.json`` embedding every emitted result plus
    the current metrics snapshot; returns the path."""
    doc = {
        "bench": name,
        "results": list(RESULTS),
        "metrics": metrics_snapshot(),
    }
    if extra:
        doc.update(extra)
    base = out_dir or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    path = os.path.join(base, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {path}", flush=True)
    return path


# -- multi-device record-plane benches ---------------------------------------

SPOOF_ENV = "SPARKRDMA_TPU_BENCH_SPOOFED"


def ensure_multidevice(script_path: str, min_devices: int = 4) -> None:
    """Benches that need a multi-device mesh call this FIRST.  On a CPU
    host with too few devices it re-execs the script onto a virtual
    8-device CPU mesh (the test suite's harness) and exits with the
    child's status; on an accelerator host with too few devices it
    fails — a CPU mesh there would print a number under the chip's
    name."""
    import subprocess
    import sys

    devs = jax.devices()
    if len(devs) >= min_devices:
        return
    if devs[0].platform != "cpu":
        sys.exit(
            f"{os.path.basename(script_path)}: needs {min_devices} "
            f"devices, this host has {len(devs)} "
            f"{devs[0].platform} device(s); run it on a "
            f"{min_devices}-chip host"
        )
    if os.environ.get(SPOOF_ENV):
        raise RuntimeError(
            f"spoofed respawn still has <{min_devices} devices"
        )
    env = dict(os.environ)
    env[SPOOF_ENV] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    sys.exit(subprocess.call(
        [sys.executable, os.path.abspath(script_path)], env=env
    ))


def zipf_keys(rng, s: float, n: int, n_keys: int = 100_000,
              dtype=np.int64) -> np.ndarray:
    """Rank-preserving bounded Zipf sample: key id == frequency rank
    (key 0 is the hottest).  Draws via inverse CDF over ranks
    1..n_keys, so P(key=r) ∝ 1/(r+1)^s exactly.

    This replaces the old ``rng.zipf(s, n) % n_keys`` idiom, which
    folds the unbounded tail onto arbitrary residues: the fold lands
    huge rank samples on top of small key ids at random, flattening
    the head and breaking the rank-frequency law the benchmark means
    to model."""
    ranks = np.arange(1, n_keys + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -s)
    cdf /= cdf[-1]
    return np.searchsorted(cdf, rng.random(n)).astype(dtype)


def canonical_record_workload(n_records: int = 1_000_000, payload: int = 64,
                              n_keys: int = 512, seed: int = 0):
    """The shared record-plane workload (keys, S-payload vals) so the
    cross-plane BASELINE comparison benchmarks identical data."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys, n_records).astype(np.int64)
    vals = np.frombuffer(
        rng.bytes(n_records * payload), dtype=f"S{payload}"
    )
    return keys, vals


def time_group_by_key(ctx, keys, vals, n_keys: int, reps: int = 3) -> float:
    """Warm + verify + best-of-reps seconds for a groupByKey of the
    canonical workload through a context."""
    ds = ctx.parallelize_columns(keys, vals, num_slices=8)
    out = ds.group_by_key(num_partitions=8).collect()
    assert len(out) == n_keys, f"expected {n_keys} groups, got {len(out)}"
    assert sum(len(vs) for _, vs in out) == len(keys)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        ds.group_by_key(num_partitions=8).collect()
        best = min(best, time.perf_counter() - t0)
    return best
