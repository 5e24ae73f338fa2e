#!/usr/bin/env python
"""Chip smoke: the shuffle's main path, once, on TPU, checked exactly.

    python chip_smoke.py [--seed N]        # one chip, three phases
    python chip_smoke.py --chips 4         # the paths that span chips

One process drives every chip.  Each phase goes through the user entry
points (``sparkrdma_tpu/api.py``), is checked exactly against a plain
numpy reference on data made from ``--seed``, and prints one JSON line:
sizes, compile seconds (JAX's backend-compile events, so a warm
persistent cache shows up as fewer), wall seconds, the device's peak
HBM so far and whether the native staging library was loaded.  The
last line is ``{"ok": true, "device": {...}}``.  Without a TPU, or
outside the repo, it exits non-zero before any phase runs.
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# HiBench TeraSort record: 4-byte key + 24 int32 payload words = 100 B
WIDE_WORDS = 24


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(f"check failed: {msg}")


_COMPILE_S = [0.0]


def _on_event(event: str, secs: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += secs


class Phase:
    """Times one phase and prints its line."""

    def __init__(self, name: str, **sizes):
        self.name, self.fields = name, dict(sizes)

    def __enter__(self):
        self.c0, self.t0 = _COMPILE_S[0], time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        import jax

        from sparkrdma_tpu.memory.staging import _NATIVE

        self.fields.update(
            compile_s=_COMPILE_S[0] - self.c0,
            wall_s=time.perf_counter() - self.t0,
            peak_hbm_bytes=[
                (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in jax.devices()
            ],
            native_staging=_NATIVE is not None,
        )
        print(json.dumps({"phase": self.name, **self.fields}), flush=True)
        return False


def terasort_wide(ctx, mesh, n: int, seed: int) -> None:
    """``ctx.device_sort`` on HiBench-shaped rows.  Payload word 0 is
    the row's input index, so one gather checks all three claims: keys
    sorted, keys a permutation of the input, every row on its key."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 1 << 31, n, dtype=np.int32)
    payload = rng.integers(0, 1 << 31, (n, WIDE_WORDS), dtype=np.int32)
    payload[:, 0] = np.arange(n, dtype=np.int32)
    D = len(mesh.devices.flat)
    with Phase("terasort_wide", records=n, record_bytes=4 + 4 * WIDE_WORDS,
               bytes=n * (4 + 4 * WIDE_WORDS), chips=D) as ph:
        sk, sp = ctx.device_sort(keys, payload, mesh=mesh)
        check(sk.shape == (n,) and sp.shape == (n, WIDE_WORDS),
              f"terasort output shapes {sk.shape} {sp.shape}")
        check(bool(np.all(sk[1:] >= sk[:-1])), "terasort keys not sorted")
        perm = sp[:, 0]
        seen = np.zeros(n, bool)
        seen[perm] = True
        check(bool(seen.all()), "terasort rows are not a permutation")
        check(np.array_equal(keys[perm], sk), "terasort keys off their rows")
        step = 1 << 22
        for lo in range(0, n, step):
            check(np.array_equal(payload[perm[lo:lo + step]],
                                 sp[lo:lo + step]),
                  f"terasort payload rows off their keys near row {lo}")
        ph.fields["exact"] = True


def wordcount(ctx, n: int, vocab: int, seed: int) -> None:
    """``ctx.device_count`` (reduceByKey(+)) on Zipf(1.3) words; the
    column sizes send its cumsums through the Pallas scan kernels."""
    from sparkrdma_tpu.ops.scan_kernels import (
        MIN_KERNEL_ELEMS,
        use_scan_kernels,
    )

    check(use_scan_kernels() and n >= MIN_KERNEL_ELEMS,
          "wordcount would bypass the scan kernels")
    rng = np.random.default_rng(seed + 1)
    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** -1.3)
    words = np.searchsorted(cdf / cdf[-1], rng.random(n)).astype(np.int32)
    with Phase("wordcount", words=n, vocab=vocab, zipf_s=1.3) as ph:
        counts = ctx.device_count(words)
        ref_k, ref_c = np.unique(words, return_counts=True)
        got_k = np.fromiter(counts.keys(), np.int64, len(counts))
        got_c = np.fromiter(counts.values(), np.int64, len(counts))
        order = np.argsort(got_k)
        check(np.array_equal(got_k[order], ref_k)
              and np.array_equal(got_c[order], ref_c),
              "wordcount counts differ from np.unique")
        ph.fields.update(distinct=len(ref_k), exact=True)


def record_plane(n_executors: int, n: int, seed: int,
                 device_exchange: bool):
    """write → publish → resolve → TileExchange → read through
    ``parallelize_columns(...).sort_by_key()``; returns the output
    (keys, vals).  Keys must equal the stable numpy sort; values match
    it as (key, value) pairs, and exactly where one executor keeps the
    map order among equal keys."""
    from sparkrdma_tpu.api import TpuShuffleContext
    from sparkrdma_tpu.conf import TpuShuffleConf

    rng = np.random.default_rng(seed + 2)
    keys = rng.integers(0, 1 << 20, n, dtype=np.int32)
    vals = rng.permutation(n).astype(np.int32)
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.readPlane": "bulk",
        "spark.shuffle.tpu.serializer": "columnar",
        "spark.shuffle.tpu.deviceExchangeEnabled":
            "true" if device_exchange else "false",
    })
    with Phase("record_plane", records=n, executors=n_executors,
               device_exchange=device_exchange) as ph:
        ctx = TpuShuffleContext(num_executors=n_executors, conf=conf)
        try:
            out = np.array(
                ctx.parallelize_columns(keys, vals).sort_by_key().collect(),
                dtype=np.int64,
            )
            stats = ctx.bulk_session.exchange.stats()
        finally:
            ctx.stop()
        check(out.shape == (n, 2), f"record plane returned {out.shape}")
        ok, ov = out[:, 0], out[:, 1]
        order = np.argsort(keys, kind="stable")
        check(np.array_equal(ok, keys[order]),
              "record plane keys differ from the stable numpy sort")
        stable = np.array_equal(ov, vals[order])
        check(stable or n_executors > 1,
              "one executor must keep the map order among equal keys")
        check(np.array_equal(ov[np.lexsort((ov, ok))],
                             vals[np.lexsort((vals, keys))]),
              "record plane (key, value) pairs differ from numpy")
        check((stats["device_exchanges"] > 0) == device_exchange,
              f"device_exchanges={stats['device_exchanges']} with "
              f"deviceExchangeEnabled={device_exchange}")
        ph.fields.update(device_exchanges=stats["device_exchanges"],
                         stable_ties=bool(stable), exact=True)
    return ok, ov


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the paths that span four chips")
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU found: JAX reports platform {devs[0].platform!r}")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPU devices, "
             f"JAX reports {len(devs)}")

    # the native staging library, from the committed sources (-B: the
    # committed binary must not win on mtime)
    build = subprocess.run(
        ["make", "-B", "-C", os.path.join(REPO, "native")],
        capture_output=True, text=True,
    )
    if build.returncode:
        fail(f"native build failed:\n{build.stdout}{build.stderr}")

    sys.path.insert(0, REPO)
    from sparkrdma_tpu.api import TpuShuffleContext
    from sparkrdma_tpu.parallel.mesh import make_mesh
    from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_event)

    ctx = TpuShuffleContext(num_executors=1)
    try:
        if args.chips == 4:
            from sparkrdma_tpu.parallel.ring import (
                supports_pallas_partition_id,
            )

            check(supports_pallas_partition_id(),
                  "the Pallas ring probe answered False on four chips")
            terasort_wide(ctx, make_mesh(4), 4 << 24, args.seed)
            on = record_plane(4, 1 << 22, args.seed, True)
            off = record_plane(4, 1 << 22, args.seed, False)
            check(all(np.array_equal(a, b) for a, b in zip(on, off)),
                  "record plane output differs with the device exchange "
                  "on and off")
        else:
            terasort_wide(ctx, make_mesh(1), 1 << 25, args.seed)
            wordcount(ctx, 1 << 26, 1 << 20, args.seed)
            record_plane(1, 1 << 22, args.seed, True)
    finally:
        ctx.stop()

    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)


if __name__ == "__main__":
    main()
