"""One run of one benchmark cell on the chips of this machine.

    python3 shufflebench/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Set-up makes the cell's inputs from the seed (on the device, fetched to
host memory, as map output in an executor), and warms the cell's job
shapes with one job.  The window then runs jobs back to back through
``TpuShuffleContext`` (``traffic/<mix>.json``) for ``--seconds``, in the
context the configuration states (:func:`_context`).
With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result holds
its per-layer metrics, read from the trace by ``metrics/<name>.py``.
After the window a sample of the jobs' outputs, drawn from the seed,
and the last job's are compared with the plain reference
(``jobs/<job>.py``); each number compared is printed beside its limit.
The last line of standard output is the result, one JSON object.

Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from shufflebench import loop, manifest as mf  # noqa: E402
from shufflebench.peaks import least_time_s, peaks_for  # noqa: E402

# JAX's persistent compilation cache: a fixed path inside the checkout,
# so only a checkout's first run compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "shufflebench")


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def process_age_s() -> float:
    """Seconds since this process started (the kernel's start time)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class Compiles:
    """Counts XLA backend compiles and their seconds."""

    def __init__(self):
        self.n, self.seconds = 0, 0.0

    def __call__(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs


class Keep:
    """Which outputs of the window are compared: one job drawn
    uniformly from the seed among those completed (a reservoir of
    one), and the last job."""

    def __init__(self, seed: int):
        import numpy as np

        self.rng = np.random.default_rng(seed)
        self.sample = self.last = None

    def __call__(self, i: int, n: int, out) -> None:
        if self.rng.integers(0, i + 1) == 0:
            self.sample = (i, n, out)
        self.last = (i, n, out)

    def outputs(self):
        got = [x for x in (self.sample, self.last) if x is not None]
        return list({i: (i, n, out) for i, n, out in got}.values())


@dataclass
class Reading:
    """What a per-layer reader (``metrics/<name>.py``) is given: the
    reduced trace, the sizes of the window's completed jobs, the cell's
    job kind, configuration and chips, and the device kind's peaks
    (None off a TPU)."""

    trace: Any
    jobs: List[int]
    job: Any
    config: dict
    chips: int
    peaks: Optional[dict]

    def least_bytes(self, n: int) -> dict:
        return self.job.least_bytes(self.config, n, self.chips)


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = mf.ROOT, manifest: dict = None,
             age_s=process_age_s, require_tpu: bool = True,
             save_trace: str = None, log=sys.stderr) -> dict:
    """Run cell ``name`` once and return its result line."""
    import jax

    manifest = manifest or mf.load(root)
    cell = mf.workload(manifest, name)
    config = mf.config(manifest, cell["config"], root)
    traffic = mf.traffic(cell["traffic"], root)
    job = mf.plugin("jobs", config["job"], root)
    chips = cell["chips"]

    devs = jax.devices()
    dev = devs[0]
    print(f"# device platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devs)}", file=log, flush=True)
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX reports platform {dev.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"cell {name} needs {chips} chips, JAX reports "
                     f"{len(devs)}")
    peaks = peaks_for(dev.device_kind) if require_tpu else None

    keep = Keep(seed)
    trace_dir = tempfile.mkdtemp(prefix="shufflebench-") if trace else None
    try:
        inputs, window, run = _measure(job, config, traffic, chips, seed,
                                       seconds, keep, trace_dir, age_s)
        stats = [d.memory_stats() or {} for d in devs]
        peak = max((s.get("peak_bytes_in_use", 0) for s in stats),
                   default=0) or None
        result = {"correct": False, "attempted": window.attempted,
                  "failed": window.failed, "metrics": {},
                  "device": {"platform": dev.platform,
                             "kind": dev.device_kind, "count": len(devs),
                             "memory_peak_bytes": peak}}
        if trace:
            _read_trace(result, trace_dir, run.pop("devices"), save_trace,
                        Reading(None, window.sizes, job, config, chips,
                                peaks), manifest, name, root, log)
        else:
            e2e = {
                "shuffle_gb_s_chip": config["bytes_per_record"]
                * sum(window.sizes) / window.seconds / chips / 1e9,
                "peak_hbm_gib": peak / 2**30 if peak else None,
                "setup_s": run["setup_s"],
            }
            for m in mf.metrics_of(manifest, name, "end_to_end"):
                if e2e[m["name"]] is not None:
                    result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                    "unit": m["unit"]}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run.pop("devices", None)
    result["run"] = run

    # the comparison that decides ``correct``, once the window is over,
    # the device's peak read and the program's state freed
    t0 = time.perf_counter()
    limits = job.LIMITS
    numbers = dict.fromkeys(limits, 0)
    refs = {}
    for _i, n, out in keep.outputs():
        if n not in refs:
            refs[n] = job.reference(inputs, n)
        for k, v in job.compare(config, inputs, n, out, refs[n]).items():
            numbers[k] = max(numbers[k], v)
    run["check_s"] = time.perf_counter() - t0
    # a job that failed is an answer that never came
    result["correct"] = window.failed == 0 and all(
        numbers[k] <= limits[k] for k in limits)
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in limits}
    print(f"# jobs attempted {window.attempted} failed {window.failed}",
          file=log)
    for k in limits:
        print(f"check {k} {numbers[k]} limit {limits[k]}", file=log)
    log.flush()
    return result


def _context(config: dict, chips: int):
    """The shuffle context a cell's jobs run in, and its record: one
    executor a chip on the conf keys the configuration states under
    ``context`` (without the ``spark.shuffle.tpu.`` prefix), or, without
    that key, one executor on the default conf."""
    from sparkrdma_tpu.api import TpuShuffleContext
    from sparkrdma_tpu.conf import TpuShuffleConf

    conf = config.get("context")
    if conf is None:
        ctx = TpuShuffleContext(num_executors=1)
        conf = {}
    else:
        bad = mf.context_problems(config.get("name", "config"), conf)
        if bad:
            raise ValueError("; ".join(bad))
        ctx = TpuShuffleContext(
            num_executors=chips,
            conf=TpuShuffleConf({TpuShuffleConf.PREFIX + k: v
                                 for k, v in conf.items()}))
    return ctx, {"executors": len(ctx.executors), "conf": dict(conf),
                 "read_plane": ctx.conf.read_plane}


def _measure(job, config, traffic, chips, seed, seconds, keep, trace_dir,
             age_s):
    """Set-up, then the measured window (under the profiler when
    ``trace_dir`` is given).  Returns the inputs, the window, and the
    run's record: set-up's parts and the compiles."""
    import jax

    from sparkrdma_tpu.parallel.mesh import make_mesh

    n = loop.records(traffic)
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    mesh = make_mesh(chips)
    ctx, context = _context(config, chips)
    try:
        t0 = time.perf_counter()
        inputs = job.make_inputs(config, n, seed)
        t1 = time.perf_counter()
        job.run(ctx, inputs, n, mesh)  # warms the one shape the window runs
        run = {"context": context,
               "inputs_s": t1 - t0, "warmup_s": time.perf_counter() - t1,
               "setup_compiles": compiles.n,
               "setup_compile_s": compiles.seconds,
               "devices": [d.id for d in mesh.devices.flat]}
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        run["setup_s"] = age_s()
        try:
            with _span("window"):
                window = loop.closed_loop(
                    traffic, lambda n: job.run(ctx, inputs, n, mesh),
                    seconds, keep, _span)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
    finally:
        ctx.stop()
    run.update(jobs=len(window.sizes), window_s=window.seconds,
               compiles_in_window=compiles.n - run["setup_compiles"],
               errors=window.errors[:3])
    return inputs, window, run


def _read_trace(result, trace_dir, devices, save_trace, reading, manifest,
                name, root, log):
    """Reduce the window's trace and add the cell's per-layer metrics,
    the device's busy and window seconds, and the breakdown to
    ``result``."""
    from shufflebench.trace import Trace

    (xplane,) = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                 for f in fs if f.endswith(".xplane.pb")]
    tr = reading.trace = Trace.from_xplane(xplane, devices=devices)
    if save_trace:
        tr.to_json(save_trace)
    lo, hi = tr.window()
    result["device"].update(busy_s=tr.busy_ns(lo, hi) / 1e9,
                            window_s=(hi - lo) / 1e9)
    for m in mf.metrics_of(manifest, name, "per_layer"):
        value = mf.plugin("metrics", m["name"], root).read(reading)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["breakdown"] = {"device_ops": tr.top_ops(10),
                           "idle_gaps": tr.idle_gaps(10)}
    if reading.peaks:
        for n in sorted(set(reading.jobs)):
            t, bound = least_time_s(reading.least_bytes(n), reading.peaks)
            print(f"# least time of a {n}-record job: {t * 1e3:.4f} ms, "
                  f"bound by {bound}", file=log)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", metavar="PATH",
                    help="also write the reduced trace, the program's "
                    "spans with it (gzip JSON), here")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), save_trace=args.save_trace)
    except NoChip as e:
        print(f"shufflebench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
