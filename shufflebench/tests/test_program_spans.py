"""The program's host spans (``shuffle.device.*``) of a traced window
(``Trace.program``, ``program_spans.py``), and the readers of the api /
host driver layer: on a synthetic trace worked by hand, on a trace the
CPU records here, and on chip traces recorded with the spans
(``data/*.spans.*``), each checked by the metrics its own cell reports
(:func:`check_recorded`).  The readers read the recorded traces exactly
as they did before the trace kept the spans."""

import glob
import io
import os
import tempfile
import threading

import pytest

from conftest import ROOT
from shufflebench import manifest as mf
from shufflebench import program_spans, run
from shufflebench.peaks import peaks_for
from shufflebench.run import Reading
from shufflebench.trace import Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
DRIVER = ("driver_place_ms", "driver_fetch_ms", "driver_stitch_ms",
          "fetch_ratio", "overflow_retries")
DEVICE = ("device_idle_pct", "step_device_ms", "step_roofline",
          "scan_kernel_ms", "collective_ms")


def read(metric, trace, cell="terasort.4chip", n=32_000_000, root=ROOT,
         kind="TPU v5 lite"):
    """What ``metric`` reads on ``trace`` as a run of ``cell`` in the
    checkout ``root`` on a device of ``kind`` (None: off a TPU) would
    read it, its jobs ``n`` records each."""
    m = mf.load(root)
    w = mf.workload(m, cell)
    config = mf.config(m, w["config"], root)
    r = Reading(trace, [n] * len(trace.jobs()),
                mf.plugin("jobs", config["job"], root), config, w["chips"],
                peaks_for(kind) if kind else None)
    return mf.plugin("metrics", metric, root).read(r)


def readings(root, cell, trace, kind="TPU v5 lite", metrics=None):
    """What each of ``metrics`` (by default every per-layer metric that
    ``cell`` reports) reads on ``trace``."""
    m = mf.load(root)
    n = mf.traffic(mf.workload(m, cell)["traffic"], root)["records_per_job"]
    if metrics is None:
        metrics = [x["name"] for x in mf.metrics_of(m, cell, "per_layer")]
    return {x: read(x, trace, cell, n, root, kind) for x in metrics}


def printed(result):
    return {k: v["value"] for k, v in result["metrics"].items()}


def span(name, a, b, **args):
    return ("shuffle.device." + name, a, b, args)


@pytest.fixture
def hand():
    """One device, a window [0, 1000) and two jobs: a wide sort and a
    count whose first attempt overflowed.  The numbers in the tests
    below are worked by hand from these intervals."""
    ops = {0: [("sort.1", 260, 380), ("fusion", 705, 780),
               ("fusion.1", 815, 835)]}
    spans = [("window", 0, 1000), ("job", 100, 500), ("job", 600, 950)]
    program = [
        span("sort", 110, 490, rows=4),
        span("place", 120, 150, bytes=16, shards=1),
        span("place", 160, 200, bytes=384, shards=1),
        span("attempt", 210, 400, factor=1.3, capacity=8, max_fill=4,
             overflowed=0),
        span("sync", 250, 390),
        span("fetch", 410, 450, bytes=130, result_bytes=100),
        span("stitch", 455, 480),
        span("count", 605, 945, rows=9),
        span("pad", 610, 640, bytes=12),
        span("place", 645, 660, bytes=12, shards=1),
        span("attempt", 665, 800, factor=2.0, capacity=8, max_fill=9,
             overflowed=1),
        span("sync", 700, 790),
        span("attempt", 805, 850, factor=4.0, capacity=16, max_fill=9,
             overflowed=0),
        span("sync", 810, 840),
        span("fetch", 855, 900, bytes=300, result_bytes=100),
        span("stitch", 905, 930),
    ]
    return Trace(ops, spans, program)


def test_driver_readers_on_the_hand_trace(hand):
    # place: job 1 30 + 40 and the sync's 10 before sort.1; job 2
    # (pad) 30 + 15 and 5 + 5 before the fusions; fetch 40, 45;
    # stitch 25, 25 (ns)
    want = {"driver_place_ms": 67.5e-6, "driver_fetch_ms": 42.5e-6,
            "driver_stitch_ms": 25e-6, "fetch_ratio": 430 / 200,
            "overflow_retries": 0.5}
    for metric, value in want.items():
        assert read(metric, hand) == pytest.approx(value)
    # the device readers see what they saw before the spans
    assert read("step_device_ms", hand) == pytest.approx(
        (120 + 75 + 20) / 2 / 1e6)


def test_driver_readers_are_silent_without_program_spans(hand):
    trace = Trace(hand.ops, hand.spans)
    assert trace.program == []
    assert all(read(m, trace) is None for m in DRIVER)


def test_program_spans_and_their_args_round_trip_through_json(hand,
                                                              tmp_path):
    p = str(tmp_path / "t.json.gz")
    hand.to_json(p)
    back, program = program_spans.load(p)
    assert back.program == hand.program
    assert back.ops == hand.ops and back.spans == hand.spans
    assert program.named("shuffle.device.attempt", 600, 950) == [
        (665, 800, {"factor": 2.0, "capacity": 8, "max_fill": 9,
                    "overflowed": 1}),
        (805, 850, {"factor": 4.0, "capacity": 16, "max_fill": 9,
                    "overflowed": 0})]
    for metric in DRIVER + DEVICE:
        assert read(metric, back) == read(metric, hand)


def _record(tmp, spans):
    """A traced window on the CPU, as ``run.py`` records it: two jobs,
    each opening the program's ``spans`` through the program's tracer,
    on its own thread and on a second one.  Returns the reduced
    trace."""
    import jax

    from sparkrdma_tpu.utils.trace import Tracer

    tracer = Tracer(enabled=False)

    def job():
        for name, args in spans:
            with tracer.span(name, **args) as sp:
                sp.set(result_bytes=8)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("job"):
                job()
                other = threading.Thread(target=job)
                other.start()
                other.join(timeout=60)
                assert not other.is_alive()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    return Trace.from_xplane(path)


def test_a_recorded_xplane_keeps_the_program_spans_of_every_thread(
        tmp_path):
    t = _record(str(tmp_path), [("shuffle.device.fetch", {"bytes": 24}),
                                ("other.fetch", {"bytes": 99})])
    assert len(t.jobs()) == 2
    assert [(n, args) for n, _, _, args in t.program] == [
        ("shuffle.device.fetch", {"bytes": 24, "result_bytes": 8})] * 4
    lo, hi = t.jobs()[0]
    assert all(lo <= a <= b <= hi for _, a, b, _ in t.program[:2])
    # the benchmark's spans are as they were without the program's
    assert [n for n, _, _ in t.spans] == ["window", "job", "job"]
    r = Reading(t, [1, 1], None, {}, 1, None)
    assert mf.plugin("metrics", "fetch_ratio", ROOT).read(r) == 3.0
    assert program_spans.of(r).spans is t.program


def test_a_traced_run_reports_the_host_driver_layer(tiny_root, monkeypatch,
                                                    tmp_path):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    saved = str(tmp_path / "wordcount.share.spans.trace.json.gz")
    r = run.run_cell("wordcount.share", 2**31 + 7, 0.3, True, root=tiny_root,
                     require_tpu=False, age_s=lambda: 0.0, save_trace=saved,
                     log=io.StringIO())
    assert r["correct"]
    got = {m: r["metrics"][m]["value"] for m in DRIVER[:4]}
    assert all(v > 0 for v in got.values()), got
    # the run removed its trace once it was reduced
    assert not glob.glob(str(tmp_path / "shufflebench-*"))
    # the saved trace carries the program's spans and reads as printed
    again = readings(tiny_root, "wordcount.share", Trace.from_json(saved),
                     kind=None)
    assert {k: v for k, v in again.items() if v is not None} == printed(r)


# the traces recorded before the spans, and what the device readers
# read on them at the commit that recorded them
BEFORE = {
    "terasort.large.trace.json.gz": ("terasort.large", 32_000_000, {
        "device_idle_pct": 84.83444200799543,
        "step_device_ms": 1066.089606,
        "step_roofline": 0.7329972800061062,
        "scan_kernel_ms": None, "collective_ms": None}),
    "terasort.4chip.64m.trace.json.gz": ("terasort.4chip", 64_000_000, {
        "device_idle_pct": 96.49711270781866,
        "step_device_ms": 1211.09990675,
        "step_roofline": 0.49541742729557847,
        "scan_kernel_ms": None, "collective_ms": 25.2305565}),
    "wordcount.share.trace.json.gz": ("wordcount.share", 32_000_000, {
        "device_idle_pct": 87.18829197660726,
        "step_device_ms": 122.64929781818181,
        "step_roofline": 0.12743483010365372,
        "scan_kernel_ms": 7.039927545454546, "collective_ms": None}),
}


@pytest.mark.parametrize("metric", DEVICE)
@pytest.mark.parametrize("name", sorted(BEFORE))
def test_device_readers_read_the_older_traces_as_before(name, metric):
    cell, n, want = BEFORE[name]
    t, program = program_spans.load(os.path.join(DATA, name))
    assert t.program == program.spans == []
    got = read(metric, t, cell, n)
    assert got == (None if want[metric] is None
                   else pytest.approx(want[metric], rel=1e-12))


SPANS = sorted(glob.glob(os.path.join(DATA, "*.spans.trace.json.gz")))

# what every reader read on the spans traces of the first three cells
# before the trace kept the program's spans (program_spans.py's own
# parse of the .xplane.pb); a later cell's trace is held by
# check_recorded alone
SPANS_BEFORE = {
    "terasort.4chip": {
        "driver_place_ms": 733.605466375, "driver_fetch_ms": 6076.925922,
        "driver_stitch_ms": 3555.713956, "fetch_ratio": 1.300000005,
        "overflow_retries": 0.0, "device_idle_pct": 95.46093821283105,
        "step_device_ms": 505.41515525, "step_roofline": 0.5935714370330014,
        "scan_kernel_ms": None, "collective_ms": 12.813576},
    "terasort.large": {
        "driver_place_ms": 1460.954044, "driver_fetch_ms": 1254.48496125,
        "driver_stitch_ms": 3775.78430325, "fetch_ratio": 1.30000000125,
        "overflow_retries": 0.0, "device_idle_pct": 85.90843954146001,
        "step_device_ms": 1066.11601125, "step_roofline": 0.732979125343552,
        "scan_kernel_ms": None, "collective_ms": None},
    "wordcount.share": {
        "driver_place_ms": 144.80272837037035,
        "driver_fetch_ms": 85.1408653888889,
        "driver_stitch_ms": 50.64822346296296, "fetch_ratio": 100663.2965,
        "overflow_retries": 0.0, "device_idle_pct": 50.59241549698334,
        "step_device_ms": 276.29438296296297,
        "step_roofline": 0.11313515578345379,
        "scan_kernel_ms": 14.067097462962963, "collective_ms": None},
}


@pytest.mark.parametrize("metric", DRIVER + DEVICE)
@pytest.mark.parametrize("cell", sorted(SPANS_BEFORE))
def test_readers_read_the_spans_traces_as_before(cell, metric):
    t = Trace.from_json(os.path.join(DATA, f"{cell}.spans.trace.json.gz"))
    want = SPANS_BEFORE[cell][metric]
    got = readings(ROOT, cell, t, metrics=(metric,))[metric]
    assert got == (None if want is None
                   else pytest.approx(want, rel=1e-12))


def test_a_spans_trace_is_recorded_for_every_cell():
    cells = [w["name"] for w in mf.load(ROOT)["workloads"]]
    assert sorted(os.path.basename(p).split(".spans")[0]
                  for p in SPANS) == sorted(cells)


# what fetch_ratio reads, by the configuration's job: TeraSort fetches
# its 1.3x capacity; WordCount 3 int32 columns of 2^26 slots for 1,000
# (id, total) pairs
FETCH_RATIO = {"terasort": pytest.approx(1.30, abs=0.01),
               "wordcount": pytest.approx(100_663, rel=0.01)}


def check_recorded(root, cell, path):
    """What a trace of ``cell`` in the checkout ``root``, saved at
    ``path``, has to show.  Every per-layer metric the cell reports
    reads a number; a reader of the device trace only where the trace
    has a device plane, which a CPU recording lacks.  Where the cell
    reports the device path's host driver (``driver_place_ms``): its
    phases and the step each take time and together account for a job,
    the fetch copies what the job's kind fetches (where ``FETCH_RATIO``
    knows it), and no bucket overflowed.  Returns the readings."""
    m = mf.load(root)
    t = Trace.from_json(path)
    got = readings(root, cell, t)
    sources = {x["name"]: x["source"] for x in m["per_layer"]}
    silent = [x for x, v in got.items() if v is None
              and (t.devices or sources[x] != "device_trace")]
    assert not silent, got
    if "driver_place_ms" not in got:
        return got
    phases = DRIVER[:3] + ("step_device_ms",)
    drv = {**readings(root, cell, t, metrics=[
        x for x in DRIVER + phases if x not in got]), **got}
    parts = [drv[x] for x in phases]
    assert all(v > 0 for v in parts), drv
    jobs = t.jobs()
    mean_job_ms = sum(b - a for a, b in jobs) / len(jobs) / 1e6
    assert 0.85 <= sum(parts) / mean_job_ms <= 1.02
    w = mf.workload(m, cell)
    job = mf.config(m, w["config"], root)["job"]
    if job in FETCH_RATIO:
        assert drv["fetch_ratio"] == FETCH_RATIO[job]
    assert drv["overflow_retries"] == 0
    return got


@pytest.mark.parametrize("path", SPANS, ids=os.path.basename)
def test_driver_readers_on_the_recorded_chip_traces(path):
    check_recorded(ROOT, os.path.basename(path).split(".spans")[0], path)
