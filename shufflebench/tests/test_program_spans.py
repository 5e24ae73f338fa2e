"""The program's host spans (``shuffle.device.*``) of a traced window
(``program_spans.py``), and the readers of the api / host driver layer:
on a synthetic trace worked by hand, on a trace the CPU records here,
and on chip traces recorded with the spans (``data/*.spans.*``).  The
device-trace readers read the traces recorded before the spans existed
exactly as they did then."""

import glob
import io
import os
import tempfile

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


def read(metric, trace, cell="terasort.4chip", n=32_000_000, program=()):
    """What ``metric`` reads on ``trace``, with the program's spans
    ``program`` (a :class:`program_spans.Program` or its spans)."""
    m = mf.load(ROOT)
    w = mf.workload(m, cell)
    config = mf.config(m, w["config"], ROOT)
    r = Reading(trace, [n] * len(trace.jobs()),
                mf.plugin("jobs", config["job"], ROOT), config, w["chips"],
                peaks_for("TPU v5 lite"))
    r.program = (program if isinstance(program, program_spans.Program)
                 else program_spans.Program(trace, program))
    return mf.plugin("metrics", metric, ROOT).read(r)


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
    return Trace(ops, spans), program


def test_driver_readers_on_the_hand_trace(hand):
    # place: job 1 30 + 40 and the sync's 10 before sort.1; job 2
    # (pad) 30 + 15 and 5 + 5 before the fusions; fetch 40, 45;
    # stitch 25, 25 (ns)
    trace, program = hand
    want = {"driver_place_ms": 67.5e-6, "driver_fetch_ms": 42.5e-6,
            "driver_stitch_ms": 25e-6, "fetch_ratio": 430 / 200,
            "overflow_retries": 0.5}
    for metric, value in want.items():
        assert read(metric, trace, program=program) == pytest.approx(value)
    # the device readers see what they saw before the spans
    assert read("step_device_ms", trace, program=program) == pytest.approx(
        (120 + 75 + 20) / 2 / 1e6)


def test_driver_readers_are_silent_without_program_spans(hand):
    trace, _ = hand
    assert all(read(m, trace) is None for m in DRIVER)


def _record(tmp, spans):
    """A traced window on the CPU, as ``run.py`` records it: two jobs,
    each opening the program's ``spans`` through the program's tracer,
    into a ``shufflebench-*`` directory of ``tmp``.  Returns the
    reduced trace."""
    import jax

    from sparkrdma_tpu.utils.trace import Tracer

    tracer = Tracer(enabled=False)
    d = tempfile.mkdtemp(prefix="shufflebench-", dir=tmp)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("job"):
                for name, args in spans:
                    with tracer.span(name, **args) as sp:
                        sp.set(result_bytes=8)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    return Trace.from_xplane(path)


def test_a_run_finds_the_program_spans_of_its_own_window(tmp_path,
                                                         monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    mine = _record(str(tmp_path), [("shuffle.device.fetch", {"bytes": 24})])
    # a newer trace of another window, as a run killed before it
    # removed its directory would leave
    _record(str(tmp_path), [("shuffle.device.fetch", {"bytes": 80})])
    r = Reading(mine, [1, 1], None, {}, 1, None)
    got = program_spans.of(r).named("shuffle.device.fetch", *mine.window())
    assert [args for _, _, args in got] == [
        {"bytes": 24, "result_bytes": 8}] * 2
    assert program_spans.of(r) is r.program
    assert mf.plugin("metrics", "fetch_ratio", ROOT).read(r) == 3.0
    # a window whose trace is gone has no program spans
    other = Trace({}, [("window", 1, 2)])
    assert program_spans.of(Reading(other, [], None, {}, 1, None)).spans == []


def test_a_traced_run_reports_the_host_driver_layer(tiny_root, monkeypatch,
                                                    tmp_path):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    r = run.run_cell("wordcount.share", 2**31 + 7, 0.3, True, root=tiny_root,
                     require_tpu=False, age_s=lambda: 0.0, log=io.StringIO())
    assert r["correct"]
    got = {m: r["metrics"][m]["value"] for m in DRIVER[:4]}
    assert all(v > 0 for v in got.values()), got
    # the run removed its trace once the readers had read it
    assert not glob.glob(str(tmp_path / "shufflebench-*"))


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
    assert program.spans == []
    got = read(metric, t, cell, n, program)
    assert got == (None if want[metric] is None
                   else pytest.approx(want[metric], rel=1e-12))


SPANS = sorted(glob.glob(os.path.join(DATA, "*.spans.trace.json.gz")))


def test_a_spans_trace_is_recorded_for_every_cell():
    cells = [w["name"] for w in mf.load(ROOT)["workloads"]]
    assert sorted(os.path.basename(p).split(".spans")[0]
                  for p in SPANS) == sorted(cells)


@pytest.mark.parametrize("path", SPANS, ids=os.path.basename)
def test_driver_readers_on_the_recorded_chip_traces(path):
    cell = os.path.basename(path).split(".spans")[0]
    m = mf.load(ROOT)
    n = mf.traffic(mf.workload(m, cell)["traffic"], ROOT)["records_per_job"]
    t, program = program_spans.load(path)
    got = {metric: read(metric, t, cell, n, program)
           for metric in DRIVER + DEVICE}
    assert all(got[x] > 0 for x in DRIVER[:3] + ("step_device_ms",))
    # the host driver's phases and the step account for the job
    jobs = t.jobs()
    mean_job_ms = sum(b - a for a, b in jobs) / len(jobs) / 1e6
    parts = sum(got[x] for x in DRIVER[:3] + ("step_device_ms",))
    assert 0.85 <= parts / mean_job_ms <= 1.02
    if cell.startswith("terasort"):
        assert got["fetch_ratio"] == pytest.approx(1.30, abs=0.01)
    else:  # 3 int32 columns of 2^26 slots for 1,000 (id, total) pairs
        assert got["fetch_ratio"] == pytest.approx(100_663, rel=0.01)
    assert got["overflow_retries"] == 0
