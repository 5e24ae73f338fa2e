"""Spans of ``utils/trace.py`` in a ``jax.profiler`` capture, and the
device path's host-driver spans (``shuffle.device.*``) and overflow
counter."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from sparkrdma_tpu.api import TpuShuffleContext
from sparkrdma_tpu.metrics import GLOBAL_REGISTRY
from sparkrdma_tpu.models.terasort import TeraSorter
from sparkrdma_tpu.parallel import make_mesh
from sparkrdma_tpu.utils.trace import Tracer

DEVICE = "shuffle.device."


def profiled(tmp_path, fn):
    """Run ``fn()`` under a profiler session; returns its result and
    the host plane's events whose name starts with ``shuffle.`` or
    ``x``, as (name, start_ns, end_ns, stats), in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = [(e.name, int(e.start_ns), int(e.end_ns), dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith(("shuffle.", "x"))]
    return out, sorted(events, key=lambda e: e[1])


def named(events, name):
    return [e for e in events if e[0] == name]


def test_disabled_tracer_span_reaches_the_profiler(tmp_path):
    tr = Tracer(enabled=False)

    def spans():
        with tr.span("x", a=1) as sp:
            assert sp.recording  # the profiler records it
            sp.set(b=2)

    _, events = profiled(tmp_path, spans)
    assert [(n, s) for n, _, _, s in events] == [("x", {"a": 1, "b": 2})]
    assert tr.events == []  # the Chrome sink stays off


def test_span_outside_a_session_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("x", a=1) as sp:
        assert not sp.recording
        sp.set(b=2)
    assert tr.events == []


def test_set_adds_args_to_both_sinks(tmp_path):
    tr = Tracer(enabled=True)

    def spans():
        with tr.span("x.outer", rows=3):
            with tr.span("x.inner", a=1) as sp:
                sp.set(minflt=12801, overflowed=True)

    _, events = profiled(tmp_path, spans)
    (outer,), (inner,) = named(events, "x.outer"), named(events, "x.inner")
    assert inner[3] == {"a": 1, "minflt": 12801, "overflowed": 1}
    assert outer[1] <= inner[1] and inner[2] <= outer[2]  # nested
    chrome = {e["name"]: e["args"] for e in tr.events}
    assert chrome == {"x.outer": {"rows": 3},
                      "x.inner": {"a": 1, "minflt": 12801,
                                  "overflowed": True}}


def check_job(events, root, phases):
    """One job's spans: the root span once, and each phase span inside
    it as often as ``phases`` says."""
    (top,) = named(events, root)
    for name, times in phases.items():
        got = named(events, DEVICE + name)
        assert len(got) == times, (name, got)
        assert all(top[1] <= a and b <= top[2] for _, a, b, _ in got)
    (attempt,) = named(events, DEVICE + "attempt")
    (sync,) = named(events, DEVICE + "sync")
    assert attempt[1] <= sync[1] and sync[2] <= attempt[2]
    assert attempt[3]["overflowed"] == 0
    assert attempt[3]["max_fill"] <= attempt[3]["capacity"]
    return top, attempt


@pytest.mark.parametrize("d", [1, 4])
def test_device_sort_wide_rows_emit_the_driver_spans(tmp_path, devices, d):
    ctx = TpuShuffleContext(num_executors=1)
    rng = np.random.default_rng(d)
    keys = rng.integers(0, 1 << 30, 4096, dtype=np.int32)
    payload = rng.integers(0, 1 << 30, (4096, 24), dtype=np.int32)
    mesh = make_mesh(d)
    try:
        (sk, sp), events = profiled(
            tmp_path, lambda: ctx.device_sort(keys, payload, mesh=mesh))
    finally:
        ctx.stop()
    np.testing.assert_array_equal(sk, np.sort(keys))
    top, attempt = check_job(events, DEVICE + "sort", {
        "pad": 0, "place": 2, "attempt": 1, "sync": 1, "fetch": 1,
        "stitch": 1})
    assert top[3] == {"rows": 4096}
    keys_place, rows_place = named(events, DEVICE + "place")
    assert keys_place[3]["bytes"] == keys.nbytes
    assert rows_place[3]["bytes"] == payload.nbytes
    assert keys_place[3]["shards"] == d and "minflt" in keys_place[3]
    (fetch,) = named(events, DEVICE + "fetch")
    cap = attempt[3]["capacity"]
    # per device D buckets of ``cap`` rows of a key and 24 words, and
    # one valid count
    assert fetch[3]["bytes"] == d * d * cap * 4 * (1 + 24) + 4 * d
    assert fetch[3]["result_bytes"] == sk.nbytes + sp.nbytes
    assert fetch[3]["shards"] == d
    # one device: the result is a view of the fetched run; several: one
    # copy of the valid rows, a chunk a device and column at this size,
    # on the calling thread
    (stitch,) = named(events, DEVICE + "stitch")
    assert stitch[3]["bytes"] == (0 if d == 1 else sk.nbytes + sp.nbytes)
    assert stitch[3]["chunks"] == (0 if d == 1 else 2 * d)
    assert stitch[3]["workers"] == (0 if d == 1 else 1)
    assert "minflt" in stitch[3]


def test_device_sort_padded_keys_emit_pad_and_place(tmp_path, devices):
    ctx = TpuShuffleContext(num_executors=1)
    keys = np.random.default_rng(5).integers(0, 1 << 30, 4000, np.int32)
    try:
        (sk, sv), events = profiled(
            tmp_path, lambda: ctx.device_sort(keys, keys, mesh=make_mesh(4)))
    finally:
        ctx.stop()
    np.testing.assert_array_equal(sk, np.sort(keys))
    check_job(events, DEVICE + "sort", {
        "pad": 1, "place": 1, "attempt": 1, "sync": 1, "fetch": 1,
        "stitch": 1})
    (pad,), (place,) = named(events, DEVICE + "pad"), named(
        events, DEVICE + "place")
    assert pad[2] <= place[1]
    # keys, values and the validity column, padded to 4096 slots
    assert pad[3]["bytes"] == place[3]["bytes"] == 3 * 4 * 4096
    (fetch,), (stitch,) = named(events, DEVICE + "fetch"), named(
        events, DEVICE + "stitch")
    assert fetch[3]["result_bytes"] == sk.nbytes + sv.nbytes
    assert fetch[3]["shards"] == 4
    assert stitch[3]["bytes"] == sk.nbytes + sv.nbytes
    assert (stitch[3]["chunks"], stitch[3]["workers"]) == (8, 1)
    assert "minflt" in stitch[3]


@pytest.mark.parametrize("d", [1, 4])
def test_device_count_emits_the_driver_spans(tmp_path, devices, d):
    ctx = TpuShuffleContext(num_executors=1)
    words = np.random.default_rng(d).integers(0, 1000, 50_000, np.int32)
    try:
        out, events = profiled(
            tmp_path, lambda: ctx.device_count(words, mesh=make_mesh(d)))
    finally:
        ctx.stop()
    ids, totals = np.unique(words, return_counts=True)
    assert out == dict(zip(ids.tolist(), totals.tolist()))
    top, attempt = check_job(events, DEVICE + "count", {
        "pad": 1, "place": 1, "attempt": 1, "sync": 1, "fetch": 1,
        "stitch": 1})
    assert top[3] == {"rows": 50_000}
    (pad,), (place,) = named(events, DEVICE + "pad"), named(
        events, DEVICE + "place")
    assert pad[3]["bytes"] == place[3]["bytes"]
    (fetch,) = named(events, DEVICE + "fetch")
    # three int32 columns come back, each the padded input on one
    # device or D buckets of ``capacity`` a device, and a count a
    # device; the result is an (id, total) pair of int32 a word
    slots = place[3]["bytes"] // 12 if d == 1 else (
        d * d * attempt[3]["capacity"])
    assert fetch[3]["bytes"] == 3 * 4 * slots + 4 * d
    assert fetch[3]["result_bytes"] == 8 * len(out)
    assert fetch[3]["shards"] == d


def test_forced_overflow_retries_and_ticks_the_counter(tmp_path, devices):
    prev = GLOBAL_REGISTRY.enabled
    GLOBAL_REGISTRY.reset()
    GLOBAL_REGISTRY.enabled = True
    # every key equal: all of a device's rows go to one bucket, which
    # holds a quarter of them at factor 1 (4 devices)
    keys = np.zeros(4096, np.int32)
    payload = np.arange(4096 * 24, dtype=np.int32).reshape(4096, 24)
    sorter = TeraSorter(make_mesh(4), capacity_factor=1.0)
    try:
        (sk, sp), events = profiled(
            tmp_path, lambda: sorter.sort(keys, payload))
        retries = GLOBAL_REGISTRY.counter(
            "device_overflow_retries_total").value
    finally:
        GLOBAL_REGISTRY.enabled = prev
        GLOBAL_REGISTRY.reset()
    assert sorted(map(tuple, sp)) == sorted(map(tuple, payload))
    attempts = named(events, DEVICE + "attempt")
    assert len(attempts) >= 2
    assert [a[3]["overflowed"] for a in attempts] == [1] * (
        len(attempts) - 1) + [0]
    assert [a[3]["factor"] for a in attempts] == [
        2.0 ** i for i in range(len(attempts))]
    assert all(a[3]["max_fill"] > a[3]["capacity"] for a in attempts[:-1])
    assert retries == len(attempts) - 1
    assert len(named(events, DEVICE + "sync")) == len(attempts)
    assert len(named(events, DEVICE + "fetch")) == 1


@pytest.mark.parametrize("name", ["terasort_wide_step", "terasort_step",
                                  "wordcount_step"])
def test_jitted_steps_name_their_module(devices, name):
    """The trace's "XLA Modules" line names the step that ran."""
    from jax import ShapeDtypeStruct as S

    from sparkrdma_tpu.models.terasort import (make_sort_step,
                                               make_wide_sort_step)
    from sparkrdma_tpu.models.wordcount import make_count_step

    mesh, col = make_mesh(4), S((1024,), np.int32)
    lowered = {
        "terasort_wide_step": lambda: make_wide_sort_step(
            mesh, 256, 24, 96).lower(col, S((1024, 24), np.int32)),
        "terasort_step": lambda: make_sort_step(mesh, 256, 96).lower(
            col, col, col),
        "wordcount_step": lambda: make_count_step(mesh, 256, 128).lower(
            col, col, col),
    }[name]()
    assert lowered.as_text().startswith(f"module @jit_{name} ")
