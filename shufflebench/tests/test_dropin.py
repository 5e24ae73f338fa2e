"""A later PR adds a configuration, a cell and a per-layer metric as new
files and new entries, and edits no file that is there."""

import io
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT, dropin_record_cell
from shufflebench import manifest as mf, run
from shufflebench.trace import Trace
from test_program_spans import DRIVER, check_recorded, printed, readings

# a program_span reader of the record plane: the bulk exchange's host
# time a job
EXCHANGE_READER = '''
from shufflebench import program_spans


def read(r):
    per_job = program_spans.of(r).per_job_ns(("shuffle.bulk.exchange",))
    if not any(per_job):
        return None
    return sum(per_job) / len(per_job) / 1e6
'''


def test_a_config_cell_and_metric_dropped_in_as_files(tiny_root):
    sb = os.path.join(tiny_root, "shufflebench")
    with open(os.path.join(sb, "configs", "hibench_terasort.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "terasort_fat_rows"
    cfg["payload_words"], cfg["bytes_per_record"] = 56, 228
    with open(os.path.join(sb, "configs", "terasort_fat_rows.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(sb, "traffic", "small_jobs.json"), "w") as f:
        json.dump({"loop": "closed", "records_per_job": 4096}, f)
    with open(os.path.join(sb, "metrics", "jobs_traced.py"), "w") as f:
        f.write("def read(r):\n    return float(len(r.trace.jobs())) "
                "or None\n")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "terasort_fat_rows",
                         "source": cfg["source"],
                         "file": "shufflebench/configs/terasort_fat_rows.json",
                         "reduced": ["key_bytes"], "why": "wider rows"})
    m["workloads"].append({"name": "fat.small_jobs",
                           "config": "terasort_fat_rows",
                           "traffic": "small_jobs", "chips": 1,
                           "why": "small jobs back to back"})
    m["per_layer"].append({"name": "jobs_traced", "unit": "jobs",
                           "better": "higher", "source": "host_clock",
                           "layer": "api: the user's call",
                           "moves": "shuffle_gb_s_chip",
                           "workloads": ["fat.small_jobs"]})
    with open(path, "w") as f:
        json.dump(m, f)
    assert mf.problems(mf.load(tiny_root), tiny_root) == []

    r = run.run_cell("fat.small_jobs", 5, 0.3, True, root=tiny_root,
                     require_tpu=False, log=io.StringIO())
    assert r["correct"], r["checks"]
    assert r["metrics"]["jobs_traced"]["value"] >= 1
    assert r["run"]["compiles_in_window"] == 0
    r = run.run_cell("fat.small_jobs", 5, 0.3, False, root=tiny_root,
                     require_tpu=False, log=io.StringIO())
    assert r["correct"] and r["attempted"] >= 2


def _dropin_exchange_cell(root, rows=8192):
    """A one-chip record-plane cell (bulk, columnar, the device exchange
    on) and a ``program_span`` reader that only it reports, dropped into
    the checkout ``root`` as files and entries only."""
    cell = dropin_record_cell(root, 1, rows, deviceExchangeEnabled=True)
    sb = os.path.join(root, "shufflebench")
    with open(os.path.join(sb, "metrics", "dropin_exchange_ms.py"),
              "w") as f:
        f.write(EXCHANGE_READER)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["per_layer"].append({"name": "dropin_exchange_ms", "unit": "ms",
                           "better": "lower", "source": "program_span",
                           "layer": "record plane: bulk exchange",
                           "moves": "shuffle_gb_s_chip",
                           "workloads": [cell]})
    with open(path, "w") as f:
        json.dump(m, f)
    assert mf.problems(mf.load(root), root) == []
    return cell


def test_a_record_plane_cell_dropped_in_with_its_saved_trace(tiny_root,
                                                              tmp_path):
    cell = _dropin_exchange_cell(tiny_root)
    saved = str(tmp_path / f"{cell}.spans.trace.json.gz")
    r = run.run_cell(cell, 2**31 + 13, 0.3, True, root=tiny_root,
                     require_tpu=False, save_trace=saved, log=io.StringIO())
    assert r["correct"], r["checks"]
    assert r["metrics"]["dropin_exchange_ms"]["value"] > 0
    assert not set(DRIVER[:3]) & set(r["metrics"])
    t = Trace.from_json(saved)
    assert "shuffle.bulk.exchange" in {n for n, _, _, _ in t.program}
    check_recorded(tiny_root, cell, saved)
    again = readings(tiny_root, cell, t, kind=None)
    assert {k: v for k, v in again.items() if v is not None} == printed(r)


# the same cell at 3.2M rows a job, traced on one v5e chip with
# --save-trace, and what that run printed
PROBE = os.path.join(os.path.dirname(__file__), "data", "dropin",
                     "dropin.bulk1.spans.trace.json.gz")
PROBE_PRINTED = {"device_idle_pct": 94.9946850680196,
                 "step_device_ms": 192.9567647142857,
                 "step_roofline": 0.40498231953560887,
                 "dropin_exchange_ms": 245.37415992857143}


def test_the_record_plane_chip_trace_passes_its_cells_check(tiny_root):
    cell = _dropin_exchange_cell(tiny_root, rows=3_200_000)
    got = check_recorded(tiny_root, cell, PROBE)
    assert got == {k: pytest.approx(v, rel=1e-12)
                   for k, v in PROBE_PRINTED.items()}
    t = Trace.from_json(PROBE)
    assert {n for n, _, _, _ in t.program} == {
        "shuffle.write.commit", "shuffle.windowed.plan_wait",
        "shuffle.windowed.stream_build", "shuffle.bulk.exchange"}


def test_a_new_cells_trace_in_the_data_directory_passes_its_tests(
        tmp_path):
    """The next cell brings its own ``tests/data/<cell>.spans.trace.json.gz``
    as a new file: the recorded-trace tests, as they stand, run on a
    checkout that holds it beside the three cells' traces."""
    shutil.copytree(os.path.join(ROOT, "shufflebench"),
                    tmp_path / "shufflebench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    cell = _dropin_exchange_cell(str(tmp_path), rows=3_200_000)
    tests = tmp_path / "shufflebench" / "tests"
    shutil.copy(PROBE, tests / "data" / f"{cell}.spans.trace.json.gz")
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         str(tests / "test_program_spans.py"), str(tests / "test_trace.py")],
        cwd=str(tmp_path), env=dict(os.environ, PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-4000:]
    for test in ("test_program_spans.py::"
                 "test_driver_readers_on_the_recorded_chip_traces",
                 "test_trace.py::test_recorded_chip_trace"):
        assert f"{test}[{cell}.spans.trace.json.gz] PASSED" in p.stdout
