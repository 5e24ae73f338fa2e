"""The harness's own tests run on the CPU with four virtual devices, set
before JAX is imported; run them with
``python -m pytest shufflebench/tests``."""

import json
import os
import shutil
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from shufflebench import manifest as mf  # noqa: E402

# job sizes a test run holds, by job kind; WordCount's is off the
# compile ladder, as the cell's 64M is
TINY = {"rows": 1 << 17, "words": 100_000}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout whose BENCHMARK.json runs every cell at a tiny size:
    the package copied, each cell's traffic pointed at a small copy of
    its mix."""
    shutil.copytree(os.path.join(ROOT, "shufflebench"),
                    tmp_path / "shufflebench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    traffic = tmp_path / "shufflebench" / "traffic"
    for cell in manifest["workloads"]:
        with open(traffic / f"{cell['traffic']}.json") as f:
            mix = json.load(f)
        job = "words" if cell["config"] == "hibench_wordcount" else "rows"
        mix["records_per_job"] = TINY[job]
        cell["traffic"] = f"tiny_{job}_{cell['traffic']}"
        with open(traffic / f"{cell['traffic']}.json", "w") as f:
            json.dump(mix, f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    return str(tmp_path)


# TeraSort's rows through the shuffle manager: the 96-byte payload as
# one void column beside the key, sorted by the record plane
RECORD_JOB = '''
import os

import numpy as np

from shufflebench import manifest as mf

_ts = mf.plugin("jobs", "terasort",
                os.path.dirname(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))))
LIMITS = _ts.LIMITS
make_inputs, reference, compare = _ts.make_inputs, _ts.reference, _ts.compare
least_bytes = _ts.least_bytes


def run(ctx, inputs, n, mesh):
    words = inputs["payload"].shape[1]
    rows = inputs["payload"][:n].view(f"V{4 * words}").reshape(n)
    out = ctx.parallelize_columns(inputs["keys"][:n], rows).sort_by_key()
    recs = out.collect()
    keys = np.fromiter((k for k, _ in recs), np.int32, len(recs))
    payload = np.frombuffer(b"".join([v for _, v in recs]), np.int32)
    return keys, payload.reshape(len(recs), words)
'''


def dropin_record_cell(root: str, chips: int, rows: int = 8192,
                       **conf) -> str:
    """Drop into the checkout ``root`` a configuration that states a
    bulk, columnar context (with the conf keys ``conf`` beside), a
    record-plane job, a mix of ``rows`` rows a job and a cell, as new
    files and entries only, as a later PR would.  Every name is the
    test's own (``dropin``).  Returns the cell's name."""
    sb = os.path.join(root, "shufflebench")
    with open(os.path.join(sb, "configs", "hibench_terasort.json")) as f:
        cfg = json.load(f)
    cfg.update(name="dropin_records", job="dropin_record_sort",
               context={"readPlane": "bulk", "serializer": "columnar",
                        **conf})
    with open(os.path.join(sb, "configs", "dropin_records.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(sb, "jobs", "dropin_record_sort.py"), "w") as f:
        f.write(RECORD_JOB)
    with open(os.path.join(sb, "traffic", "dropin_records.json"), "w") as f:
        json.dump({"loop": "closed", "records_per_job": rows}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "dropin_records",
                         "source": cfg["source"],
                         "file": "shufflebench/configs/dropin_records.json",
                         "reduced": ["key_bytes"], "why": "the record plane"})
    name = f"dropin.bulk{chips}"
    m["workloads"].append({"name": name, "config": "dropin_records",
                           "traffic": "dropin_records", "chips": chips,
                           "why": "sortByKey through the shuffle manager"})
    with open(path, "w") as f:
        json.dump(m, f)
    assert mf.problems(mf.load(root), root) == []
    return name
