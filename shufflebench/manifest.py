"""``BENCHMARK.json`` and the files it names, found by name.

Everything that belongs to one configuration, traffic mix, job kind or
per-layer metric lives in a file of its own under ``shufflebench/``:

    configs/<config>.json    the deployment (its ``file`` in the manifest),
                             and, under ``context``, the shuffle context
                             its cells run in
    traffic/<traffic>.json   the mix: loop kind and records per job
    jobs/<job>.py            inputs, API call, reference, bytes
    metrics/<metric>.py      ``read(r)``: one per-layer metric

so a later PR adds a cell, a configuration or a metric as new files and
new entries, and edits no file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from types import ModuleType
from typing import List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "shufflebench"

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# conf keys a configuration's ``context`` may state, and their types
CONTEXT_CONF = {"readPlane": str, "serializer": str,
                "deviceExchangeEnabled": bool,
                "deviceExchangeWindowRounds": int, "exchangeTileBytes": int}
READ_PLANES = ("host", "windowed", "bulk")
SERIALIZERS = ("pickle", "columnar")


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(manifest: dict, name: str) -> dict:
    return _by_name(manifest["workloads"], name, "workload")


def config(manifest: dict, name: str, root: str = ROOT) -> dict:
    entry = _by_name(manifest["configs"], name, "config")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def traffic(name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, PACKAGE, "traffic", name + ".json")) as f:
        return json.load(f)


def plugin(kind: str, name: str, root: str = ROOT) -> ModuleType:
    """Load ``shufflebench/<kind>/<name>.py`` by its path."""
    path = os.path.join(root, PACKAGE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"{PACKAGE}_{kind}_{name}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reports(metric: dict, cell: str, manifest: dict) -> bool:
    """Whether ``cell`` reports ``metric``: the cells it names, or,
    without a ``workloads`` key, every cell that reports the
    end-to-end metric it moves (or, for an end-to-end metric, every
    cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        moved = _by_name(manifest["end_to_end"], metric["moves"], "metric")
        return reports(moved, cell, manifest)
    return True


def metrics_of(manifest: dict, cell: str, section: str) -> List[dict]:
    """The metrics of ``section`` (``end_to_end`` or ``per_layer``)
    that ``cell`` reports."""
    return [m for m in manifest[section] if reports(m, cell, manifest)]


def context_problems(name: str, spec) -> List[str]:
    """What in configuration ``name``'s ``context``, a mapping of conf
    keys without the ``spark.shuffle.tpu.`` prefix, the run would
    misread or the program would refuse.  Only the deployment keys in
    ``CONTEXT_CONF`` are taken: a path, a trace or metrics switch, or
    an integrity switch would make runs meet on the host, put work in
    the window or change a guarantee."""
    if not isinstance(spec, dict):
        return [f"{name}: context is not an object"]
    out = []
    for k, v in spec.items():
        if k not in CONTEXT_CONF:
            out.append(f"{name}: context key {k!r} is not one of "
                       f"{sorted(CONTEXT_CONF)}")
        elif type(v) is not CONTEXT_CONF[k]:
            out.append(f"{name}: context {k} is {v!r}")
    for k, allowed in (("readPlane", READ_PLANES),
                       ("serializer", SERIALIZERS)):
        if isinstance(spec.get(k), str) and spec[k] not in allowed:
            out.append(f"{name}: {k} {spec[k]!r}, not one of {allowed}")
    return out


def problems(manifest: dict, root: str = ROOT) -> List[str]:
    """What in the manifest breaks the benchmark's contract, as far as
    the files can show it without a run."""
    out = []
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    for group in (manifest["configs"], manifest["workloads"], metrics):
        names = [e["name"] for e in group]
        if len(set(names)) != len(names):
            out.append(f"duplicate names in {names}")
        out += [f"bad name {n!r}" for n in names if not NAME.match(n)]
    for m in metrics:
        if not UNIT.match(m["unit"]):
            out.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            out.append(f"{m['name']}: better is {m['better']!r}")
        if m["source"] not in SOURCES:
            out.append(f"{m['name']}: source {m['source']!r}")
        for w in m.get("workloads", []):
            if w not in cells:
                out.append(f"{m['name']}: unknown workload {w!r}")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        out.append("no setup_s")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"{m['name']}: end-to-end source {m['source']!r}")
        if not 0.01 <= m["bound"] <= 0.25:
            out.append(f"{m['name']}: bound {m['bound']} outside [0.01, 0.25]")
    # the contract lets a configuration and traffic pair appear once,
    # whatever the chips: a cell on a mesh names a mix of its own
    pairs = set()
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            out.append(f"{w['name']}: unknown config {w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"{w['name']}: config and traffic pair repeats")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            out.append(f"{w['name']}: chips {w['chips']}")
        if not os.path.exists(os.path.join(root, PACKAGE, "traffic",
                                           w["traffic"] + ".json")):
            out.append(f"{w['name']}: no traffic file {w['traffic']!r}")
        for section in ("end_to_end", "per_layer"):
            if not any(m["name"] != "setup_s"
                       for m in metrics_of(manifest, w["name"], section)):
                out.append(f"{w['name']}: reports no {section} metric")
    for m in manifest["per_layer"]:
        if m["moves"] not in e2e:
            out.append(f"{m['name']}: moves unknown {m['moves']!r}")
            continue
        for w in cells:
            if reports(m, w, manifest) and not reports(e2e[m["moves"]], w,
                                                       manifest):
                out.append(f"{m['name']}: {w} does not report "
                           f"{m['moves']}")
        if not os.path.exists(os.path.join(root, PACKAGE, "metrics",
                                           m["name"] + ".py")):
            out.append(f"{m['name']}: no reader file")
    for c in manifest["configs"]:
        path = os.path.join(root, c["file"])
        if not os.path.exists(path):
            out.append(f"{c['name']}: no file {c['file']}")
        else:
            with open(path) as f:
                spec = json.load(f).get("context")
            if spec is not None:
                out += context_problems(c["name"], spec)
        if c["name"] not in {w["config"] for w in cells.values()}:
            out.append(f"{c['name']}: used by no cell")
    four = sum(w["chips"] == 4 for w in cells.values())
    if four > max(1, len(cells) // 2):
        out.append(f"{four} of {len(cells)} cells ask for four chips")
    return out
