"""Tier-1 wrapper for the style gate (tools/lint.py) + unit coverage
for the PY08 rule (no ``time.perf_counter()`` in library code outside
metrics/ and utils/trace.py — metric timing flows through the
registry)."""

import importlib.util
import pathlib

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "sparkrdma_tpu_lint", REPO / "tools" / "lint.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_repo_is_lint_clean():
    lint = _load_lint()
    findings = []
    for f in lint.py_files():
        lint.lint_python(f, findings)
    for f in lint.cc_files():
        lint.lint_cpp(f, findings)
    assert not findings, "\n".join(
        f"{rel}:{line}: {code} {msg}" for rel, line, code, msg in findings
    )


def test_py08_flags_perf_counter_in_library_code(tmp_path):
    lint = _load_lint()
    lib = tmp_path / "sparkrdma_tpu"
    (lib / "metrics").mkdir(parents=True)
    (lib / "utils").mkdir()

    bad_attr = lib / "hot.py"
    bad_attr.write_text("import time\nT0 = time.perf_counter()\n")
    bad_name = lib / "hot2.py"
    bad_name.write_text(
        "from time import perf_counter\nT0 = perf_counter()\n"
    )
    ok_metrics = lib / "metrics" / "registry.py"
    ok_metrics.write_text("import time\nT0 = time.perf_counter()\n")
    ok_trace = lib / "utils" / "trace.py"
    ok_trace.write_text("import time\nT0 = time.perf_counter()\n")

    findings = []
    for f in (bad_attr, bad_name, ok_metrics, ok_trace):
        lint.lint_python(f, findings, root=tmp_path)
    py08 = [str(rel) for rel, _l, code, _m in findings if code == "PY08"]
    assert sorted(py08) == [
        "sparkrdma_tpu/hot.py", "sparkrdma_tpu/hot2.py",
    ], findings
    # nothing else should fire on these files
    assert all(code == "PY08" for _r, _l, code, _m in findings), findings


def test_py08_ignores_non_library_code(tmp_path):
    lint = _load_lint()
    (tmp_path / "shufflebench").mkdir()
    bench = tmp_path / "shufflebench" / "b.py"
    bench.write_text("import time\nT0 = time.perf_counter()\n")
    findings = []
    lint.lint_python(bench, findings, root=tmp_path)
    assert not [f for f in findings if f[2] == "PY08"], findings


def test_py09_flags_hot_path_materialization(tmp_path):
    """.tobytes() / b"".join in the exchange hot paths regress the
    zero-copy data path; PY09 pins them out (noqa escapes)."""
    lint = _load_lint()
    lib = tmp_path / "sparkrdma_tpu"
    (lib / "parallel").mkdir(parents=True)
    (lib / "shuffle").mkdir()

    hot = lib / "parallel" / "exchange.py"
    hot.write_text(
        "def f(a, parts):\n"
        "    x = a.tobytes()\n"
        '    y = b"".join(parts)\n'
        "    z = a.tobytes()  # noqa\n"
        "    return x, y, z\n"
    )
    hot2 = lib / "shuffle" / "bulk.py"
    hot2.write_text("def g(a):\n    return a.tobytes()\n")
    cold = lib / "shuffle" / "writer.py"
    cold.write_text(
        'def h(a, parts):\n    return a.tobytes(), b"".join(parts)\n'
    )

    findings = []
    for f in (hot, hot2, cold):
        lint.lint_python(f, findings, root=tmp_path)
    py09 = sorted(
        (str(rel), line) for rel, line, code, _m in findings
        if code == "PY09"
    )
    assert py09 == [
        ("sparkrdma_tpu/parallel/exchange.py", 2),
        ("sparkrdma_tpu/parallel/exchange.py", 3),
        ("sparkrdma_tpu/shuffle/bulk.py", 2),
    ], findings


def test_py10_flags_tcp_hot_path_concat(tmp_path):
    """sendall(a + b)-style payload concatenation and per-frame bytes()
    materialization regress the scatter-gather TCP data path; PY10 pins
    them out of transport/tcp.py (noqa escapes)."""
    lint = _load_lint()
    lib = tmp_path / "sparkrdma_tpu"
    (lib / "transport").mkdir(parents=True)

    hot = lib / "transport" / "tcp.py"
    hot.write_text(
        "class C:\n"
        "    def _send_msg(self, opcode, payload):\n"
        "        self._sock.sendall(HDR.pack(opcode) + payload)\n"
        '        self._sock.sendall(b"".join(parts))\n'
        "    def _serve_read(self, payload):\n"
        "        body = bytes(payload)\n"
        "        deliberate = bytes(payload)  # noqa\n"
        "    def _post_read(self, locations, listener):\n"
        "        cold = bytes(locations)\n"
        "        self._sock.sendall(cold)\n"
    )
    cold = lib / "transport" / "loopback.py"
    cold.write_text(
        "def f(sock, a, b):\n"
        "    sock.sendall(a + b)\n"
        "    return bytes(a)\n"
    )

    findings = []
    for f in (hot, cold):
        lint.lint_python(f, findings, root=tmp_path)
    py10 = sorted(
        (str(rel), line) for rel, line, code, _m in findings
        if code == "PY10"
    )
    # line 3: sendall concat; line 4: sendall join; line 6: bytes() in
    # a hot function.  NOT flagged: the noqa'd bytes() (7), bytes()/
    # sendall of a plain name in a non-hot function (9-10), and
    # anything outside transport/tcp.py.
    assert py10 == [
        ("sparkrdma_tpu/transport/tcp.py", 3),
        ("sparkrdma_tpu/transport/tcp.py", 4),
        ("sparkrdma_tpu/transport/tcp.py", 6),
    ], findings


def test_py13_flags_device_hot_path_host_copies(tmp_path):
    """np.asarray() / jax.device_get() / .tobytes() inside the
    device-exchange hot functions pull the padded payload back to
    host; PY13 pins them out (same-line noqa escapes for
    plan-metadata reads)."""
    lint = _load_lint()
    lib = tmp_path / "sparkrdma_tpu"
    (lib / "parallel").mkdir(parents=True)
    (lib / "memory").mkdir()

    hot = lib / "parallel" / "exchange.py"
    hot.write_text(
        "class TileExchange:\n"
        "    def exchange_padded(self, lengths, src_rows):\n"
        "        meta = np.asarray(lengths)  # noqa: PY13\n"
        "        mat = np.asarray(src_rows)\n"
        "        host = jax.device_get(src_rows)\n"
        "    def exchange_meta(self, lengths):\n"
        "        return np.asarray(lengths)\n"
    )
    hot2 = lib / "memory" / "device_arena.py"
    hot2.write_text(
        "def to_device(rows):\n"
        "    return rows.tobytes()\n"
        "def describe(rows):\n"
        "    return rows.tobytes()\n"
    )

    findings = []
    for f in (hot, hot2):
        lint.lint_python(f, findings, root=tmp_path)
    py13 = sorted(
        (str(rel), line) for rel, line, code, _m in findings
        if code == "PY13"
    )
    # Flagged: the bare np.asarray (4) and jax.device_get (5) inside
    # exchange_padded, and .tobytes() inside to_device (2).  NOT
    # flagged: the noqa'd metadata read (3), or the same calls in
    # functions outside DEVICE_HOT_FUNCS (exchange_meta, describe).
    assert py13 == [
        ("sparkrdma_tpu/memory/device_arena.py", 2),
        ("sparkrdma_tpu/parallel/exchange.py", 4),
        ("sparkrdma_tpu/parallel/exchange.py", 5),
    ], findings


def test_noqa_is_code_scoped(tmp_path):
    """# noqa: PYxx suppresses only PYxx; a scoped escape for one rule
    can no longer blanket-silence an unrelated hot-path rule."""
    lint = _load_lint()
    lib = tmp_path / "sparkrdma_tpu"
    (lib / "transport").mkdir(parents=True)
    hot = lib / "transport" / "tcp.py"
    hot.write_text(
        "class C:\n"
        "    def _send_msg(self, a, b):\n"
        "        self._sock.sendall(a + b)  # noqa: PY05\n"
        "        self._sock.sendall(a + b)  # noqa: PY10\n"
        "        self._sock.sendall(a + b)  # noqa\n"
        "        self._sock.sendall(a + b)  # noqa: PY02, PY10\n"
    )
    findings = []
    lint.lint_python(hot, findings, root=tmp_path)
    py10 = [line for _r, line, code, _m in findings if code == "PY10"]
    # only line 3 survives: its escape names an unrelated code
    assert py10 == [3], findings


def test_py05_noqa_on_multiline_from_import(tmp_path):
    """The escape is honored on the imported name's OWN line inside a
    multi-line from-import, and on the statement's first line."""
    lint = _load_lint()
    (tmp_path / "tools").mkdir()
    f = tmp_path / "tools" / "a.py"
    f.write_text(
        "from os import (\n"
        "    getcwd,\n"
        "    sep,  # noqa: PY05\n"
        ")\n"
        "from sys import (  # noqa: PY05\n"
        "    argv,\n"
        "    path,\n"
        ")\n"
    )
    findings = []
    lint.lint_python(f, findings, root=tmp_path)
    py05 = [(line, msg) for _r, line, code, msg in findings
            if code == "PY05"]
    # getcwd (line 2) flags at its own line; sep escaped on its line;
    # argv/path escaped by the statement-line noqa
    assert py05 == [(2, "unused import: getcwd")], findings


def test_py05_f401_alias_and_ast_usage(tmp_path):
    """F401 (the flake8 code) suppresses PY05; string annotations and
    __all__ exports count as real uses."""
    lint = _load_lint()
    (tmp_path / "tools").mkdir()
    f = tmp_path / "tools" / "b.py"
    f.write_text(
        "import json  # noqa: F401\n"
        "import os\n"
        "import struct\n"
        "import sys\n"
        "__all__ = [\"os\"]\n"
        "def g(x: \"struct.Struct\") -> None:\n"
        "    return None\n"
    )
    findings = []
    lint.lint_python(f, findings, root=tmp_path)
    py05 = [msg for _r, _l, code, msg in findings if code == "PY05"]
    # json: F401-aliased escape; os: __all__ export; struct: string
    # annotation; sys: genuinely unused
    assert py05 == ["unused import: sys"], findings


def test_noqa_code_followed_by_justification_prose(tmp_path):
    """The documented escape style '# noqa: CK02 <why>' scopes to the
    leading code token(s); the prose does not widen or break it."""
    lint = _load_lint()
    assert lint._noqa_codes("x()  # noqa: PY10 frame serialization") \
        == {"PY10"}
    assert lint._noqa_codes("x()  # noqa: CK02, CK03 deliberate") \
        == {"CK02", "CK03"}
    assert lint._noqa_codes("x()  # noqa") == set()
    assert lint._noqa_codes("x()") is None
    lib = tmp_path / "sparkrdma_tpu"
    (lib / "transport").mkdir(parents=True)
    hot = lib / "transport" / "tcp.py"
    hot.write_text(
        "class C:\n"
        "    def _send_msg(self, a, b):\n"
        "        self._sock.sendall(a + b)  # noqa: PY10 serialized\n"
        "        self._sock.sendall(a + b)  # noqa: PY05 wrong code\n"
    )
    findings = []
    lint.lint_python(hot, findings, root=tmp_path)
    py10 = [line for _r, line, code, _m in findings if code == "PY10"]
    assert py10 == [4], findings


def _py11_root(tmp_path, readme: str):
    """A fake repo root: conf.py declaring two keys (one via a legacy
    rdma rename), one library file referencing keys, and a README."""
    lib = tmp_path / "sparkrdma_tpu"
    lib.mkdir()
    (lib / "conf.py").write_text(
        'LEGACY_RENAMES = {"useOdp": "lazyStaging"}\n\n\n'
        "class Conf:\n"
        "    def a(self):\n"
        '        self.get("tierHotBytes")\n'
        '        return self._bool("lazyStaging", False)\n'
    )
    (tmp_path / "README.md").write_text(readme)
    return lib


def test_py11_flags_undeclared_key_reference(tmp_path):
    lint = _load_lint()
    lib = _py11_root(tmp_path, "`tierHotBytes` and `lazyStaging`\n")
    (lib / "mod.py").write_text(
        '"""Knobs: spark.shuffle.tpu.tierHotBytes is declared,\n'
        "spark.shuffle.rdma.useOdp renames onto a declared key, but\n"
        'spark.shuffle.tpu.ghostKnob is drift."""\n'
    )
    findings = []
    lint.lint_conf_keys(findings, root=tmp_path)
    assert [(str(r), line, code) for r, line, code, _m in findings] == [
        ("sparkrdma_tpu/mod.py", 3, "PY11")
    ], findings
    assert "ghostKnob" in findings[0][3]


def test_py11_noqa_suppresses_reference_finding(tmp_path):
    lint = _load_lint()
    lib = _py11_root(tmp_path, "`tierHotBytes` and `lazyStaging`\n")
    (lib / "mod.py").write_text(
        "# spark.shuffle.tpu.ghostKnob  # noqa: PY11 - doc of a removed key\n"
    )
    findings = []
    lint.lint_conf_keys(findings, root=tmp_path)
    assert findings == []


def test_py11_flags_undocumented_declared_key(tmp_path):
    lint = _load_lint()
    # README documents tierHotBytes only: lazyStaging goes undocumented
    _py11_root(tmp_path, "| `tierHotBytes` | 64m |\n")
    findings = []
    lint.lint_conf_keys(findings, root=tmp_path)
    assert len(findings) == 1, findings
    rel, _line, code, msg = findings[0]
    assert code == "PY11" and "lazyStaging" in msg
    assert str(rel) == "README.md"


def test_py11_full_dotted_key_documents_too(tmp_path):
    lint = _load_lint()
    _py11_root(
        tmp_path,
        "spark.shuffle.tpu.tierHotBytes and spark.shuffle.tpu.lazyStaging\n",
    )
    findings = []
    lint.lint_conf_keys(findings, root=tmp_path)
    assert findings == []
