#!/usr/bin/env python
"""Style gate failing the build — the checkstyle/scalastyle analog.

The reference runs checkstyle + scalastyle at Maven's ``validate``
phase with ``failsOnError=true`` (pom.xml:93-141); this is the
same gate for the rebuild, implemented on the stdlib because the
environment ships no third-party linter.  Rules:

Suppressions are CODE-SCOPED: ``# noqa: PY10`` silences only PY10 on
that line (comma-separate several codes); a bare ``# noqa`` still
silences everything, but a scoped escape can never blanket-silence an
unrelated hot-path rule.  ``F401`` is accepted as an alias for PY05
(flake8 compatibility).

Python (sparkrdma_tpu/, tests/, tools/, repo-root *.py):
  PY01  file does not parse (SyntaxError)
  PY02  line longer than 88 characters
  PY03  tab character in indentation
  PY04  trailing whitespace
  PY05  unused import, via AST usage tracking (attribute roots,
        decorators, string annotations, ``__all__`` exports all count
        as uses; skipped in __init__.py re-export files; suppress on
        the import statement line OR — for multi-line
        ``from x import (a, b)`` statements — on the imported name's
        own line)
  PY06  bare ``except:`` (use ``except BaseException:`` when you truly
        mean everything)
  PY07  ``print(`` in library code (sparkrdma_tpu/ only; scripts, tests
        and tools print by design)
  PY08  ``time.perf_counter()`` in library code outside
        sparkrdma_tpu/metrics/ and sparkrdma_tpu/utils/trace.py —
        metric timing must flow through the registry/tracer (use
        ``Histogram.time()`` or ``time.monotonic()`` for plain
        interval math)
  PY09  ``.tobytes()`` call or ``b"".join`` in the exchange hot paths
        (sparkrdma_tpu/parallel/exchange.py, sparkrdma_tpu/shuffle/
        bulk.py) — the zero-copy data path stages into preallocated
        contiguous rows; per-block ``bytes`` materialization there is
        a regression (suppress a deliberate one with ``# noqa``)
  PY10  payload concatenation / materialization on the TCP transport
        hot paths (sparkrdma_tpu/transport/tcp.py): ``sendall(a + b)``
        or ``sendall(b"".join(...))`` anywhere in the file, and
        ``bytes(...)`` calls inside the hot send/serve/receive
        functions — frames go out as sendmsg iovecs and land via
        recv_into; an intermediate copy there is a regression
        (suppress a deliberate one with ``# noqa``)
  PY11  conf-key drift, both directions.  Every full
        ``spark.shuffle.tpu.<key>`` / ``spark.shuffle.rdma.<key>``
        reference in sparkrdma_tpu/ must name a key DECLARED in
        conf.py (a str first argument to ``self.get``/``self.set``/
        ``_int_in_range``/``_bytes_in_range``/``_bool``/``_time_ms``;
        rdma-namespace references resolve through LEGACY_RENAMES
        first).  And every declared key must appear in a README.md
        conf table — as the backticked short key (`` `tierHotBytes` ``)
        or the full dotted key — so no knob ships undocumented.
  PY12  flight-recorder event drift.  Every ``fr_event(plane, event,
        ...)`` call in sparkrdma_tpu/ must pass the plane and event as
        string LITERALS naming an entry declared in the
        ``obs/events.py`` ``EVENTS`` registry — dashboards and
        ``tools/trace_report.py`` group by these names, so a dynamic
        or undeclared name is silent drift.  Declare first, then emit.
  PY13  host materialization on the device-exchange hot paths:
        ``.tobytes()``, ``np.asarray(...)``, or ``jax.device_get(...)``
        inside the named device-native exchange functions
        (``DEVICE_HOT_FUNCS`` — the padded staging/framing/assembly
        seam).  The device path's whole contract is ZERO intermediate
        host copies between assembly and the destination views; the
        few sanctioned zero-copy shard reads carry a scoped
        ``# noqa: PY13`` with justification.

C++ (native/):
  CC01  line longer than 100 characters
  CC02  trailing whitespace

Exit status 1 on any finding; ``make test`` depends on this.
"""

from __future__ import annotations

import ast
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
PY_MAX_LINE = 88
CC_MAX_LINE = 100

# the noqa grammar + file walking live in tools/gatelib.py (shared by
# every gate); the historical private names are re-exported here so
# the other gates' ``from lint import _suppressed`` keeps meaning ONE
# suppression decision
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from gatelib import (  # noqa: PY05 _noqa_codes re-exported for tests
    PY_DIRS,
    noqa_codes as _noqa_codes,
    suppressed as _suppressed,
)

_IDENT_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")

LIB_DIR = ROOT / "sparkrdma_tpu"


def py_files():
    for d in PY_DIRS:
        yield from sorted((ROOT / d).rglob("*.py"))
    yield from sorted(ROOT.glob("*.py"))


def cc_files():
    native = ROOT / "native"
    if native.is_dir():
        for pat in ("*.cpp", "*.cc", "*.h", "*.hpp"):
            yield from sorted(native.rglob(pat))


class _ImportUsage(ast.NodeVisitor):
    """Collect imported names and every use: plain names, attribute
    roots (via the root Name leaf), decorators (ordinary expressions),
    identifiers inside STRING annotations, and ``__all__`` exports."""

    def __init__(self):
        # name -> (name's own line, import statement's first line)
        self.imports = {}
        self.used = set()

    def visit_Import(self, node):
        for a in node.names:
            name = (a.asname or a.name).split(".")[0]
            self.imports[name] = (
                getattr(a, "lineno", node.lineno), node.lineno
            )

    def visit_ImportFrom(self, node):
        for a in node.names:
            if a.name == "*":
                continue
            self.imports[a.asname or a.name] = (
                getattr(a, "lineno", node.lineno), node.lineno
            )

    def visit_Name(self, node):
        self.used.add(node.id)

    def visit_Attribute(self, node):
        self.generic_visit(node)

    # -- string annotations -------------------------------------------------
    def _ann_strings(self, ann) -> None:
        """Names inside a (possibly quoted) annotation count as used —
        ``x: "np.ndarray"`` keeps its numpy import."""
        if ann is None:
            return
        for n in ast.walk(ann):
            if isinstance(n, ast.Constant) and isinstance(n.value, str):
                self.used.update(_IDENT_RE.findall(n.value))

    def visit_FunctionDef(self, node):
        # argument annotations are handled by visit_arg (generic_visit
        # dispatches it per arg); only the return annotation is ours
        self._ann_strings(node.returns)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_AnnAssign(self, node):
        self._ann_strings(node.annotation)
        self.generic_visit(node)

    def visit_arg(self, node):
        self._ann_strings(node.annotation)
        self.generic_visit(node)

    def visit_Assign(self, node):
        # __all__ re-exports: the listed names are used by definition
        for t in node.targets:
            if isinstance(t, ast.Name) and t.id == "__all__":
                for e in ast.walk(node.value):
                    if isinstance(e, ast.Constant) \
                            and isinstance(e.value, str):
                        self.used.add(e.value)
        self.generic_visit(node)


# zero-copy exchange hot paths: PY09 bans per-block bytes
# materialization (.tobytes() / b"".join) inside these files
HOT_PATHS = (
    pathlib.Path("sparkrdma_tpu/parallel/exchange.py"),
    pathlib.Path("sparkrdma_tpu/shuffle/bulk.py"),
)


def _is_hot_path_copy(node: ast.Call) -> bool:
    """``x.tobytes(...)`` or ``b"".join(...)``."""
    f = node.func
    if not isinstance(f, ast.Attribute):
        return False
    if f.attr == "tobytes":
        return True
    return (
        f.attr == "join"
        and isinstance(f.value, ast.Constant)
        and f.value.value == b""
    )


# TCP transport hot paths: PY10 bans concat-into-sendall anywhere in
# the file and per-frame bytes() materialization inside these functions
TCP_HOT_PATH = pathlib.Path("sparkrdma_tpu/transport/tcp.py")
TCP_HOT_FUNCS = {
    "_send_msg", "_sendmsg_all", "_serve_read", "_recv_read_resp",
    "_recv_payload", "_recv_into", "_read_loop",
}


def _is_bytes_join(node: ast.expr) -> bool:
    """``b"".join(...)``"""
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == "join"
        and isinstance(node.func.value, ast.Constant)
        and node.func.value.value == b""
    )


def _is_sendall_concat(node: ast.Call) -> bool:
    """``<sock>.sendall(a + b)`` / ``<sock>.sendall(b"".join(...))``."""
    f = node.func
    if not (isinstance(f, ast.Attribute) and f.attr == "sendall"):
        return False
    return any(
        isinstance(a, ast.BinOp) and isinstance(a.op, ast.Add)
        or _is_bytes_join(a)
        for a in node.args
    )


def _tcp_hot_func_lines(tree: ast.AST) -> set:
    """Line ranges of the TCP hot-path functions."""
    lines = set()
    for node in ast.walk(tree):
        if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name in TCP_HOT_FUNCS):
            lines.update(range(node.lineno, (node.end_lineno or node.lineno) + 1))
    return lines


# device-native exchange hot paths: PY13 bans host materialization
# (.tobytes() / np.asarray / jax.device_get) inside these functions —
# the padded device path promises zero intermediate host copies
# between assembly and the destination views; deliberate zero-copy
# shard reads get a scoped ``# noqa: PY13`` with a justification
DEVICE_HOT_FUNCS = {
    pathlib.Path("sparkrdma_tpu/parallel/exchange.py"): {
        "exchange_padded",
    },
    pathlib.Path("sparkrdma_tpu/shuffle/bulk.py"): {
        "_assemble", "_exchange_contributed", "_make_round_emitter",
        "_iter_residual_blocks",
    },
    pathlib.Path("sparkrdma_tpu/memory/device_arena.py"): {
        "as_words", "alloc_row", "to_device",
    },
}


def _is_device_host_copy(node: ast.Call):
    """The banned-call label for PY13, or None."""
    f = node.func
    if not isinstance(f, ast.Attribute):
        return None
    if f.attr == "tobytes":
        return ".tobytes()"
    if (f.attr == "asarray" and isinstance(f.value, ast.Name)
            and f.value.id in ("np", "numpy")):
        return "np.asarray()"
    if (f.attr == "device_get" and isinstance(f.value, ast.Name)
            and f.value.id == "jax"):
        return "jax.device_get()"
    return None


def _named_func_lines(tree: ast.AST, names: set) -> set:
    """Line ranges of the named functions (the TCP hot-func pattern)."""
    lines = set()
    for node in ast.walk(tree):
        if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name in names):
            lines.update(
                range(node.lineno, (node.end_lineno or node.lineno) + 1)
            )
    return lines


def _perf_counter_exempt(path: pathlib.Path, lib_dir: pathlib.Path) -> bool:
    """PY08 applies to library code only; the registry (metrics/) and
    the tracer (utils/trace.py) are the sanctioned timing sources."""
    if lib_dir not in path.parents:
        return True
    if lib_dir / "metrics" in path.parents:
        return True
    return path == lib_dir / "utils" / "trace.py"


def _is_perf_counter_call(node: ast.Call) -> bool:
    f = node.func
    if (isinstance(f, ast.Attribute) and f.attr == "perf_counter"
            and isinstance(f.value, ast.Name) and f.value.id == "time"):
        return True
    return isinstance(f, ast.Name) and f.id == "perf_counter"


def lint_python(path: pathlib.Path, findings: list,
                root: pathlib.Path = ROOT) -> None:
    lib_dir = root / "sparkrdma_tpu"
    rel = path.relative_to(root)
    try:
        text = path.read_text()
    except UnicodeDecodeError as e:
        findings.append((rel, 0, "PY01", f"not utf-8: {e}"))
        return
    lines = text.splitlines()
    try:
        tree = ast.parse(text)
    except SyntaxError as e:
        findings.append((rel, e.lineno or 0, "PY01", f"syntax error: {e.msg}"))
        return

    out: list = []  # pre-suppression findings

    for i, line in enumerate(lines, 1):
        if len(line) > PY_MAX_LINE:
            out.append(
                (rel, i, "PY02", f"line too long ({len(line)} > {PY_MAX_LINE})")
            )
        stripped_nl = line.rstrip("\n")
        indent = stripped_nl[: len(stripped_nl) - len(stripped_nl.lstrip())]
        if "\t" in indent:
            out.append((rel, i, "PY03", "tab in indentation"))
        if stripped_nl != stripped_nl.rstrip():
            out.append((rel, i, "PY04", "trailing whitespace"))

    # unused imports (AST usage tracking; __init__ files re-export)
    if path.name != "__init__.py":
        usage = _ImportUsage()
        usage.visit(tree)
        for name, (lineno, stmt_lineno) in usage.imports.items():
            if name in usage.used or name == "annotations":
                continue
            # honor the escape on the import statement's first line AND
            # on the imported name's own line (multi-line from-imports)
            if _suppressed(lines, stmt_lineno, "PY05"):
                continue
            out.append((rel, lineno, "PY05", f"unused import: {name}"))

    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            out.append(
                (rel, node.lineno, "PY06",
                 "bare except: (name the exception type)")
            )
        if (
            lib_dir in path.parents
            and isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "print"
        ):
            out.append(
                (rel, node.lineno, "PY07",
                 "print() in library code (use logging)")
            )
        if (
            isinstance(node, ast.Call)
            and _is_perf_counter_call(node)
            and not _perf_counter_exempt(path, lib_dir)
        ):
            out.append(
                (rel, node.lineno, "PY08",
                 "time.perf_counter() in library code (metric timing "
                 "goes through metrics/ or utils/trace.py)")
            )
        if (
            rel in HOT_PATHS
            and isinstance(node, ast.Call)
            and _is_hot_path_copy(node)
        ):
            out.append(
                (rel, node.lineno, "PY09",
                 'per-block bytes materialization (.tobytes()/b"".join)'
                 " in an exchange hot path (stage into preallocated "
                 "rows instead)")
            )

    if rel == TCP_HOT_PATH:
        hot_lines = _tcp_hot_func_lines(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if _is_sendall_concat(node):
                out.append(
                    (rel, node.lineno, "PY10",
                     "payload concatenation into sendall (send the "
                     "parts as one sendmsg iovec instead)")
                )
            elif (
                node.lineno in hot_lines
                and isinstance(node.func, ast.Name)
                and node.func.id == "bytes"
            ):
                out.append(
                    (rel, node.lineno, "PY10",
                     "per-frame bytes() materialization on a TCP hot "
                     "path (use buffer views / recv_into instead)")
                )

    dev_funcs = DEVICE_HOT_FUNCS.get(rel)
    if dev_funcs:
        dev_lines = _named_func_lines(tree, dev_funcs)
        for node in ast.walk(tree):
            if (not isinstance(node, ast.Call)
                    or node.lineno not in dev_lines):
                continue
            label = _is_device_host_copy(node)
            if label:
                out.append(
                    (rel, node.lineno, "PY13",
                     f"{label} on a device-exchange hot path (keep the"
                     " padded payload device-resident / zero-copy)")
                )

    # one code-scoped suppression gate for every rule
    for rel_, lineno, code, msg in out:
        if not _suppressed(lines, lineno, code):
            findings.append((rel_, lineno, code, msg))


# PY11: conf-key drift.  The declaration side is conf.py's accessor
# calls; the reference side is every full dotted key in library text
# (docstrings included — a doc pointing at a key that does not exist
# is exactly the drift this rule exists to catch).
_CONF_GETTERS = {"get", "set", "_int_in_range", "_bytes_in_range",
                 "_bool", "_time_ms", "_float_in_range"}
_CONF_KEY_RE = re.compile(
    r"spark\.shuffle\.(tpu|rdma)\.([A-Za-z_][A-Za-z0-9_]*)"
)


def _declared_conf_keys(conf_path: pathlib.Path):
    """(declared short keys, legacy→tpu rename map) from conf.py's AST."""
    tree = ast.parse(conf_path.read_text())
    declared: set = set()
    renames: dict = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _CONF_GETTERS
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            declared.add(node.args[0].value)
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Dict):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "LEGACY_RENAMES":
                    for k, v in zip(node.value.keys, node.value.values):
                        if (isinstance(k, ast.Constant)
                                and isinstance(v, ast.Constant)):
                            renames[k.value] = v.value
    return declared, renames


def lint_conf_keys(findings: list, root: pathlib.Path = ROOT) -> None:
    """PY11 — see the module docstring."""
    lib = root / "sparkrdma_tpu"
    conf_path = lib / "conf.py"
    if not conf_path.is_file():
        return
    declared, renames = _declared_conf_keys(conf_path)
    for path in sorted(lib.rglob("*.py")):
        rel = path.relative_to(root)
        lines = path.read_text().splitlines()
        for i, line in enumerate(lines, 1):
            for m in _CONF_KEY_RE.finditer(line):
                ns, short = m.group(1), m.group(2)
                key = renames.get(short, short) if ns == "rdma" else short
                if key in declared:
                    continue
                if _suppressed(lines, i, "PY11"):
                    continue
                findings.append(
                    (rel, i, "PY11",
                     f"conf key {m.group(0)} is not declared in conf.py")
                )
    readme = root / "README.md"
    text = readme.read_text() if readme.is_file() else ""
    for key in sorted(declared):
        if f"`{key}`" in text or f"spark.shuffle.tpu.{key}" in text:
            continue
        findings.append(
            (readme.relative_to(root) if readme.is_file()
             else pathlib.Path("README.md"), 0, "PY11",
             f"declared conf key {key} missing from the README conf tables")
        )


# PY12: flight-recorder event drift.  The declaration side is the
# EVENTS dict literal in obs/events.py; the reference side is every
# fr_event(plane, event, ...) call in library code.  Same shape as
# PY11 — registry parsed from the AST, call sites walked per file.
def _declared_events(events_path: pathlib.Path):
    """``{plane: {event, ...}}`` from the EVENTS dict literal."""
    tree = ast.parse(events_path.read_text())
    declared: dict = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Dict)):
            continue
        for t in node.targets:
            if not (isinstance(t, ast.Name) and t.id == "EVENTS"):
                continue
            for k, v in zip(node.value.keys, node.value.values):
                if not (isinstance(k, ast.Constant)
                        and isinstance(k.value, str)
                        and isinstance(v, (ast.Tuple, ast.List, ast.Set))):
                    continue
                declared[k.value] = {
                    e.value for e in v.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str)
                }
    return declared


def lint_fr_events(findings: list, root: pathlib.Path = ROOT) -> None:
    """PY12 — see the module docstring."""
    lib = root / "sparkrdma_tpu"
    events_path = lib / "obs" / "events.py"
    if not events_path.is_file():
        return
    declared = _declared_events(events_path)
    for path in sorted(lib.rglob("*.py")):
        rel = path.relative_to(root)
        text = path.read_text()
        if "fr_event" not in text:
            continue
        lines = text.splitlines()
        try:
            tree = ast.parse(text)
        except SyntaxError:
            continue  # PY01 already owns this finding
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "fr_event"):
                continue
            msg = None
            if (len(node.args) < 2
                    or not all(isinstance(a, ast.Constant)
                               and isinstance(a.value, str)
                               for a in node.args[:2])):
                msg = ("fr_event(plane, event, ...) must pass plane and "
                       "event as string literals")
            else:
                plane, event = node.args[0].value, node.args[1].value
                if plane not in declared:
                    msg = (f"fr_event plane {plane!r} is not declared "
                           f"in obs/events.py EVENTS")
                elif event not in declared[plane]:
                    msg = (f"fr_event event {plane!r}/{event!r} is not "
                           f"declared in obs/events.py EVENTS")
            if msg is not None and not _suppressed(lines, node.lineno,
                                                   "PY12"):
                findings.append((rel, node.lineno, "PY12", msg))


def lint_cpp(path: pathlib.Path, findings: list) -> None:
    rel = path.relative_to(ROOT)
    for i, line in enumerate(path.read_text().splitlines(), 1):
        if len(line) > CC_MAX_LINE:
            findings.append(
                (rel, i, "CC01", f"line too long ({len(line)} > {CC_MAX_LINE})")
            )
        if line != line.rstrip():
            findings.append((rel, i, "CC02", "trailing whitespace"))


def main() -> int:
    findings: list = []
    for f in py_files():
        lint_python(f, findings)
    for f in cc_files():
        lint_cpp(f, findings)
    lint_conf_keys(findings)
    lint_fr_events(findings)
    for rel, line, code, msg in findings:
        print(f"{rel}:{line}: {code} {msg}")
    if findings:
        print(f"lint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    print(f"lint: clean ({sum(1 for _ in py_files())} py, "
          f"{sum(1 for _ in cc_files())} c++ files)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
