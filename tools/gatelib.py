#!/usr/bin/env python
"""Shared plumbing for the AST gates (lint / concheck / flowcheck /
wirecheck / statecheck).

Four near-identical copies of the same scaffolding had grown across
the gates; this module is the single home for:

* the code-scoped ``# noqa`` grammar — :func:`noqa_codes`,
  :func:`suppressed`, :class:`Suppressor` (tools/lint.py re-exports
  ``_suppressed`` for backwards compatibility, so every gate keeps ONE
  suppression decision);
* the finding shape — :class:`Finding`, a ``(rel, line, code, msg)``
  named tuple that sorts and unpacks exactly like the plain tuples the
  gates historically used;
* file walking — :func:`walk_py` (dirs rglob to ``*.py``, files pass
  through) and :func:`py_files` (lint's repo-wide walk);
* statement-span helpers — :func:`span_search` (trailing annotation
  comments on multi-line statements), :func:`stmt_header_span`
  (compound-statement headers), :func:`string_lines` (docstring spans
  to exclude from comment-grammar scans);
* concheck's guard-lock resolution machinery — :class:`LockDecl`,
  :class:`ClassInfo`, :class:`ModuleInfo`, :func:`collect_module`, and
  :func:`resolve_lock` — so any gate that needs "is this read under
  ``with <recv>._lock:``?" (CK03, SC03) resolves locks the same way.

Nothing here prints or exits; the gates own their own CLIs.
"""

from __future__ import annotations

import ast
import pathlib
import re
from typing import Dict, List, NamedTuple, Optional, Set, Tuple

ROOT = pathlib.Path(__file__).resolve().parent.parent

# -- code-scoped noqa grammar -------------------------------------------------

NOQA_RE = re.compile(r"#\s*noqa\b(?:\s*:\s*(?P<codes>[^#]*))?", re.I)
_CODE_TOKEN_RE = re.compile(r"[A-Za-z]+\d+")
# foreign linter codes accepted as aliases for ours
CODE_ALIASES = {"PY05": {"F401"}}


def noqa_codes(line: str):
    """None = no noqa on the line; empty set = bare ``# noqa``
    (suppresses everything); else the set of named codes.  Code
    tokens (letters+digits, comma/space separated) may be followed by
    a justification — ``# noqa: CK02 serialized frame writes`` scopes
    to CK02; prose with no leading code degrades to a bare noqa."""
    m = NOQA_RE.search(line)
    if m is None:
        return None
    spec = m.group("codes")
    if spec is None:
        return set()
    codes = set()
    for tok in re.split(r"[,\s]+", spec.strip()):
        if _CODE_TOKEN_RE.fullmatch(tok):
            codes.add(tok.upper())
        else:
            break  # justification prose starts here
    return codes


def suppressed(lines, lineno: int, code: str) -> bool:
    """Code-scoped noqa check for a finding at ``lineno``."""
    if not (1 <= lineno <= len(lines)):
        return False
    codes = noqa_codes(lines[lineno - 1])
    if codes is None:
        return False
    if not codes:
        return True  # bare noqa
    return bool(codes & ({code} | CODE_ALIASES.get(code, set())))


class Suppressor:
    """Per-file suppression decision bound to its line list."""

    def __init__(self, lines: List[str]):
        self._lines = lines

    def suppressed(self, lineno: int, code: str) -> bool:
        return suppressed(self._lines, lineno, code)


# -- the finding shape --------------------------------------------------------

class Finding(NamedTuple):
    """One gate finding.  A tuple subclass: unpacks, indexes, sorts and
    compares exactly like the ``(rel, line, code, msg)`` tuples the
    gates historically appended."""

    rel: object
    line: int
    code: str
    msg: str


# -- file walking -------------------------------------------------------------

PY_DIRS = ["sparkrdma_tpu", "tests", "tools"]


def py_files(root: pathlib.Path = ROOT):
    """The repo-wide python walk (lint's scope): the library, tests,
    tools, plus repo-root scripts."""
    for d in PY_DIRS:
        yield from sorted((root / d).rglob("*.py"))
    yield from sorted(root.glob("*.py"))


def walk_py(paths) -> List[pathlib.Path]:
    """Expand a path list the way the analyzers do: directories rglob
    to every ``*.py`` under them (sorted), files pass through."""
    files: List[pathlib.Path] = []
    for p in paths:
        p = pathlib.Path(p)
        if p.is_dir():
            files.extend(sorted(p.rglob("*.py")))
        else:
            files.append(p)
    return files


def rel_to(path: pathlib.Path, root: pathlib.Path) -> str:
    try:
        return str(path.relative_to(root))
    except ValueError:
        return str(path)


# -- statement-span helpers ---------------------------------------------------

def span_search(pattern: re.Pattern, lines: List[str], lineno: int,
                end_lineno: Optional[int]):
    """Search a statement's whole line span (multi-line assignments
    carry their trailing annotation comment on the LAST line)."""
    for i in range(lineno, (end_lineno or lineno) + 1):
        if i <= len(lines):
            m = pattern.search(lines[i - 1])
            if m is not None:
                return m
    return None


COMPOUND_STMTS = (ast.If, ast.While, ast.For, ast.AsyncFor, ast.With,
                  ast.AsyncWith, ast.Try)


def stmt_header_span(stmt: ast.stmt) -> Tuple[int, int]:
    """Line span carrying a statement's trailing annotation: the whole
    span for simple statements, only the header line(s) for compound
    ones (their bodies' annotations belong to the inner statements)."""
    if isinstance(stmt, COMPOUND_STMTS):
        first_body = stmt.body[0].lineno if stmt.body else stmt.lineno
        return stmt.lineno, max(stmt.lineno, first_body - 1)
    return stmt.lineno, stmt.end_lineno or stmt.lineno


def string_lines(tree: ast.Module) -> Set[int]:
    """Lines covered by multi-line string constants (docstrings,
    embedded text): annotation grammar EXAMPLES live there — never
    live annotations — so every scan skips these lines."""
    out: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) \
                and isinstance(node.value, str) \
                and node.end_lineno is not None \
                and node.end_lineno > node.lineno:
            out.update(range(node.lineno, node.end_lineno + 1))
    return out


# -- guard-lock resolution (concheck's declaration machinery) -----------------

THREADING_LOCKS = {"Lock": "Lock", "RLock": "RLock",
                   "Condition": "Condition"}
DBG_CTORS = {"dbg_lock": "Lock", "dbg_rlock": "RLock",
             "dbg_condition": "Condition"}

RANK_RE = re.compile(r"#\s*lock-order:\s*(-?\d+)")
GUARD_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z_][A-Za-z0-9_]*)")

LockId = Tuple[str, ...]


class LockDecl:
    __slots__ = ("lock_id", "kind", "rank", "line", "group", "name")

    def __init__(self, lock_id: LockId, kind: str, rank: Optional[int],
                 line: int, group: bool, name: str):
        self.lock_id = lock_id
        self.kind = kind
        self.rank = rank
        self.line = line
        self.group = group
        self.name = name


class ClassInfo:
    def __init__(self, name: str):
        self.name = name
        self.locks: Dict[str, LockDecl] = {}
        self.events: Set[str] = set()
        self.queues: Set[str] = set()
        self.threads: Set[str] = set()
        self.guarded: Dict[str, Tuple[str, int]] = {}  # attr -> (lock, line)
        self.methods: Dict[str, ast.AST] = {}


class ModuleInfo:
    def __init__(self, rel: str, lines: List[str], tree: ast.Module):
        self.rel = rel
        self.lines = lines
        self.tree = tree  # parsed once, shared by both passes
        self.locks: Dict[str, LockDecl] = {}  # module-level, by name
        self.classes: Dict[str, ClassInfo] = {}


def call_name(func: ast.expr) -> str:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return ""


def lock_ctor(node: ast.expr) -> Optional[Tuple[str, Optional[int]]]:
    """(kind, dbg rank or None) when ``node`` constructs a lock."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
            and f.value.id == "threading"
            and f.attr in THREADING_LOCKS):
        return THREADING_LOCKS[f.attr], None
    name = call_name(f)
    if name in DBG_CTORS:
        rank = None
        if len(node.args) >= 2 and isinstance(node.args[1], ast.Constant) \
                and isinstance(node.args[1].value, int):
            rank = node.args[1].value
        for kw in node.keywords:
            if kw.arg == "rank" and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, int):
                rank = kw.value.value
        return DBG_CTORS[name], rank
    return None


def lock_group_ctor(node: ast.expr) -> Optional[str]:
    """Kind when ``node`` builds a list of locks (lock striping)."""
    elts: List[ast.expr] = []
    if isinstance(node, (ast.List, ast.Tuple)):
        elts = list(node.elts)
    elif isinstance(node, ast.ListComp):
        elts = [node.elt]
    for e in elts:
        got = lock_ctor(e)
        if got is not None:
            return got[0]
    return None


def ctor_of(node: ast.expr, module: str, names: Set[str]) -> bool:
    """``node`` is a call to module.name() or a bare name() in names."""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
            and f.value.id == module and f.attr in names):
        return True
    return isinstance(f, ast.Name) and f.id in names


def make_decl(lock_id: LockId, kind: str, dbg_rank: Optional[int],
              lineno: int, group: bool, name: str, lines: List[str],
              findings: List[Finding], sup: Suppressor,
              rel: str, end_lineno: Optional[int] = None,
              rank_findings: bool = True) -> LockDecl:
    """Build one LockDecl, resolving its rank from the ``# lock-order``
    annotation or the dbg ctor argument.  Rank-discipline findings
    (CK04) are concheck's to emit — a gate reusing the collection for
    resolution only passes ``rank_findings=False``."""
    m = span_search(RANK_RE, lines, lineno, end_lineno)
    rank = int(m.group(1)) if m else None
    if rank is not None and dbg_rank is not None and rank != dbg_rank \
            and rank_findings:
        if not sup.suppressed(lineno, "CK04"):
            findings.append((rel, lineno, "CK04",
                             f"lock {name}: # lock-order comment ({rank}) "
                             f"disagrees with dbg rank ({dbg_rank})"))
    if rank is None:
        rank = dbg_rank
    if rank is None and rank_findings \
            and not sup.suppressed(lineno, "CK04"):
        findings.append(
            (rel, lineno, "CK04",
             f"lock {name} has no rank — annotate its creation line "
             f"with '# lock-order: N' (or create it via dbg_lock/"
             f"dbg_rlock/dbg_condition with a rank argument)")
        )
    return LockDecl(lock_id, kind, rank, lineno, group, name)


def collect_class(rel: str, cls: ast.ClassDef, lines: List[str],
                  findings: List[Finding], sup: Suppressor,
                  rank_findings: bool = True) -> ClassInfo:
    info = ClassInfo(cls.name)
    for item in cls.body:
        if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            info.methods[item.name] = item
    for meth in info.methods.values():
        for node in ast.walk(meth):
            target = None
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt = node.targets[0]
                if isinstance(tgt, ast.Attribute) \
                        and isinstance(tgt.value, ast.Name) \
                        and tgt.value.id == "self":
                    target, value = tgt.attr, node.value
            elif isinstance(node, ast.AnnAssign) \
                    and isinstance(node.target, ast.Attribute) \
                    and isinstance(node.target.value, ast.Name) \
                    and node.target.value.id == "self" \
                    and node.value is not None:
                target, value = node.target.attr, node.value
            if target is None:
                continue
            got = lock_ctor(value)
            group_kind = lock_group_ctor(value) if got is None else None
            if got is not None or group_kind is not None:
                kind, dbg_rank = got if got is not None \
                    else (group_kind, None)
                info.locks[target] = make_decl(
                    ("attr", rel, cls.name, target), kind, dbg_rank,
                    node.lineno, got is None, f"{cls.name}.{target}",
                    lines, findings, sup, rel, node.end_lineno,
                    rank_findings,
                )
                continue
            if ctor_of(value, "threading", {"Event"}):
                info.events.add(target)
            elif ctor_of(value, "queue", {"Queue", "SimpleQueue",
                                          "LifoQueue", "PriorityQueue"}):
                info.queues.add(target)
            elif ctor_of(value, "threading", {"Thread", "Timer"}):
                info.threads.add(target)
            g = span_search(GUARD_RE, lines, node.lineno,
                            node.end_lineno)
            if g is not None:
                info.guarded[target] = (g.group(1), node.lineno)
    return info


def collect_module(rel: str, tree: ast.Module,
                   lines: List[str], findings: List[Finding],
                   sup: Suppressor,
                   rank_findings: bool = True) -> ModuleInfo:
    """Pass 1 of concheck's analysis: every module/class lock
    declaration plus guarded-by annotations — the resolution index
    both CK03 and SC03 check held regions against."""
    mod = ModuleInfo(rel, lines, tree)
    for stmt in tree.body:
        target = None
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name):
            target, value = stmt.targets[0].id, stmt.value
        elif isinstance(stmt, ast.AnnAssign) \
                and isinstance(stmt.target, ast.Name) \
                and stmt.value is not None:
            target, value = stmt.target.id, stmt.value
        if target is None:
            continue
        got = lock_ctor(value)
        if got is not None:
            kind, dbg_rank = got
            mod.locks[target] = make_decl(
                ("mod", rel, target), kind, dbg_rank, stmt.lineno,
                False, target, lines, findings, sup, rel,
                stmt.end_lineno, rank_findings,
            )
    for stmt in tree.body:
        if isinstance(stmt, ast.ClassDef):
            mod.classes[stmt.name] = collect_class(
                rel, stmt, lines, findings, sup, rank_findings
            )
    # nested classes (e.g. helper classes defined inside functions) are
    # rare; classes nested one level inside classes are picked up too
    for stmt in ast.walk(tree):
        if isinstance(stmt, ast.ClassDef) and stmt.name not in mod.classes:
            mod.classes[stmt.name] = collect_class(
                rel, stmt, lines, findings, sup, rank_findings
            )
    return mod


class Held:
    """One entry of a held-lock stack: ``key`` is the syntactic
    ``(receiver, attr)`` identity a guarded read is checked against."""

    __slots__ = ("key", "lock_id", "kind", "line")

    def __init__(self, key, lock_id, kind, line):
        self.key = key        # (receiver, attr) or ("", name)
        self.lock_id = lock_id
        self.kind = kind
        self.line = line


def resolve_lock(mod: ModuleInfo, cls: Optional[ClassInfo],
                 local_locks: Set[str], expr: ast.expr):
    """(key, decl-or-None) for a with-item that looks like a lock;
    None when it is not lock-shaped at all.  Attribute locks resolve
    through the current class first, then any unique owner class in
    the module (non-self receivers like ``pool._lock``)."""
    if isinstance(expr, ast.Subscript):
        expr = expr.value
    if isinstance(expr, ast.Attribute) \
            and isinstance(expr.value, ast.Name):
        recv, attr = expr.value.id, expr.attr
        decl = None
        if cls is not None and attr in cls.locks:
            decl = cls.locks[attr]
        else:
            owners = [
                c for c in mod.classes.values()
                if attr in c.locks
            ]
            if len(owners) == 1:
                decl = owners[0].locks[attr]
        if decl is not None or attr.endswith("lock") \
                or attr.endswith("_cv"):
            return (recv, attr), decl
        return None
    if isinstance(expr, ast.Name):
        if expr.id in mod.locks:
            return ("", expr.id), mod.locks[expr.id]
        if expr.id in local_locks:
            return ("", expr.id), None
    return None

