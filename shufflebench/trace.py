"""Reduction of a profiler trace to what the per-layer metrics read.

A ``.xplane.pb`` (``jax.profiler``) is reduced to three things, all on
the trace's one clock in nanoseconds:

- per device, the operations that ran on it: the events of the device
  plane's "XLA Ops" line (name, start, end);
- the benchmark's own host spans (``jax.profiler.TraceAnnotation``):
  ``window`` around the measured loop, ``job`` around each call into
  the user API;
- the program's own host spans, opened through
  ``sparkrdma_tpu/utils/trace.py`` ``Tracer.span`` on any host thread:
  the events whose name starts with ``shuffle.``, with their args (the
  event's stats).

Everything the readers in ``metrics/`` need is computed here from that
reduction (the program's spans through ``program_spans.py``), so each
PR reads the same trace the same way.  A reduced trace saves to JSON
(:meth:`Trace.to_json`), which is how the recorded chip traces in
``tests/`` are kept.
"""

from __future__ import annotations

import bisect
import gzip
import json
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

SPANS = ("window", "job")
PROGRAM = "shuffle."
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU|CPU):(\d+)$")
OPS_LINE = "XLA Ops"

Interval = Tuple[int, int]
ProgramSpan = Tuple[str, int, int, dict]


def short_name(name: str) -> str:
    """An operation's HLO instruction name: the trace names a TPU
    operation by its whole HLO text, ``%sort.11 = (s32[...]) sort(...)``,
    which reduces to ``sort.11``."""
    if name.startswith("%"):
        return name[1:].split(" = ", 1)[0]
    return name


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def covered(merged: Sequence[Interval], lo: int, hi: int) -> int:
    """Length of ``[lo, hi)`` that the disjoint ``merged`` covers."""
    return sum(max(0, min(b, hi) - max(a, lo)) for a, b in merged)


def holes(merged: Sequence[Interval], lo: int,
          hi: int) -> List[Interval]:
    """The parts of ``[lo, hi)`` that the disjoint ``merged`` leaves
    uncovered."""
    out, at = [], lo
    for a, b in merged:
        if b <= at or a >= hi:
            continue
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return out


class Trace:
    """Device operations and host spans of one traced window.

    ``ops`` maps a device id to its operations as (name, start_ns,
    end_ns); ``spans`` lists the benchmark's host spans as (name,
    start_ns, end_ns); ``program`` the program's host spans as (name,
    start_ns, end_ns, args).  Only ``spans`` delimit the window and its
    jobs and label the idle gaps.  ``busy`` maps a device id to the
    disjoint union of its operations' intervals."""

    def __init__(self, ops: Dict[int, List[Tuple[str, int, int]]],
                 spans: List[Tuple[str, int, int]],
                 program: Sequence[ProgramSpan] = ()):
        self.ops = {int(d): sorted((short_name(str(n)), int(a), int(b))
                                   for n, a, b in evs)
                    for d, evs in ops.items()}
        self.spans = sorted(((str(n), int(a), int(b)) for n, a, b in spans),
                            key=lambda s: s[1])
        self.program = sorted(((str(n), int(a), int(b), dict(args))
                               for n, a, b, args in program),
                              key=lambda s: s[1])
        self.busy = {d: merge([(a, b) for _, a, b in evs])
                     for d, evs in self.ops.items()}

    # -- loading and saving ------------------------------------------------
    @classmethod
    def from_xplane(cls, path: str,
                    devices: Optional[Sequence[int]] = None) -> "Trace":
        """Reduce the ``.xplane.pb`` at ``path``; ``devices`` limits the
        device planes read to those ids (the chips a cell uses)."""
        from jax.profiler import ProfileData

        ops: Dict[int, List[Tuple[str, int, int]]] = {}
        spans: List[Tuple[str, int, int]] = []
        program: List[ProgramSpan] = []
        for plane in ProfileData.from_file(path).planes:
            m = _DEVICE_PLANE.match(plane.name)
            if m and m.group(1) != "CPU":
                dev = int(m.group(2))
                if devices is not None and dev not in devices:
                    continue
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops.setdefault(dev, []).extend(
                            (e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events
                        )
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name in SPANS:
                            spans.append((e.name, int(e.start_ns),
                                          int(e.end_ns)))
                        elif e.name.startswith(PROGRAM):
                            program.append((e.name, int(e.start_ns),
                                            int(e.end_ns), dict(e.stats)))
        return cls(ops, spans, program)

    def to_json(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"ops": {str(d): evs for d, evs in self.ops.items()},
                       "spans": self.spans, "program": self.program}, f)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        """A saved trace; one saved before the program's spans were kept
        has none."""
        with gzip.open(path, "rt") as f:
            data = json.load(f)
        return cls({int(d): evs for d, evs in data["ops"].items()},
                   data["spans"], data.get("program", ()))

    # -- spans ---------------------------------------------------------------
    def spans_named(self, name: str) -> List[Interval]:
        return [(a, b) for n, a, b in self.spans if n == name]

    def window(self) -> Interval:
        """The measured window: the ``window`` span."""
        win = self.spans_named("window")
        if not win:
            raise ValueError("trace holds no window span")
        return win[0]

    def jobs(self) -> List[Interval]:
        lo, hi = self.window()
        return [(a, b) for a, b in self.spans_named("job")
                if a >= lo and b <= hi]

    # -- device time ---------------------------------------------------------
    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def busy_ns(self, lo: int, hi: int) -> float:
        """Time in ``[lo, hi)`` in which some operation ran, averaged
        over the devices."""
        if not self.busy:
            return 0.0
        return sum(covered(b, lo, hi) for b in self.busy.values()) / len(
            self.busy)

    def idle_share(self) -> float:
        lo, hi = self.window()
        return 1.0 - self.busy_ns(lo, hi) / (hi - lo)

    def per_job_busy_ns(self) -> List[float]:
        return [self.busy_ns(a, b) for a, b in self.jobs()]

    def per_job_op_ns(self, match: Callable[[str], bool]) -> List[float]:
        """Per job, the summed device time of the operations whose name
        ``match`` accepts, clipped to the job's span and averaged over
        the devices."""
        out = []
        n_dev = max(1, len(self.ops))
        for a, b in self.jobs():
            tot = 0
            for evs in self.ops.values():
                tot += sum(max(0, min(e, b) - max(s, a))
                           for name, s, e in evs if match(name))
            out.append(tot / n_dev)
        return out

    # -- breakdown -----------------------------------------------------------
    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """The ``k`` operation names with the most device time in the
        window, in seconds per device."""
        lo, hi = self.window()
        tot: Dict[str, int] = {}
        for evs in self.ops.values():
            for name, s, e in evs:
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    tot[name] = tot.get(name, 0) + d
        n_dev = max(1, len(self.ops))
        return [(name, ns / n_dev / 1e9) for name, ns in
                sorted(tot.items(), key=lambda t: -t[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[Tuple[str, float]]:
        """Where the window's idle time goes: the stretches in which no
        device ran an operation, cut where a job starts or ends, each
        labelled with the host span that holds it and what bounds it on
        either side, an operation or the job's edge (``"job: sort.11 ->
        job end"``).  Returns the ``k`` labels with the most idle
        seconds in all, each with its count of stretches."""
        lo, hi = self.window()
        allops = [(s, e, name) for evs in self.ops.values()
                  for name, s, e in evs]
        busy = merge([(s, e) for s, e, _ in allops])
        ends = sorted((e, name) for s, e, name in allops)
        starts = sorted((s, name) for s, e, name in allops)
        edges = sorted([(a, "job start") for a, _ in self.jobs()]
                       + [(b, "job end") for _, b in self.jobs()])

        def edge_at(t):
            i = bisect.bisect_left(edges, (t, ""))
            return edges[i][1] if i < len(edges) and edges[i][0] == t \
                else None

        gaps: Dict[str, Tuple[int, int]] = {}
        for a, b in holes(busy, lo, hi):
            cuts = [t for t, _ in edges if a < t < b]
            for x, y in zip([a] + cuts, cuts + [b]):
                i = bisect.bisect_right(ends, (x, "\uffff")) - 1
                j = bisect.bisect_left(starts, (y, ""))
                before = edge_at(x) or (ends[i][1] if i >= 0
                                        and ends[i][0] == x else "start")
                after = edge_at(y) or (starts[j][1] if j < len(starts)
                                       and starts[j][0] == y else "end")
                label = f"{self.span_at((x + y) // 2)}: {before} -> {after}"
                n, tot = gaps.get(label, (0, 0))
                gaps[label] = (n + 1, tot + y - x)
        top = sorted(gaps.items(), key=lambda g: -g[1][1])[:k]
        return [(f"{label} x{n}", tot / 1e9) for label, (n, tot) in top]

    def span_at(self, t: int) -> str:
        """The innermost benchmark span that holds the instant ``t``."""
        best = None
        for name, a, b in self.spans:
            if a <= t < b and (best is None or b - a < best[1]):
                best = (name, b - a)
        return best[0] if best else "none"
