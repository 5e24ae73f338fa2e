"""The program's own host spans in a traced window, for the readers of
the api / host driver layer.

The program opens its spans through ``sparkrdma_tpu/utils/trace.py``
``Tracer.span``, which writes each into the profiler's host plane as an
event named ``shuffle.<plane>.<phase>`` with its args as stats, on the
clock of the device operations.  :class:`shufflebench.trace.Trace`
keeps only the benchmark's own ``window`` and ``job`` spans of that
plane, so the events whose name starts with ``shuffle.`` are read here,
from the same ``.xplane.pb``: ``run.py`` writes it into a
``shufflebench-*`` directory of the temporary directory and removes
that only once the readers have run.  :func:`of` finds it by the
reading's ``window`` span and keeps what it read on the reading, so the
readers of one run parse it once.  A trace of a program that opens no
such spans gives none, and its readers give no value.

A recorded trace (``tests/data/*.spans.trace.json.gz``) holds the
program's spans beside the reduced trace, under ``"program"``
(:func:`load`).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import tempfile
from typing import List, Optional, Sequence, Tuple

from shufflebench.trace import Trace, merge

PREFIX = "shuffle."

Span = Tuple[str, int, int, dict]


class Program:
    """The program's spans of one traced window, as (name, start_ns,
    end_ns, args), beside the reduced ``trace`` on whose jobs they are
    summed."""

    def __init__(self, trace: Trace, spans: Sequence[Span] = ()):
        self.trace = trace
        self.spans = sorted(((str(n), int(a), int(b), dict(args))
                             for n, a, b, args in spans),
                            key=lambda s: s[1])
        self._busy = [merge([(a, b) for _, a, b in evs])
                      for evs in trace.ops.values()]

    def named(self, name: str, lo: int,
              hi: int) -> List[Tuple[int, int, dict]]:
        """The spans named ``name`` that lie in ``[lo, hi]``, as
        (start_ns, end_ns, args)."""
        return [(a, b, args) for n, a, b, args in self.spans
                if n == name and a >= lo and b <= hi]

    def per_job_ns(self, names: Sequence[str]) -> List[int]:
        """Per job, the summed length of the spans named in ``names``
        that lie inside the job's span."""
        return [sum(b - a for name in names
                    for a, b, _ in self.named(name, lo, hi))
                for lo, hi in self.trace.jobs()]

    def idle_until_busy_ns(self, lo: int, hi: int) -> float:
        """From ``lo`` to the first instant in ``[lo, hi)`` at which an
        operation runs (``hi`` if none does), averaged over the
        devices."""
        if not self._busy:
            return 0.0
        return sum(next((max(a, lo) for a, b in busy if b > lo and a < hi),
                        hi) - lo
                   for busy in self._busy) / len(self._busy)


def from_xplane(path: str) -> Tuple[Optional[Tuple[int, int]], List[Span]]:
    """The ``window`` span and the program's spans of the ``.xplane.pb``
    at ``path``."""
    from jax.profiler import ProfileData

    window, spans = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "window":
                    window = (int(e.start_ns), int(e.end_ns))
                elif e.name.startswith(PREFIX):
                    spans.append((e.name, int(e.start_ns), int(e.end_ns),
                                  dict(e.stats)))
    return window, spans


def _of_run(trace: Trace) -> List[Span]:
    """The program's spans of the run whose reduced trace is ``trace``:
    those of the newest ``.xplane.pb`` under the temporary directory's
    ``shufflebench-*`` directories whose ``window`` span is the
    trace's."""
    paths = glob.glob(os.path.join(tempfile.gettempdir(), "shufflebench-*",
                                   "**", "*.xplane.pb"), recursive=True)
    want = trace.window()
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        window, spans = from_xplane(path)
        if window == want:
            return spans
    return []


def of(r) -> Program:
    """The program's spans of reading ``r`` (``run.Reading``), read
    once and kept on it."""
    p = getattr(r, "program", None)
    if p is None:
        p = r.program = Program(r.trace, _of_run(r.trace))
    return p


def load(path: str) -> Tuple[Trace, Program]:
    """A recorded trace and the program's spans it holds."""
    trace = Trace.from_json(path)
    with gzip.open(path, "rt") as f:
        spans = json.load(f).get("program", ())
    return trace, Program(trace, spans)

