"""The program's own host spans in a traced window, for the readers of
the api / host driver layer.

The program opens its spans through ``sparkrdma_tpu/utils/trace.py``
``Tracer.span``, which writes each into the profiler's host plane as an
event named ``shuffle.<plane>.<phase>`` with its args as stats, on the
clock of the device operations.  :class:`shufflebench.trace.Trace`
keeps them, with their args, as ``Trace.program``, and a saved trace
(``run.py --save-trace``, ``tests/data/*.spans.trace.json.gz``) carries
them under ``"program"``.  A trace of a program that opens no such
spans holds none, and its readers give no value.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

from shufflebench.trace import Trace


class Program:
    """The program's spans of one traced window, as (name, start_ns,
    end_ns, args), beside the reduced ``trace`` on whose jobs they are
    summed."""

    def __init__(self, trace: Trace):
        self.trace = trace
        self.spans = trace.program

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
        busy = self.trace.busy.values()
        if not busy:
            return 0.0
        return sum(next((max(a, lo) for a, b in evs if b > lo and a < hi),
                        hi) - lo
                   for evs in busy) / len(busy)


def of(r) -> Program:
    """The program's spans of reading ``r`` (``run.Reading``)."""
    return Program(r.trace)


def load(path: str) -> Tuple[Trace, Program]:
    """A recorded trace and the program's spans it holds."""
    trace = Trace.from_json(path)
    return trace, Program(trace)
