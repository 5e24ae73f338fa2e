"""Lightweight span tracing, into two sinks behind one API.

The reference's only tracing is inline wall-clock logging
(SURVEY.md §5: connection latency at RdmaNode.java:279,307-308, fetch
timing at RdmaShuffleFetcherIterator.scala:110,140-148).  The rebuild
promotes that to a proper subsystem.  ``Tracer.span`` writes into:

- the profiler's trace, always: a ``jax.profiler.TraceAnnotation``
  that lands in a ``jax.profiler`` capture's host plane, on the clock
  of the device operations, with the span's args as event stats.
  Outside a profiler session it records nothing (under a microsecond a
  span);
- a Chrome trace-event buffer, only while the Tracer is enabled (conf
  ``spark.shuffle.tpu.trace``, or programmatically): nested spans
  collected per thread, dumpable as a ``chrome://tracing`` / Perfetto
  JSON file.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from typing import Dict, List


def _annotation_class():
    """``jax.profiler.TraceAnnotation``, or None in a process that has
    not imported JAX: such a process runs no profiler session, and the
    record plane's executors need not import JAX to trace."""
    profiler = sys.modules.get("jax.profiler")
    return getattr(profiler, "TraceAnnotation", None)


class _Span:
    """What ``Tracer.span`` yields.  ``recording`` is true while a sink
    records the span (a profiler session or the enabled Tracer), so a
    value that costs a syscall is computed only then; ``set(**args)``
    adds values known only at the span's end to both sinks."""

    __slots__ = ("_tracer", "_name", "_args", "_ann", "_ts", "recording")

    def __init__(self, tracer: "Tracer", name: str, args: Dict):
        self._tracer, self._name, self._args = tracer, name, args

    def __enter__(self) -> "_Span":
        cls = _annotation_class()
        self._ann = None if cls is None else cls(self._name, **self._args)
        if self._ann is not None:
            self._ann.__enter__()
        self._ts = self._tracer._now_us() if self._tracer.enabled else None
        self.recording = self._ts is not None or (
            self._ann is not None and cls.is_enabled())
        return self

    def set(self, **args) -> None:
        if not self.recording:
            return
        self._args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc) -> None:
        if self._ts is not None:
            self._tracer._append({
                "name": self._name, "ph": "X", "ts": self._ts,
                "dur": self._tracer._now_us() - self._ts,
                "pid": 0, "tid": threading.get_ident() % 100000,
                "args": self._args,
            })
        if self._ann is not None:
            self._ann.__exit__(*exc)


class Tracer:
    def __init__(self, enabled: bool = False, process_name: str = "sparkrdma_tpu",
                 max_events: int = 1 << 20):
        self.enabled = enabled
        self.process_name = process_name
        self.max_events = max_events
        self.dropped = 0
        self._events: List[Dict] = []
        self._lock = threading.Lock()  # lock-order: 92
        self._t0 = time.perf_counter()

    def _append(self, event: Dict) -> None:
        """Bounded append: beyond max_events new events are counted but
        dropped, so an always-on trace can't grow without limit.  Drops
        were once silent (the count surfaced only in the dump's
        metadata); now they tick ``trace_dropped_total`` so a live
        scrape shows a saturated tracer while the run is still up."""
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                dropped = True
            else:
                self._events.append(event)
                dropped = False
        if dropped:
            # outside the tracer lock (92): the registry's stripe locks
            # rank higher but keeping inc() lock-free here is cheaper
            from sparkrdma_tpu.metrics import counter

            counter("trace_dropped_total").inc()

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def span(self, name: str, **args) -> _Span:
        """A span around a ``with`` block, on the calling thread, so
        spans nest.  ``args`` are known at the start; the handle's
        ``set`` adds those known at the end."""
        return _Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        if not self.enabled:
            return
        self._append({
            "name": name, "ph": "i", "ts": self._now_us(), "s": "t",
            "pid": 0, "tid": threading.get_ident() % 100000,
            "args": args or {},
        })

    def counter(self, name: str, **values) -> None:
        if not self.enabled:
            return
        self._append({
            "name": name, "ph": "C", "ts": self._now_us(),
            "pid": 0, "args": values,
        })

    @property
    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._events)

    def dump(self, path: str) -> None:
        """Write a chrome://tracing-compatible JSON file."""
        with self._lock:
            events = list(self._events)
        doc = {
            "traceEvents": events,
            "metadata": {
                "process_name": self.process_name,
                "dropped_events": self.dropped,
            },
        }
        with open(path, "w") as f:
            json.dump(doc, f)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()


# process-global default tracer; managers enable it from conf
GLOBAL_TRACER = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return GLOBAL_TRACER
