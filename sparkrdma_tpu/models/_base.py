"""Shared host-driver machinery for the SPMD model pipelines.

Capacity sizing and the overflow-retry loop are policy, shared by every
capacity-bucketed exchange model (sort, count, …): buckets are padded to
a static capacity; true counts travel with the exchange; if any bucket's
true count exceeded capacity the host re-runs the step with doubled
capacity (the SPMD inversion of the reference's maxAggBlock fetch cap,
SURVEY.md §7 hard parts).

The host driver opens a span (``utils/trace.py``) at each of its
boundaries, ``shuffle.device.<phase>``: ``pad`` (host preparation),
``place`` (``jax.device_put``), ``attempt`` (one pass of the overflow
loop) holding ``sync`` (the wait for the step's bucket fill),
``fetch`` (the step's outputs to host memory) and ``stitch`` (the
host-side result).  Each retry ticks ``device_overflow_retries_total``.

Outputs come to the host shard by shard (:meth:`ExchangeModel._fetch_runs`):
one host buffer a device, never a global host array.
"""

from __future__ import annotations

import contextlib
import math
import resource
from typing import Callable, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.metrics import counter
from sparkrdma_tpu.parallel.mesh import EXCHANGE_AXIS, make_mesh
from sparkrdma_tpu.utils.trace import get_tracer

MAX_OVERFLOW_RETRIES = 6


@contextlib.contextmanager
def faulting_span(name: str, **args):
    """``get_tracer().span`` that, while recorded, also sets ``minflt``:
    the minor page faults the process took inside it (host buffers
    touched for the first time)."""
    with get_tracer().span(name, **args) as sp:
        before = resource.getrusage(resource.RUSAGE_SELF).ru_minflt \
            if sp.recording else None
        yield sp
        if before is not None:
            sp.set(minflt=resource.getrusage(
                resource.RUSAGE_SELF).ru_minflt - before)


def quantize_padded_length(n: int, d: int) -> int:
    """Smallest padded length ≥ n that is a multiple of ``d`` and sits
    on a 16-steps-per-octave ladder (≤12.5% padding, worst case just
    past an octave boundary where the step is 1/8 of n).

    The SPMD steps compile per (n_local, capacity) shape, so feeding
    exact input sizes compiles a fresh XLA program for every distinct
    job size (20-40s per novel shape on a real chip).  Quantizing the
    padded length collapses arbitrary sizes onto ~16 shapes per octave;
    padding rides the existing validity column.  Inputs already on the
    ladder (e.g. power-of-two benches) pad nothing and keep the
    validity-free fast path.
    """
    if n <= 0:
        return n
    if n <= 16:
        m = n
    else:
        k = (n - 1).bit_length()
        step = 1 << max(0, k - 4)
        m = (n + step - 1) // step * step
    return (m + d - 1) // d * d


def check_no_silent_truncation(**columns) -> None:
    """Reject int64 columns when jax_enable_x64 is off: jnp.asarray
    would silently truncate them to int32, colliding keys or corrupting
    values with no error.  Shared by every keyed model (aggregations
    AND joins)."""
    for name, col in columns.items():
        if np.asarray(col).dtype == np.int64 and not jax.config.jax_enable_x64:
            raise ValueError(
                f"int64 {name} require jax_enable_x64 (without it JAX "
                "silently truncates to int32 — colliding keys / "
                "corrupting values)"
            )


class ExchangeModel:
    """Base for host-facing drivers of capacity-bucketed SPMD steps."""

    def __init__(self, mesh: Optional[Mesh] = None,
                 capacity_factor: float = 1.3,
                 quantize_shapes: bool = True):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_devices = len(list(self.mesh.devices.flat))
        self.capacity_factor = capacity_factor
        # quantize padded lengths onto the compile-shape ladder
        # (quantize_padded_length); opt out for exact-shape control
        self.quantize_shapes = quantize_shapes
        self.sharding = NamedSharding(self.mesh, P(EXCHANGE_AXIS))

    def _padded_length(self, n: int) -> int:
        """Padded total length for an n-row input: multiple of D, on
        the compile-shape ladder when ``quantize_shapes``."""
        if self.quantize_shapes:
            return quantize_padded_length(n, self.n_devices)
        return n + ((-n) % self.n_devices)

    def _capacity(self, n_local: int, factor: Optional[float] = None) -> int:
        """Per-bucket capacity: n_local/D scaled by the skew factor,
        rounded up to a sublane-friendly multiple of 8."""
        factor = self.capacity_factor if factor is None else factor
        cap = int(math.ceil(n_local / self.n_devices * factor))
        return max(8, (cap + 7) // 8 * 8)

    def _run_with_overflow_retry(
        self, n_total: int, run: Callable[[int], Tuple]
    ):
        """Call ``run(capacity)`` → (outputs, max_fill); re-run with
        doubled skew factor while any bucket overflowed."""
        tracer = get_tracer()
        factor = self.capacity_factor
        for attempt in range(MAX_OVERFLOW_RETRIES):
            if attempt:
                # key skew overflowed a bucket: retry bigger
                counter("device_overflow_retries_total").inc()
                factor *= 2
            cap = self._capacity(n_total // self.n_devices, factor)
            with tracer.span("shuffle.device.attempt", factor=factor,
                             capacity=cap) as sp:
                outputs, max_fill = run(cap)
                with tracer.span("shuffle.device.sync"):
                    (fills,) = self._fetch_runs(max_fill)
                    fill = max(int(f.max()) for f in fills)
                sp.set(max_fill=fill, overflowed=fill > cap)
            if fill <= cap:
                return outputs
        raise RuntimeError(
            f"bucket overflow persisted after {MAX_OVERFLOW_RETRIES} retries"
        )

    def _place(self, *cols, sharding=None) -> Tuple:
        """``jax.device_put`` each host column, shard by shard (never
        whole on one device), in one ``place`` span."""
        sharding = sharding or self.sharding
        with faulting_span("shuffle.device.place",
                           bytes=sum(c.nbytes for c in cols),
                           shards=self.n_devices):
            return tuple(jax.device_put(c, sharding) for c in cols)

    @staticmethod
    def _fetch_runs(*arrays) -> List[List[np.ndarray]]:
        """Each array's per-device shards as read-only host arrays, in
        mesh order.  Every shard's copy starts before the first is
        waited for.  ``np.asarray`` of a sharded array would also copy
        every shard into a fresh global host array; this never builds
        one, so one device's run is one host buffer."""
        shards = [[s.data for s in sorted(
            a.addressable_shards, key=lambda s: s.index[0].start or 0)]
            for a in arrays]
        for runs in shards:
            for x in runs:
                x.copy_to_host_async()
        return [[np.asarray(x) for x in runs] for runs in shards]

    def _fetch(self, *outputs, row_bytes: int):
        """The step's outputs to host memory, in one ``fetch`` span.
        ``outputs`` end with the per-device valid-row counts; returns
        (per output but the counts, its D per-device runs; the counts
        as int[D]).  ``row_bytes``: what the caller returns of each
        valid row (the span's ``result_bytes``)."""
        with faulting_span("shuffle.device.fetch",
                           bytes=sum(o.nbytes for o in outputs),
                           shards=self.n_devices) as sp:
            *runs, counts = self._fetch_runs(*outputs)
            counts = np.concatenate(counts)
            sp.set(result_bytes=row_bytes * int(counts.sum()))
        return runs, counts

    def _run_padded_keyed(self, keys, vals, make_step,
                          result_cols: Optional[int] = None):
        """Shared host driver for keyed-exchange models (wordcount,
        aggregate): pad columns to a multiple of D with a validity
        column, place them on the mesh ONCE, run
        ``make_step(mesh, n_local, capacity)`` under the overflow-retry
        policy, and hand back per-device host rows.  ``vals`` None
        counts one per key.

        The step must return ``(*row_arrays, n_unique[1], max_fill[1])``
        per device.  Returns ``(rows, nu)``: each of ``rows`` a list of
        its D per-device host runs (``rows[i][d]``, read-only), ``nu``
        the int32[D] valid-row counts.
        ``result_cols``: the leading row columns the caller returns of
        each valid row (the fetch span's ``result_bytes``); all of them
        by default.
        """
        tracer = get_tracer()
        with tracer.span("shuffle.device.pad") as sp:
            keys = np.asarray(keys)
            vals = np.ones_like(keys) if vals is None else np.asarray(vals)
            if keys.shape != vals.shape or keys.ndim != 1:
                raise ValueError("keys/vals must be equal-length 1-D arrays")
            check_no_silent_truncation(keys=keys, vals=vals)
            n = keys.shape[0]
            if n == 0:
                return None, None
            D = self.n_devices
            n_pad = self._padded_length(n) - n
            valid = np.ones(n + n_pad, np.int32)
            if n_pad:
                keys = np.concatenate([keys, np.zeros(n_pad, keys.dtype)])
                vals = np.concatenate([vals, np.zeros(n_pad, vals.dtype)])
                valid[n:] = 0
            # D == 1 with no padding: every slot is real, so the step
            # can drop the validity operand from its sort (the sort is
            # the step's whole cost on one chip)
            fast = D == 1 and n_pad == 0
            cols = (keys, vals) if fast else (keys, vals, valid)
            sp.set(bytes=sum(c.nbytes for c in cols))
        # place once: only the capacity changes between overflow retries
        placed = self._place(*cols)

        def run(cap):
            step = make_step(
                self.mesh, (n + n_pad) // D, cap,
                with_validity=not fast,
            )
            *rows, n_unique, max_fill = step(*placed)
            return (rows, n_unique), max_fill

        rows, n_unique = self._run_with_overflow_retry(n + n_pad, run)
        return self._fetch(*rows, n_unique, row_bytes=sum(
            r.dtype.itemsize for r in rows[:result_cols]))
