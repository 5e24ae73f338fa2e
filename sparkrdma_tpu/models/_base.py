"""Shared host-driver machinery for the SPMD model pipelines.

Capacity sizing and the overflow-retry loop are policy, shared by every
capacity-bucketed exchange model (sort, count, …): buckets are padded to
a static capacity; true counts travel with the exchange; if any bucket's
true count exceeded capacity the host re-runs the step with doubled
capacity (the SPMD inversion of the reference's maxAggBlock fetch cap,
SURVEY.md §7 hard parts).
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.parallel.mesh import EXCHANGE_AXIS, make_mesh

MAX_OVERFLOW_RETRIES = 6


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

    def _retry_with_factor(self, run: Callable[[float], Tuple]):
        """Call ``run(factor)`` → (outputs, overflowed: bool); re-run
        with doubled skew factor while any bucket overflowed.  The
        general form for models with more than one capacity (e.g. the
        two-sided join)."""
        factor = self.capacity_factor
        for _attempt in range(MAX_OVERFLOW_RETRIES):
            outputs, overflowed = run(factor)
            if not overflowed:
                return outputs
            factor *= 2  # key skew overflowed a bucket: retry bigger
        raise RuntimeError(
            f"bucket overflow persisted after {MAX_OVERFLOW_RETRIES} retries"
        )

    def _run_with_overflow_retry(
        self, n_total: int, run: Callable[[int], Tuple]
    ):
        """Call ``run(capacity)`` → (outputs, max_fill); re-run with
        doubled factor while any bucket overflowed."""

        def attempt(factor: float):
            cap = self._capacity(n_total // self.n_devices, factor)
            outputs, max_fill = run(cap)
            return outputs, int(np.max(np.asarray(max_fill))) > cap

        return self._retry_with_factor(attempt)

    def _run_padded_keyed(self, keys, vals, make_step):
        """Shared host driver for keyed-exchange models (wordcount,
        aggregate): pad columns to a multiple of D with a validity
        column, place them on the mesh ONCE, run
        ``make_step(mesh, n_local, capacity)`` under the overflow-retry
        policy, and hand back per-device host rows.

        The step must return ``(*row_arrays, n_unique[1], max_fill[1])``
        per device.  Returns ``(rows, nu)``: each of ``rows`` reshaped
        to [D, -1] on the host, ``nu`` the int32[D] valid-row counts.
        """
        keys = np.asarray(keys)
        vals = np.asarray(vals)
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
        # D == 1 with no padding: every slot is real, so the step can
        # drop the validity operand from its sort (the sort is the
        # step's whole cost on one chip)
        fast = D == 1 and n_pad == 0
        cols = (keys, vals) if fast else (keys, vals, valid)
        # place once, shard by shard (never whole on one device): only
        # the capacity changes between overflow retries
        placed = tuple(jax.device_put(x, self.sharding) for x in cols)

        def run(cap):
            step = make_step(
                self.mesh, (n + n_pad) // D, cap,
                with_validity=not fast,
            )
            *rows, n_unique, max_fill = step(*placed)
            return (rows, n_unique), max_fill

        rows, n_unique = self._run_with_overflow_retry(n + n_pad, run)
        host_rows = [np.asarray(r).reshape(D, -1) for r in rows]
        nu = np.asarray(n_unique).reshape(-1)
        return host_rows, nu
