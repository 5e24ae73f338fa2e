"""WordCount / reduceByKey on the mesh.

The reference's hash-partitioned shuffle benchmarks (HiBench Sort +
WordCount, README.md:17) as one SPMD program: hash-partition keys,
all_to_all, then a device-side segment reduction
(sparkrdma_tpu.ops.segment) — every key's total ends up on exactly one
device, the contract a reduceByKey shuffle provides.

Validity is an explicit 0/1 column (not a key sentinel), so real keys
equal to the dtype max are counted correctly.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from sparkrdma_tpu.models._base import ExchangeModel
from sparkrdma_tpu.ops.exchange import hash_exchange
from sparkrdma_tpu.ops.segment import reduce_by_key_local
from sparkrdma_tpu.parallel.mesh import EXCHANGE_AXIS
from sparkrdma_tpu.utils.trace import get_tracer


@functools.lru_cache(maxsize=16)
def make_count_step(mesh: Mesh, n_local: int, capacity: int,
                    with_validity: bool = True):
    """Jitted reduceByKey(+) step over global [D*n_local] key/value
    (/valid) arrays sharded on the mesh axis.  ``with_validity=False``
    is the D == 1 unpadded fast path: every slot is real, so the
    validity operand drops out of the reduction sort entirely."""
    D = len(list(mesh.devices.flat))
    spec = P(EXCHANGE_AXIS)

    if not with_validity:
        if D != 1:
            raise ValueError(
                "with_validity=False requires D == 1 (bucket fills on "
                "a real exchange need the validity column)"
            )

        def wordcount_step(k, v):  # local [n_local], all slots real
            uniq, sums, cnts, n_unique = reduce_by_key_local(k, v, None)
            return uniq, sums, cnts, n_unique[None], jnp.zeros(1, jnp.int32)

        mapped = jax.shard_map(
            wordcount_step, mesh=mesh, in_specs=(spec, spec),
            out_specs=(spec,) * 5,
        )
        return jax.jit(mapped)

    def wordcount_step(k, v, valid):  # local [n_local]
        # (hash_exchange is the identity for D == 1 — no padded sorts)
        flat_k, flat_v, flat_m, max_fill = hash_exchange(
            k, v, valid, D, capacity
        )
        # pre-mask for the reduction contract: invalid slots (bucket pads
        # and input padding) get the grouping key + zero value
        sentinel = jnp.array(jnp.iinfo(k.dtype).max, k.dtype)
        flat_k = jnp.where(flat_m > 0, flat_k, sentinel)
        flat_v = jnp.where(flat_m > 0, flat_v, jnp.zeros((), v.dtype))
        uniq, sums, cnts, n_unique = reduce_by_key_local(
            flat_k, flat_v, flat_m
        )
        return uniq, sums, cnts, n_unique[None], max_fill[None]

    mapped = jax.shard_map(
        wordcount_step, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec,) * 5,
    )
    return jax.jit(mapped)


class WordCounter(ExchangeModel):
    """Host-facing reduceByKey(+): returns {key: total}."""

    def __init__(self, mesh: Optional[Mesh] = None, capacity_factor: float = 2.0):
        super().__init__(mesh, capacity_factor)

    def count_device(self, keys: jax.Array, vals: jax.Array,
                     valid: Optional[jax.Array] = None,
                     capacity: Optional[int] = None):
        n = keys.shape[0]
        if n % self.n_devices:
            raise ValueError(f"length {n} not divisible by D={self.n_devices}")
        n_local = n // self.n_devices
        cap = capacity or self._capacity(n_local)
        keys = jax.device_put(keys, self.sharding)
        vals = jax.device_put(vals, self.sharding)
        if valid is None and self.n_devices == 1:
            # every slot real on one device: validity-free sort
            step = make_count_step(
                self.mesh, n_local, cap, with_validity=False
            )
            return step(keys, vals), cap
        step = make_count_step(self.mesh, n_local, cap)
        if valid is None:
            valid = jnp.ones(n, jnp.int32)
        valid = jax.device_put(valid, self.sharding)
        return step(keys, vals, valid), cap

    def count(self, keys, vals=None) -> Dict[int, int]:
        """Totals wrap in the value dtype on overflow (JVM Int/Long
        parity — Spark's reduceByKey(_+_) over Int wraps identically)."""
        keys = np.asarray(keys)
        tracer = get_tracer()
        with tracer.span("shuffle.device.count", rows=keys.size):
            # a returned entry is (key, total): the first two row columns
            rows, nu = self._run_padded_keyed(keys, vals, make_count_step,
                                              result_cols=2)
            if rows is None:
                return {}
            uniq_h, sums_h, counts_h = rows
            out: Dict[int, int] = {}
            with tracer.span("shuffle.device.stitch"):
                for d in range(self.n_devices):
                    # results live at run-end positions: extract by
                    # counts > 0
                    mask = counts_h[d] > 0
                    for k, s in zip(uniq_h[d][mask], sums_h[d][mask]):
                        out[int(k)] = int(s)
            return out
