"""Ring exchange: ppermute-based alternative data plane.

Two reasons this exists alongside the all_to_all engine
(sparkrdma_tpu.parallel.exchange):

1. **Memory ceiling.**  An all_to_all round holds every peer's tile at
   once (D × tile per chip).  The ring moves one neighbor-hop per step
   (``ppermute`` shift by 1), so peak exchange memory is 2 × tile per
   chip regardless of D — the knob that lets shuffles larger than HBM
   stream through, the way the reference's ``maxBytesInFlight`` window
   bounds NIC buffer usage (RdmaShuffleFetcherIterator.scala:241-251).

2. **Sequence/context parallelism.**  Ring attention and ring
   sequence-parallel schedules are exactly this communication pattern:
   each chip consumes one remote shard per step while computing on the
   previous one.  ``ring_exchange_step`` is the reusable primitive; the
   shuffle data plane and a ring-attention consumer share it.

After D-1 hops every chip has seen every source shard once; a consumer
callback receives ``(source_index, shard)`` per hop and never needs the
whole exchange resident.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.parallel.mesh import EXCHANGE_AXIS, make_mesh


def ring_shift(x: jax.Array, axis_name: str = EXCHANGE_AXIS) -> jax.Array:
    """One ring hop: device i's block goes to device (i+1) mod D.
    Must run inside shard_map/pjit over the mesh axis."""
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


@functools.lru_cache(maxsize=1)
def supports_pallas_partition_id() -> bool:
    """Can this backend compile the ring-attention schedule's hot
    pattern — ``jax.lax.axis_index`` feeding a Pallas kernel's block
    offsets inside a ``lax.scan`` over ring hops?

    ``axis_index`` under SPMD lowers to a ``PartitionId`` HLO; the CPU
    backend's SPMD partitioner rejects the instruction when the scan
    keeps it alive past DCE ("PartitionId instruction is not supported
    for SPMD partitioning"), which was a documented seed failure of the
    pallas ring test.  Probed ONCE by compiling a miniature (D=2,
    8×128) replica of exactly that pattern; callers route to the
    data-carried device-index fallback when it answers False.  A
    1-device process has no SPMD partitioning to trip — True.  On a
    TPU backend the pattern must compile: a failure there raises."""
    if len(jax.devices()) < 2:
        return True
    from sparkrdma_tpu.ops.attention import block_attention

    mesh = make_mesh(2)
    spec = P(EXCHANGE_AXIS, None, None)

    def body(q_):
        q = q_[0]
        my = jax.lax.axis_index(EXCHANGE_AXIS)

        def step(carry, j):
            k = carry
            _m, _l, o = block_attention(
                q, k, k, q_offset=my * 8, k_offset=((my - j) % 2) * 8,
                causal=False, scale=0.5, impl="pallas",
            )
            return ring_shift(k), o

        _, outs = jax.lax.scan(step, q, jnp.arange(2))
        return outs.sum(0)[None]

    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )
    # 8×128: lane-aligned so the probe also compiles on real TPU
    # backends (where it should answer True, keeping the native path)
    x = jnp.zeros((2, 8, 128), jnp.float32)
    probe = jax.jit(mapped)
    if jax.default_backend() == "tpu":
        probe(x).block_until_ready()
        return True
    try:
        probe(x).block_until_ready()
    except Exception:
        return False
    return True


@functools.lru_cache(maxsize=32)
def _ring_scan_fn(mesh: Mesh, n_local_shape, dtype_str: str, reverse: bool):
    """Jitted full-ring pass: returns [D, ...] where slot j holds the
    shard originating at device (i - j) mod D (i = my index) — i.e. the
    scan collects every source's shard at every device in D steps."""
    D = len(list(mesh.devices.flat))
    spec = P(EXCHANGE_AXIS)

    def body(x):  # local shard [1, ...] under shard_map of [D, ...]
        shard = x[0]

        def step(carry, _):
            nxt = ring_shift(carry) if not reverse else _ring_shift_back(carry)
            return nxt, carry

        _, seen = jax.lax.scan(step, shard, None, length=D)
        # seen[j] = shard after j hops = block of source (i - j) mod D
        return seen[None]  # [1, D, ...]

    mapped = jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)
    return jax.jit(mapped)


def _ring_shift_back(x: jax.Array, axis_name: str = EXCHANGE_AXIS) -> jax.Array:
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i - 1) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


class RingExchange:
    """Ring data plane over the exchange mesh."""

    def __init__(self, mesh: Optional[Mesh] = None):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_devices = len(list(self.mesh.devices.flat))
        self.sharding = NamedSharding(self.mesh, P(EXCHANGE_AXIS))

    def all_shards(self, x: jax.Array, reverse: bool = False) -> jax.Array:
        """Ring-collect: input [D, ...] sharded on axis 0; output
        [D, D, ...] where out[i, j] = shard of source (i - j) mod D —
        every device ends holding all shards, having moved only one
        shard per hop (an all_gather that never exceeds 2 shards of
        in-flight memory)."""
        if x.shape[0] != self.n_devices:
            raise ValueError(
                f"leading dim {x.shape[0]} != D={self.n_devices}"
            )
        fn = _ring_scan_fn(
            self.mesh, tuple(x.shape[1:]), str(x.dtype), reverse
        )
        x = jax.device_put(x, self.sharding)
        return fn(x)

    def ring_reduce(
        self, x: jax.Array, init_fn: Callable, consume: Callable
    ) -> jax.Array:
        """Streaming consume: fold ``consume(acc, src_index, shard)``
        over every source's shard without ever materializing [D, D, ...].

        ``init_fn(local_shard) -> acc`` builds the accumulator;
        ``consume(acc, src_index, shard) -> acc`` folds one hop.  Runs
        as one jitted scan — the ring-attention-shaped schedule.

        The jitted program is cached on (mesh, shape, dtype, init_fn,
        consume) — callables compare by identity, so pass the SAME
        function objects across calls to reuse the compilation.
        """
        fn = _ring_reduce_fn(
            self.mesh, tuple(x.shape[1:]), str(x.dtype), init_fn, consume
        )
        return fn(jax.device_put(x, self.sharding))


@functools.lru_cache(maxsize=32)
def _ring_reduce_fn(mesh: Mesh, shard_shape, dtype_str: str,
                    init_fn: Callable, consume: Callable):
    """Cached jitted ring_reduce program (mirrors _ring_scan_fn; without
    this every call would pay a fresh XLA compile)."""
    D = len(list(mesh.devices.flat))
    spec = P(EXCHANGE_AXIS)

    def body(x):
        shard = x[0]
        my = jax.lax.axis_index(EXCHANGE_AXIS)

        def step(carry, j):
            acc, cur = carry
            src = (my - j) % D
            acc = consume(acc, src, cur)
            return (acc, ring_shift(cur)), None

        (acc, _), _ = jax.lax.scan(
            step, (init_fn(shard), shard), jnp.arange(D)
        )
        return jax.tree.map(lambda a: a[None], acc)

    mapped = jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)
    return jax.jit(mapped)
