#!/usr/bin/env python
"""Long-context sequence-parallel attention throughput.

Measures the ring-attention schedule (Pallas blockwise kernel + ppermute
K/V circulation) on whatever devices are visible, reported as attention
TFLOP/s per chip.  The reference has no model plane — this benchmarks
the long-context capability SURVEY.md §5 marks first-class for the
rebuild; ``vs_baseline`` is vs a 10 TFLOP/s round figure for a
flash-attention CPU/GPU-class single-node baseline of the reference's
2015 hardware era (the README cluster's Xeon E5-2697v3 peaks ~1.2
fp32 TFLOP/s/node).

    python benchmarks/bench_attention.py [seq_len] [n_heads] [d_head] [dtype]
"""

import sys

import jax
import numpy as np

sys.path.insert(0, ".")
from benchmarks.common import emit, time_iters

from sparkrdma_tpu.models.ring_attention import ring_attention
from sparkrdma_tpu.parallel.mesh import make_mesh

BASELINE_TFLOPS = 10.0


def main():
    S = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    H = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    d = int(sys.argv[3]) if len(sys.argv) > 3 else 128
    dtype = sys.argv[4] if len(sys.argv) > 4 else "bfloat16"
    mesh = make_mesh()
    D = len(list(mesh.devices.flat))
    rng = np.random.default_rng(0)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkrdma_tpu.parallel.mesh import EXCHANGE_AXIS

    # place inputs once: steady state keeps activations device-resident
    import jax.numpy as jnp

    sharding = NamedSharding(mesh, P(None, EXCHANGE_AXIS, None))
    q, k, v = (
        jax.device_put(
            jnp.asarray(
                rng.standard_normal((H, S, d)).astype(np.float32),
                dtype=jnp.dtype(dtype),
            ),
            sharding,
        )
        for _ in range(3)
    )

    def run():
        return ring_attention(q, k, v, mesh=mesh, causal=True)

    dt = time_iters(run, iters=10)
    # causal attention: 2 matmuls of S*S/2 * d MACs per head
    flops = 2 * 2 * H * (S * S / 2) * d
    tflops_chip = flops / dt / 1e12 / D
    emit(
        f"ring attention throughput per chip (S={S}, H={H}, d={d}, "
        f"{dtype}, {D} chip(s))",
        tflops_chip, "TFLOP/s/chip", tflops_chip / BASELINE_TFLOPS,
    )


if __name__ == "__main__":
    main()
