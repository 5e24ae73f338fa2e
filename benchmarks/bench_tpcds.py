#!/usr/bin/env python
"""BASELINE config 5 end-to-end: a TPC-DS q64/q72-shaped pipeline.

q64/q72 physical plans chain exchange and broadcast joins over a
star-schema fact table and finish in an aggregation.  The skeleton here
does the same through the device models, chained ENTIRELY on device:

  stage 1: fact ⋈ dim1 (exchange hash join on fk1, payload carries fk2)
  stage 2: result ⋈ dim2 (broadcast join on fk2, payload carries dv1)
  stage 3: aggregateByKey over the surviving rows (sum/count/min/max)

No compaction between stages: each join's ``found`` mask IS the next
stage's validity column (unmatched rows ride along as ROLE_INVALID and
can never join or aggregate), so stage outputs stay device-resident
with static shapes and only a one-element fence touches the host —
the SQL-engine pattern of keeping exchanges on the fabric end to end.

Two variants are reported: the 3-stage pipeline above, and the fused
2-stage pipeline where stages 2+3 run as ONE sort
(models/join_aggregate.py — the group key here is a pure function of
the stage-2 join key, the fusion precondition).  Reported as fact-row
bytes through the full pipeline per second per chip.
"""

import sys

import jax
import numpy as np

sys.path.insert(0, ".")
from benchmarks.common import ROCE_LINE_RATE_GBPS, emit, time_iters

from sparkrdma_tpu.models.aggregate import make_aggregate_step
from sparkrdma_tpu.models.join import (
    HashJoiner,
    make_broadcast_join_step,
    make_hash_join_step,
)
from sparkrdma_tpu.parallel.mesh import make_mesh


def main():
    import functools

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    log2 = int(sys.argv[1]) if len(sys.argv) > 1 else 22
    n_fact = 1 << log2
    n_dim1 = 1 << max(10, log2 - 6)
    n_dim2 = 1 << max(8, log2 - 8)
    mesh = make_mesh()
    rng = np.random.default_rng(21)

    # star schema: fact(fk1, fk2), dim1(k→v), dim2(k→v); ~93% of fact
    # rows survive stage 1 (dim1 keys cover most of fk1's range), the
    # broadcast stage keeps all survivors (dense dim2 keys)
    dim1_keys = np.sort(
        rng.choice(int(n_dim1 * 1.07), n_dim1, replace=False)
    ).astype(np.int32)
    dim1_vals = rng.integers(0, 1 << 31, n_dim1, dtype=np.int32)
    dim2_keys = np.arange(n_dim2, dtype=np.int32)
    dim2_vals = rng.integers(0, 1 << 31, n_dim2, dtype=np.int32)
    fk1 = rng.integers(0, int(n_dim1 * 1.07), n_fact).astype(np.int32)
    fk2 = rng.integers(0, n_dim2, n_fact).astype(np.int32)

    joiner = HashJoiner(mesh, capacity_factor=2.0)
    D = joiner.n_devices
    sh = joiner.sharding
    rep = NamedSharding(mesh, P(None))

    cap1 = joiner._capacity((n_fact + n_dim1) // D, 2.0)
    step1 = make_hash_join_step(mesh, n_fact // D, n_dim1 // D, cap1)
    m1 = (n_fact + n_dim1) if D == 1 else D * D * cap1
    step2 = make_broadcast_join_step(mesh, m1 // D, n_dim2)
    m2 = m1 + D * n_dim2
    cap3 = joiner._capacity(m2 // D, 2.0)
    step3 = make_aggregate_step(mesh, m2 // D, cap3)

    # group-key/value prep between stages 2 and 3, on device
    @functools.partial(
        jax.jit,
        in_shardings=(sh, sh, sh, sh),
        out_shardings=(sh, sh),
    )
    def prep3(sk2, spay2, fval2, found2):
        return (sk2 % jnp.uint32(1024), spay2 ^ fval2)

    lk = jax.device_put(fk1, sh)
    lv = jax.device_put(fk2, sh)
    l_valid = jax.device_put(np.ones(n_fact, np.int32), sh)
    rk1 = jax.device_put(dim1_keys, sh)
    rv1 = jax.device_put(dim1_vals, sh)
    r1_valid = jax.device_put(np.ones(n_dim1, np.int32), sh)
    rk2 = jax.device_put(dim2_keys, rep)
    rv2 = jax.device_put(dim2_vals, rep)
    r2_valid = jax.device_put(np.ones(n_dim2, np.int32), rep)

    def pipeline():
        sk1, spay1, fval1, found1, _isf1, fill1 = step1(
            lk, lv, l_valid, rk1, rv1, r1_valid
        )
        # stage 2: join key = the fk2 payload, value = dim1's value,
        # validity = stage 1's found mask (no compaction)
        sk2, spay2, fval2, found2, _isf2 = step2(
            spay1, fval1, found1, rk2, rv2, r2_valid
        )
        k3, v3 = prep3(sk2, spay2, fval2, found2)
        uniq, sums, counts, mins, maxs, n_unique, fill3 = step3(
            k3, v3, found2
        )
        return counts, fill1, fill3

    # sanity once: no bucket overflow, and the aggregate saw every
    # matched fact row (dim1 covers ~93% of fk1's key space)
    counts, fill1, fill3 = pipeline()
    assert int(np.max(np.asarray(fill1))) <= cap1, "stage-1 overflow"
    assert int(np.max(np.asarray(fill3))) <= cap3, "stage-3 overflow"
    total = int(np.asarray(counts).sum())
    assert total > 0.9 * n_fact, (total, n_fact)

    dt = time_iters(lambda: pipeline()[0], iters=5)
    gbps_chip = n_fact * 8 / dt / 1e9 / D
    emit(
        f"TPC-DS q64/q72-shaped 2-join+aggregate device pipeline per "
        f"chip ({n_fact} fact rows, {D} chip(s))",
        gbps_chip, "GB/s/chip", gbps_chip / ROCE_LINE_RATE_GBPS,
    )

    # fused variant: stages 2+3 in ONE sort (join_aggregate.py); the
    # group key (join key % 1024) is a pure function of the join key
    from sparkrdma_tpu.models.join_aggregate import (
        make_broadcast_join_aggregate_step,
    )

    def gk_fn(ku):
        return ku % jnp.asarray(1024, ku.dtype)

    def val_fn(ku, fact_pay_u, dim_val_u):
        return jax.lax.bitcast_convert_type(
            fact_pay_u ^ dim_val_u, jnp.int32
        )

    step23 = make_broadcast_join_aggregate_step(
        mesh, m1 // D, n_dim2, gk_fn, val_fn
    )

    def pipeline_fused():
        sk1, spay1, fval1, found1, _isf1, fill1 = step1(
            lk, lv, l_valid, rk1, rv1, r1_valid
        )
        gk, sums, counts, mins, maxs, _n = step23(
            spay1, fval1, found1, rk2, rv2, r2_valid
        )
        return counts, fill1

    counts_f, fill1_f = pipeline_fused()
    assert int(np.max(np.asarray(fill1_f))) <= cap1, "stage-1 overflow"
    total_f = int(np.asarray(counts_f).sum())
    assert total_f == total, (total_f, total)

    dt_f = time_iters(lambda: pipeline_fused()[0], iters=5)
    gbps_f = n_fact * 8 / dt_f / 1e9 / D
    emit(
        f"TPC-DS pipeline, fused join+aggregate (ONE sort for stages "
        f"2+3) per chip ({n_fact} fact rows, {D} chip(s))",
        gbps_f, "GB/s/chip", gbps_f / ROCE_LINE_RATE_GBPS,
    )

    # single-dispatch variant: the WHOLE pipeline traced as one XLA
    # program — no per-stage launch, and XLA may fuse across the
    # stage-1 output → stage-2 input boundary
    @functools.partial(
        jax.jit,
        in_shardings=(sh, sh, sh, sh, sh, sh, rep, rep, rep),
    )
    def pipeline_one(lk, lv, l_valid, rk1, rv1, r1_valid,
                     rk2, rv2, r2_valid):
        sk1, spay1, fval1, found1, _isf1, fill1 = step1(
            lk, lv, l_valid, rk1, rv1, r1_valid
        )
        gk, sums, counts, mins, maxs, _n = step23(
            spay1, fval1, found1, rk2, rv2, r2_valid
        )
        return counts, fill1

    counts_1, fill1_1 = pipeline_one(
        lk, lv, l_valid, rk1, rv1, r1_valid, rk2, rv2, r2_valid
    )
    assert int(np.max(np.asarray(fill1_1))) <= cap1, "stage-1 overflow"
    assert int(np.asarray(counts_1).sum()) == total

    dt_1 = time_iters(
        lambda: pipeline_one(
            lk, lv, l_valid, rk1, rv1, r1_valid, rk2, rv2, r2_valid
        )[0],
        iters=5,
    )
    gbps_1 = n_fact * 8 / dt_1 / 1e9 / D
    emit(
        f"TPC-DS pipeline, single-dispatch (whole pipeline = ONE XLA "
        f"program) per chip ({n_fact} fact rows, {D} chip(s))",
        gbps_1, "GB/s/chip", gbps_1 / ROCE_LINE_RATE_GBPS,
    )


if __name__ == "__main__":
    main()
