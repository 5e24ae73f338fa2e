"""One-pass Pallas scan kernels for the log-step fill/scan hot loops.

The forward fills and segmented scans in ``ops/segment.py`` /
``models/join.py`` are Hillis–Steele loops over full-length HBM arrays:
~log2(n) passes, each reading and writing every column (3-7 ms per use
at 4M rows — ~40% of a join probe).  They are all instances of one
associative recurrence over (flag, columns) tuples, so ONE sequential
pass can compute them: TPU Pallas grids execute in order, which makes
the classic block-scan-with-carry pattern exact —

  per grid step: load a [C, 128] block (the 1-D column reshaped
  row-major), run the log-step combine IN VMEM (VPU traffic, not HBM),
  fold in the running carry from SMEM-side scratch, write the block,
  update the carry.

HBM traffic drops from O(n log n) to O(n): one read + one write per
column.  Combine kinds:

- ``fill``: forward-fill columns from flagged positions (the probe
  fill of join.py and the run-end carry of segment.py).  Positions
  before the first flag keep an UNSPECIFIED column value with an
  unset output flag — exactly the contract consumers rely on (they
  mask by the returned flag).
- ``add`` / ``min`` / ``max``: inclusive segmented scan with ``flag``
  as segment heads (ops/segment.py ``segmented_scan``).

The kernels are dispatched only on TPU backends; every caller keeps the
jnp log-step path for the CPU, the interpret-mode tests pin kernel
semantics to the jnp reference, and tests/test_chip_compile.py compiles
them for v5e.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows of 128 lanes per grid block: 1024*128 elements = 512 KiB per
# int32 column in VMEM — small enough for several columns + scratch.
BLOCK_ROWS = 1024
_BLOCK = BLOCK_ROWS * LANES

# columns longer than this use the kernel on TPU backends; below it the
# jnp log-step path wins (kernel launch + padding overhead)
MIN_KERNEL_ELEMS = 1 << 16


def use_scan_kernels() -> bool:
    """Kernel dispatch gate: TPU backends only.  Kill switch: set
    SPARKRDMA_TPU_DISABLE_SCAN_KERNELS=1 to force the jnp log-step
    paths (e.g. to bisect a Mosaic lowering issue)."""
    import os

    if os.environ.get("SPARKRDMA_TPU_DISABLE_SCAN_KERNELS"):
        return False
    return jax.default_backend() == "tpu"


def kernel_eligible(*cols) -> bool:
    """Dtype gate for the kernels: 64-bit integer columns (reachable
    only under ``jax_enable_x64``) stay on the jnp log-step paths —
    Mosaic's emulated 64-bit support is not something to bet the
    x64 join path on."""
    return all(np.dtype(c.dtype).itemsize <= 4 for c in cols)


def _identity(kind: str, dtype) -> np.generic:
    dt = np.dtype(dtype)
    if kind == "min":
        return (
            np.array(np.inf, dt) if np.issubdtype(dt, np.floating)
            else np.array(np.iinfo(dt).max, dt)
        )
    if kind == "max":
        return (
            np.array(-np.inf, dt) if np.issubdtype(dt, np.floating)
            else np.array(np.iinfo(dt).min, dt)
        )
    return np.zeros((), dt)  # add / fill


def _combine(kind: str, pf, pxs, cf, cxs):
    """combine(prev_aggregate, current_aggregate) for the (flag, cols)
    recurrence; prev = elements strictly earlier in scan order.  Flags
    are int32 0/1: Mosaic takes no scalar out of a bool block."""
    f = pf | cf
    cf = cf != 0
    if kind == "fill":
        xs = [jnp.where(cf, cx, px) for px, cx in zip(pxs, cxs)]
    elif kind == "add":
        xs = [jnp.where(cf, cx, px + cx) for px, cx in zip(pxs, cxs)]
    elif kind == "min":
        xs = [
            jnp.where(cf, cx, jnp.minimum(px, cx))
            for px, cx in zip(pxs, cxs)
        ]
    elif kind == "max":
        xs = [
            jnp.where(cf, cx, jnp.maximum(px, cx))
            for px, cx in zip(pxs, cxs)
        ]
    else:  # pragma: no cover - guarded by callers
        raise ValueError(f"unknown scan kind {kind!r}")
    return f, xs


def _flat_shift_one(x, s, fill):
    """Shift a [C, L] block by ``s`` positions along the FLATTENED
    row-major order (earlier elements move toward higher indices),
    filling vacated slots with ``fill``.  s must be < C * L."""
    C, L = x.shape
    fill = jnp.asarray(fill, x.dtype)
    rows, lanes = divmod(s, L)
    if rows:
        pad = jnp.full((rows, L), fill, x.dtype)
        x = jnp.concatenate([pad, x[: C - rows]], axis=0)
    if lanes:
        tail = x[:, L - lanes :]
        down = jnp.concatenate(
            [jnp.full((1, lanes), fill, x.dtype), tail[:-1]], axis=0
        )
        x = jnp.concatenate([down, x[:, : L - lanes]], axis=1)
    return x


def _scan_kernel_body(kind, n_cols, idents, flag_ref, *refs):
    col_refs = refs[:n_cols]
    out_flag_ref = refs[n_cols]
    out_refs = refs[n_cols + 1 : 2 * n_cols + 1]
    scr_flag = refs[2 * n_cols + 1]
    scr_cols = refs[2 * n_cols + 2 :]

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        scr_flag[0, 0] = jnp.int32(0)
        for scr, ident in zip(scr_cols, idents):
            scr[0, 0] = jnp.asarray(ident, scr.dtype)

    f = flag_ref[...]
    xs = [r[...] for r in col_refs]
    s = 1
    while s < _BLOCK:
        pf = _flat_shift_one(f, s, 0)
        pxs = [
            _flat_shift_one(x, s, ident)
            for x, ident in zip(xs, idents)
        ]
        f, xs = _combine(kind, pf, pxs, f, xs)
        s <<= 1
    # fold the running carry (aggregate of every element before this
    # block) in as "prev" for the whole block
    cf = jnp.full_like(f, scr_flag[0, 0])
    cxs = [
        jnp.full_like(x, scr[0, 0]) for x, scr in zip(xs, scr_cols)
    ]
    f, xs = _combine(kind, cf, cxs, f, xs)
    out_flag_ref[...] = f
    for out, x in zip(out_refs, xs):
        out[...] = x
    scr_flag[0, 0] = f[BLOCK_ROWS - 1, LANES - 1]
    for scr, x in zip(scr_cols, xs):
        scr[0, 0] = x[BLOCK_ROWS - 1, LANES - 1]


@functools.partial(
    jax.jit, static_argnames=("kind", "dtypes", "n_pad", "interpret")
)
def _scan_padded(kind, dtypes, n_pad, interpret, flag_i32, *cols):
    """Run the kernel over already padded/reshaped [R, 128] arrays."""
    n_cols = len(cols)
    idents = tuple(_identity(kind, dt) for dt in dtypes)
    R = flag_i32.shape[0]
    grid = (R // BLOCK_ROWS,)
    blk = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    kernel = functools.partial(_scan_kernel_body, kind, n_cols, idents)
    # under shard_map the outputs vary over the same mesh axes as the
    # inputs; out_shape must carry that annotation explicitly
    vma = jax.typeof(flag_i32).vma
    out_flag, *out_cols = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[blk] * (1 + n_cols),
        out_specs=[blk] * (1 + n_cols),
        out_shape=[jax.ShapeDtypeStruct(flag_i32.shape, jnp.int32, vma=vma)]
        + [jax.ShapeDtypeStruct(c.shape, c.dtype, vma=vma) for c in cols],
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.int32)]
        + [pltpu.SMEM((1, 1), np.dtype(dt)) for dt in dtypes],
        interpret=interpret,
    )(flag_i32, *cols)
    return out_flag, out_cols


def cumsum_1d(vals: jax.Array) -> jax.Array:
    """``jnp.cumsum`` through the one-pass add kernel on TPU backends
    (XLA lowers cumulative ops to logarithmic passes too); jnp
    elsewhere or below the size threshold."""
    n = int(vals.shape[0])
    if (n >= MIN_KERNEL_ELEMS and kernel_eligible(vals)
            and use_scan_kernels()):
        _f, (out,) = scan_flagged(
            "add", jnp.zeros(n, bool), (vals,)
        )
        return out
    return jnp.cumsum(vals)


def scan_flagged(
    kind: str,
    flag: jax.Array,
    cols: Sequence[jax.Array],
    interpret: bool = False,
) -> Tuple[jax.Array, list]:
    """One-pass (flag, columns) scan over 1-D arrays; see module docs.

    Returns ``(flag_out: bool[n], cols_out)`` with the same semantics
    as the jnp log-step implementations it replaces.  Works inside jit
    (shapes are static); pad/reshape happens in traced ops.
    """
    n = int(flag.shape[0])
    cols = list(cols)
    dtypes = tuple(np.dtype(c.dtype).name for c in cols)
    n_pad = (-n) % _BLOCK
    idents = [_identity(kind, dt) for dt in dtypes]
    f = flag.astype(jnp.int32)
    if n_pad:
        f = jnp.concatenate([f, jnp.zeros(n_pad, jnp.int32)])
        cols = [
            jnp.concatenate(
                [c, jnp.full((n_pad,), ident, c.dtype)]
            )
            for c, ident in zip(cols, idents)
        ]
    f2 = f.reshape(-1, LANES)
    cols2 = [c.reshape(-1, LANES) for c in cols]
    out_flag, out_cols = _scan_padded(
        kind, dtypes, n_pad, interpret, f2, *cols2
    )
    out_flag = out_flag.reshape(-1)[:n] != 0
    outs = [c.reshape(-1)[:n] for c in out_cols]
    return out_flag, outs
