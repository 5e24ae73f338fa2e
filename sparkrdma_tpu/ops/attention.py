"""Blockwise attention kernel (Pallas): the ring-attention hot op.

One call computes the flash-style partial results of attention between
the local queries and ONE circulating K/V block:

    m_blk[i] = max_j s[i, j]                  (row max of masked scores)
    l_blk[i] = Σ_j exp(s[i, j] - m_blk[i])    (unnormalized denominator)
    o_blk[i] = Σ_j exp(s[i, j] - m_blk[i]) v[j]

with ``s = (q @ kᵀ) · scale`` and optional causal masking by global
positions.  The ring step then folds the partials into its running
(m, l, o) accumulator with two exponentials — an EXACT online softmax
(models/ring_attention.py).

Rows fully masked within this block keep ``m_blk = NEG_INF``; their
(garbage) l/o partials are annihilated by the fold's
``exp(m_blk - m_new) = 0`` factor, so no in-kernel special-casing is
needed — but this is why NEG_INF is a large finite number, not -inf
(inf - inf would poison the fold with NaNs).

The Pallas kernel tiles q × k over a 2-D grid, accumulating in VMEM
scratch, scores on the MXU in float32 (pallas_guide.md: MXU matmul +
scratch-accumulator pattern); ``impl="xla"`` is the plain-jnp reference
used on non-TPU backends and in equivalence tests.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _pick_block(n: int, preferred: int) -> int:
    b = min(preferred, n)
    while n % b:
        b -= 1
    return b


def _xla_block_attention(q, k, v, q_offset, k_offset, causal, scale):
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    s = (q32 @ k32.T) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[0])
        k_pos = k_offset + jnp.arange(k.shape[0])
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
    m_blk = jnp.max(s, axis=-1)
    p = jnp.exp(s - m_blk[:, None])
    return m_blk, p.sum(axis=-1), p @ v32


def _kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
            o_ref, m_ref, l_ref, acc, m_s, l_s,
            *, causal: bool, scale: float, block_q: int, block_k: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc[:] = jnp.zeros_like(acc)

    # feed the MXU its native input dtype (bf16×bf16→f32 runs at full
    # rate; an up-front astype(f32) would force the slow fp32 path),
    # accumulate in float32 either way via preferred_element_type
    s = jax.lax.dot_general(
        q_ref[:], k_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    if causal:
        q_pos = qoff_ref[0, 0] + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = koff_ref[0, 0] + j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_s[:, :1]
    l_prev = l_s[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    # PV matmul: cast the probabilities down to V's dtype so bf16 V
    # rides the fast MXU path too (the standard flash-attention trade;
    # f32 V keeps the exact path since the cast is then a no-op)
    acc[:] = acc[:] * alpha + jnp.dot(
        p.astype(v_ref.dtype), v_ref[:], preferred_element_type=jnp.float32
    )
    m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[:] = acc[:]
        m_ref[:] = m_s[:]
        l_ref[:] = l_s[:]


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "block_q", "block_k", "interpret",
    ),
)
def _pallas_block_attention(q, k, v, q_offset, k_offset, *, causal, scale,
                            block_q, block_k, interpret):
    s_q, d = q.shape
    s_k = k.shape[0]
    # under shard_map the outputs vary over the same mesh axes as the
    # inputs; out_shape must carry that annotation explicitly
    vma = jax.typeof(q).vma
    bq = _pick_block(s_q, block_q)
    bk = _pick_block(s_k, block_k)
    grid = (s_q // bq, s_k // bk)
    kernel = functools.partial(
        _kernel, causal=causal, scale=scale, block_q=bq, block_k=bk
    )
    smem = pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                        memory_space=pltpu.SMEM)
    o, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((bq, d), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bq, _LANES), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bq, _LANES), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_q, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((s_q, _LANES), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((s_q, _LANES), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(
        jnp.asarray(q_offset, jnp.int32).reshape(1, 1),
        jnp.asarray(k_offset, jnp.int32).reshape(1, 1),
        q, k, v,
    )
    return m[:, 0], l[:, 0], o


def block_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset,
    k_offset,
    causal: bool = False,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
    block_q: int = 512,
    block_k: int = 1024,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Partial attention of ``q`` [s_q, d] against one K/V block
    [s_k, d].  Returns float32 ``(m_blk [s_q], l_blk [s_q],
    o_blk [s_q, d])``.

    ``impl``: "pallas" (TPU kernel; interpreted elsewhere), "xla"
    (plain jnp), or None = pallas on TPU backends, xla otherwise.

    Default blocks (512, 1024) measure ~98% of the best swept
    configuration for bf16 at d_head=128 on a real chip while keeping
    the f32 score/probability temporaries (block_q x block_k) and
    double-buffered operand blocks comfortably inside the ~16 MB VMEM
    budget even for float32 inputs; (1024, 1024) is marginally faster
    for bf16 but within ~3% and tighter on VMEM.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return _xla_block_attention(q, k, v, q_offset, k_offset, causal, scale)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")
    return _pallas_block_attention(
        q, k, v, q_offset, k_offset, causal=causal, scale=float(scale),
        block_q=block_q, block_k=block_k,
        interpret=jax.default_backend() != "tpu",
    )
