"""Ahead-of-time compiles of the main path's device programs for a
described (not attached) TPU v5e 2x2, at their real widths.

The TPU compiler is installed with JAX, so it refuses here what the
chip would refuse: an unsupported Mosaic lowering, a program that does
not fit 16 GB of HBM, a collective that cannot be partitioned.  The
topology is described inside a module fixture, never at import time:
only one process may load libtpu, and every xdist worker imports this
file.  Keep these tests in this one file so one worker owns the lock.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _per_device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("kind", ["add", "fill", "max"])
def test_scan_kernel_compiles(one_chip, kind):
    from sparkrdma_tpu.ops.scan_kernels import scan_flagged

    n = 1 << 22
    compiled = jax.jit(
        lambda f, c: scan_flagged(kind, f, (c,))
    ).lower(
        _sds((n,), jnp.bool_, one_chip), _sds((n,), jnp.int32, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sort_pairs_full_compiles(one_chip):
    from sparkrdma_tpu.ops.sort_kernel import sort_pairs_full

    n = 1 << 24
    compiled = jax.jit(sort_pairs_full).lower(
        _sds((n,), jnp.int32, one_chip), _sds((n,), jnp.int32, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _per_device_bytes(compiled) < HBM_BYTES


def test_attention_kernel_compiles(one_chip):
    from sparkrdma_tpu.ops.attention import _pallas_block_attention

    def attend(q, k, v):
        return _pallas_block_attention(
            q, k, v, 0, 0, causal=True, scale=128 ** -0.5,
            block_q=512, block_k=1024, interpret=False,
        )

    x = _sds((4096, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(attend).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("chips,n_local", [(1, 1 << 25), (4, 1 << 24)])
def test_wide_sort_step_compiles(topo, chips, n_local):
    """The HiBench-shaped TeraSort step (4-byte key + 24 int32 payload
    words = 100 B records) fits one chip's HBM at the smoke's sizes,
    and on four chips carries a real all-to-all."""
    from sparkrdma_tpu.models.terasort import TeraSorter, make_wide_sort_step
    from sparkrdma_tpu.parallel.mesh import EXCHANGE_AXIS

    W = 24
    mesh = Mesh(np.array(topo.devices[:chips]), (EXCHANGE_AXIS,))
    cap = TeraSorter(mesh)._capacity(n_local)
    step = make_wide_sort_step(mesh, n_local, W, cap)
    n = chips * n_local
    compiled = step.lower(
        _sds((n,), jnp.int32, NamedSharding(mesh, P(EXCHANGE_AXIS))),
        _sds((n, W), jnp.int32,
             NamedSharding(mesh, P(EXCHANGE_AXIS, None))),
    ).compile()
    if chips > 1:
        assert "all-to-all" in compiled.as_text()
    assert _per_device_bytes(compiled) < HBM_BYTES


def test_count_step_compiles_with_scan_kernels(topo, monkeypatch):
    """The WordCount step as the chip runs it: at 2^26 words its
    cumsums take the Pallas scan kernel, inside shard_map."""
    from sparkrdma_tpu.models.wordcount import WordCounter, make_count_step
    from sparkrdma_tpu.ops import scan_kernels
    from sparkrdma_tpu.parallel.mesh import EXCHANGE_AXIS

    # the described chip is not the default backend, so open the gate
    monkeypatch.setattr(scan_kernels, "use_scan_kernels", lambda: True)
    n = 1 << 26
    mesh = Mesh(np.array(topo.devices[:1]), (EXCHANGE_AXIS,))
    step = make_count_step(
        mesh, n, WordCounter(mesh)._capacity(n), with_validity=False
    )
    x = _sds((n,), jnp.int32, NamedSharding(mesh, P(EXCHANGE_AXIS)))
    compiled = step.lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _per_device_bytes(compiled) < HBM_BYTES
