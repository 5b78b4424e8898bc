"""Compile the scheduler's device programs for a described TPU v5e chip.

Nothing runs: each program is lowered from shapes alone and compiled by the
TPU compiler for a chip described with ``topologies.get_topology_desc``, so
what Mosaic or XLA would refuse on the chip (block tiling, fast-memory
limits) fails here at no chip time. Sizes are the sweeps' largest: N =
65,536 nodes, P = 64 pods, block 2,048 (the grid kernel at S = 512 schemes,
N = 1,024), and the fused jax round at K = 3 kinds, N = 5,000, P = 256.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

N, P, BLOCK = 65_536, 64, 2_048
C_PAD = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> a ShapeDtypeStruct on one described chip,
    with JAX's persistent compile cache off for the module: executables
    compiled for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _single(shape):
    from repro.kernels import topsis_pallas as tp
    small = shape((C_PAD, 1))
    return tp.topsis_closeness_blocks.lower(
        shape((C_PAD, N)), small, small, small, small, block_n=BLOCK,
        interpret=False)


def _kinds(shape, k=3):
    from repro.kernels import topsis_pallas as tp
    small = shape((P, C_PAD, 1))
    return tp.topsis_closeness_kinds_blocks.lower(
        shape((P,), jnp.int32), shape((k, C_PAD, N)), small, small, small,
        small, block_n=BLOCK, interpret=False)


def _grid(shape, s=512, n=1_024):
    from repro.kernels import topsis_pallas as tp
    per_scheme = shape((s, P, C_PAD, 1))
    return tp.topsis_closeness_grid_blocks.lower(
        shape((P, C_PAD, n)), shape((P, C_PAD, 1)), per_scheme, per_scheme,
        per_scheme, block_n=n, interpret=False)


@pytest.mark.parametrize("lower", [_single, _kinds, _grid],
                         ids=["single", "kinds", "grid"])
def test_pallas_kernel_compiles_for_v5e(shape, lower):
    compiled = lower(shape).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_jax_round_compiles_for_v5e(shape):
    from repro.core import scheduler
    scheduler._jit_helpers()
    k, n, p, c = 3, 5_000, 256, 5
    compiled = scheduler._closeness_from_kinds.lower(
        shape((k, n, c)), shape((p,), jnp.int32), shape((p, c)),
        shape((c,), jnp.bool_), shape((p, n), jnp.bool_)).compile()
    assert compiled.memory_analysis() is not None
