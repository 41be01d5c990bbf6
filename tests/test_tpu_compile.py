"""Ahead-of-time compiles of the push-apply kernels for a described TPU v5e.

Interpret mode never checks Mosaic's block-shape and VMEM rules; the TPU
compiler, which is installed even where no chip is attached, does. These
tests compile ``fused_apply_2d`` and ``fused_update_2d`` at the real
payload sizes — LeNet-5 (62,006 floats, one block), the MLP backend
(379,774, three blocks) and a 10^6-float serving shard (eight blocks) — and
check that the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the workers of a
parallel run must all collect the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_update.kernel import (LANES, fused_apply_2d,
                                               fused_update_2d)
from repro.kernels.fused_update.ops import clamp_block_rows

SIZES = {"lenet5": 62_006, "mlp": 379_774, "shard_1m": 1_000_000}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _operands(n, one_chip, n_scalars):
    block_rows = clamp_block_rows(n)
    per_block = block_rows * LANES
    rows = -(-n // per_block) * per_block // LANES
    arr = jax.ShapeDtypeStruct((rows, LANES), jnp.float32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    return block_rows, (arr, arr, arr) + (scalar,) * n_scalars


@pytest.mark.parametrize("n", SIZES.values(), ids=SIZES.keys())
def test_fused_apply_compiles_for_v5e(one_chip, n):
    block_rows, args = _operands(n, one_chip, 3)
    compiled = jax.jit(lambda *a: fused_apply_2d(
        *a, block_rows=block_rows)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", SIZES.values(), ids=SIZES.keys())
def test_fused_update_compiles_for_v5e(one_chip, n):
    block_rows, args = _operands(n, one_chip, 2)
    compiled = jax.jit(lambda *a: fused_update_2d(
        *a, block_rows=block_rows)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
