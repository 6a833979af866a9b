"""Ahead-of-time compiles of both Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see Mosaic's block-shape
and memory rules; the TPU compiler installed with JAX can, for a chip that
is described rather than attached.  Each case compiles one kernel at one of
the paper's widths (n in {16, 18, 32, 64, 90}) with the default
``tile_size=64, dim_block=32`` and checks that the kernel survived as a
``tpu_custom_call``.  The topology is described inside a fixture: only the
test worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dense_tile import dense_tile_distance
from repro.kernels.distance_tile import tile_pair_distance

os.environ.setdefault("TPU_LOG_DIR", "disabled")

WIDTHS = (16, 18, 32, 64, 90)
TILE, DIM_BLOCK, PAIRS, NUM_TILES = 64, 32, 1024, 4096
KERNELS = {"indexed": tile_pair_distance, "dense": dense_tile_distance}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # an AOT compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("mode", ["counts", "mask"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("n", WIDTHS)
def test_kernel_compiles_for_v5e(one_chip, no_persistent_cache, kernel, mode, n):
    nb = -(-n // DIM_BLOCK)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(
        lambda tiles, tlen, pa, pb, eps: KERNELS[kernel](
            tiles, tlen, pa, pb, eps=eps,
            interpret=False, return_mask=(mode == "mask"),
        )
    )
    compiled = fn.lower(
        spec((NUM_TILES, nb, TILE, DIM_BLOCK), jnp.float32),
        spec((NUM_TILES,), jnp.int32),
        spec((PAIRS,), jnp.int32),
        spec((PAIRS,), jnp.int32),
        spec((), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
