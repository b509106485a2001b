"""The scoring kernel compiles for a TPU v5e at the serving shapes.

Compiles against a described (not attached) v5e chip: the TPU compiler
rejects what interpret mode accepts (unaligned blocks, primitives without a
Mosaic lowering), so these tests guard the chip path without a chip.  Shapes
are the chip smoke's: a batch of 16 queries of 64 coordinates over 2^20
slots, m=64 sketch rows per side.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import sinnamon_score

B, L, M, C = 16, 64, 64, 1 << 20
KPRIME = 800


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled.as_text()


@pytest.mark.parametrize("one_sided", [True, False],
                         ids=["one_sided", "upper_only"])
@pytest.mark.parametrize("cell", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_topk_kernel_compiles_for_v5e(one_chip, no_compile_cache, cell,
                                      one_sided):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R = 2 * M if one_sided else M
    shapes = (sds((B, L), jnp.float32), sds((B, L, 1), jnp.int32),
              sds((B, L, C // 32), jnp.uint32), sds((1, C), jnp.float32),
              sds((R, C), cell))

    def step(qv, rows, qbits, gate, skmat):
        return sinnamon_score.sinnamon_score_topk(
            qv, rows, qbits, gate, skmat, kp=KPRIME,
            tile_c=sinnamon_score.DEFAULT_TILE_C, one_sided=one_sided,
            interpret=False)

    assert "tpu_custom_call" in _compiled_text(step, *shapes)


def test_dense_kernel_compiles_for_v5e(one_chip, no_compile_cache):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = (sds((B, L), jnp.float32), sds((B, L, 2), jnp.int32),
              sds((B, L, C // 32), jnp.uint32), sds((M, C), jnp.bfloat16),
              sds((M, C), jnp.bfloat16))

    def step(qv, rows, qbits, u, l):
        return sinnamon_score.sinnamon_score(qv, rows, qbits, u, l,
                                             interpret=False)

    assert "tpu_custom_call" in _compiled_text(step, *shapes)
