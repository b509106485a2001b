"""The scoring kernel and the exact rerank compile for a TPU v5e at the
serving shapes.

Compiles against a described (not attached) v5e chip: the TPU compiler
rejects what interpret mode accepts (unaligned blocks, primitives without a
Mosaic lowering), so these tests guard the chip path without a chip.  Shapes
are the chip smoke's: a batch of 16 queries of 64 coordinates over 2^20
slots, m=64 sketch rows per side; the rerank's are the benchmark cells'.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import sinnamon_score
from repro.storage import vecstore

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


def test_search_program_keeps_its_named_scopes_on_v5e(one_chip,
                                                      no_compile_cache,
                                                      monkeypatch):
    """The named scopes survive the TPU compiler into the op metadata a
    profile reads: the kernel under ``scan``, the rerank's comparison under
    ``rerank``, the operand gathers under ``operands``; the program runs no
    loop."""
    import re

    from repro.core import engine as eng
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    spec = eng.EngineSpec(n=4096, m=16, h=1, capacity=1 << 14, max_nnz=128,
                          positive_only=True, dtype="float32",
                          value_dtype="bfloat16")
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: eng.init(spec)))
    q = [jax.ShapeDtypeStruct((B, 32), dt, sharding=one_chip)
         for dt in (jnp.int32, jnp.float32)]
    search = jax.jit(eng.search_batch, static_argnums=(1, 4, 5, 6),
                     static_argnames=("score_fn", "backend"))
    text = search.lower(state, spec, *q, 10, 128, None, None,
                        backend="pallas").compile().as_text()
    kernel, = [ln for ln in text.splitlines()
               if re.match(r"\s*%tile_scores[.\d]* = ", ln)]
    assert "jit(search_batch)/scan/" in kernel
    assert " while(" not in text
    assert 'op_name="jit(search_batch)/rerank/' in text
    assert 'op_name="jit(search_batch)/operands/' in text


@pytest.mark.parametrize("B,K,P,W", [(16, 800, 176, 96), (16, 400, 160, 160)],
                         ids=["splade", "g100"])
def test_exact_rerank_compiles_without_loop_or_product_on_v5e(
        one_chip, no_compile_cache, B, K, P, W):
    """The batched exact rerank at the two cells' widths (k' rows of P
    coordinates, a query pad of W) compiles to no loop, and its temporaries
    stay below twice the gathered rows' bytes: the ``[B, k', P, W]``
    comparison is fused into its reduction, never written."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.vmap(vecstore.exact_scores_rows)).lower(
        sds((B, K, P), jnp.int32), sds((B, K, P), jnp.bfloat16),
        sds((B, W), jnp.int32), sds((B, W), jnp.float32)).compile()
    assert "while" not in compiled.as_text()
    rows_bytes = B * K * P * (4 + 2)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * rows_bytes
