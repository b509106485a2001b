"""Each benchmark configuration's search program compiles for a described
TPU v5e.

The search program is the one the front door drives: ``engine.search_batch``
on one chip and ``serving.sharded.make_search_step`` on four, at the
configuration's full sizes and at the widest query-width bucket its traffic
produces (64 coordinates for the SPLADE-like cells, 160 for G100 with its
m = 37 sketch rows per side).  The TPU compiler refuses what interpret mode
accepts (unaligned blocks, too much fast memory), so this catches an
alignment refusal before any chip time is spent.
"""

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[2]


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


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


@pytest.fixture
def tpu_path(monkeypatch):
    """The program picks the compiled kernel only where JAX's backend is a
    TPU; here the backend is the CPU, so steer it for the lowering."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _spec(cfg, capacity):
    from repro.core import engine as eng
    ix = cfg["index"]
    return eng.EngineSpec(
        n=cfg["dataset"]["n"], m=ix["m"], h=ix["h"], capacity=capacity,
        max_nnz=ix["doc_pad"], positive_only=ix["positive_only"],
        dtype=ix["cell_dtype"], value_dtype=ix["store_dtype"])


def _widest(cfg):
    return -(-cfg["index"]["query_pad"] // 32) * 32


def _with_sharding(tree, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings)


@pytest.mark.parametrize("config", ["splade_msmarco_f32", "g100_f32"])
def test_one_chip_search_compiles_for_v5e(config, topo, no_compile_cache,
                                          tpu_path):
    from repro.core import engine as eng
    cfg = _config(config)
    ix = cfg["index"]
    spec = _spec(cfg, cfg["n_docs"])
    one = SingleDeviceSharding(topo.devices[0])
    state = jax.eval_shape(lambda: eng.init(spec))
    state = _with_sharding(state, jax.tree.map(lambda _: one, state))
    B, W = 16, _widest(cfg)
    qi = jax.ShapeDtypeStruct((B, W), jnp.int32, sharding=one)
    qv = jax.ShapeDtypeStruct((B, W), jnp.float32, sharding=one)
    search = jax.jit(eng.search_batch, static_argnums=(1, 4, 5, 6),
                     static_argnames=("score_fn", "backend"))
    compiled = search.lower(state, spec, qi, qv, ix["k"], ix["kprime"], None,
                            None, backend="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()
    used = compiled.memory_analysis()
    assert used.argument_size_in_bytes + used.temp_size_in_bytes < 16e9


def test_four_chip_search_compiles_for_v5e(topo, no_compile_cache, tpu_path):
    from repro.core import engine as eng
    from repro.serving import sharded
    cfg = _config("splade_msmarco_x4_f32")
    ix = cfg["index"]
    chips = cfg["chips"]
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                ("data", "model"))
    local = _spec(cfg, cfg["n_docs"] // chips)
    glob = _spec(cfg, cfg["n_docs"])
    state = jax.eval_shape(lambda: eng.init(glob))
    state = _with_sharding(state, sharded.state_shardings(mesh,
                                                          local.upper_only))
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    B, W = 16, _widest(cfg)
    qi = jax.ShapeDtypeStruct((B, W), jnp.int32, sharding=rep)
    qv = jax.ShapeDtypeStruct((B, W), jnp.float32, sharding=rep)
    step = sharded.make_search_step(mesh, local, k=ix["k"],
                                    kprime_local=ix["kprime"],
                                    backend="pallas")
    compiled = step.lower(state, qi, qv).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text
