"""Pallas TPU embedding-bag kernel (gather + weighted segment reduce).

JAX has no native ``nn.EmbeddingBag``; the recsys substrate builds it here.
The table lives in HBM and is far too large for VMEM, so the kernel uses the
canonical Pallas-TPU gather idiom: the grid walks the flattened (bag, feature)
space and the *table's BlockSpec index_map reads the feature id from a
scalar-prefetch operand*, so each grid step DMAs exactly one embedding row
``(1, D)`` into VMEM.  The output block revisits the same bag row for F
consecutive steps, initialising on the first and accumulating in place.

Padded feature slots carry weight 0 and a clamped index of 0 — they fetch row
0 and add nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, table_ref, w_ref, out_ref, *, F: int):
    i = pl.program_id(0)
    f = i % F

    @pl.when(f == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[0, f]
    out_ref[0, :] += table_ref[0, :].astype(jnp.float32) * w


@functools.partial(jax.jit, static_argnames=("interpret",))
def embed_bag(
    table: jax.Array,        # [V, D]
    indices: jax.Array,      # int32[B, F]  (pad = -1)
    weights: jax.Array,      # f32[B, F]    (0 at padded slots)
    *,
    interpret: bool,
) -> jax.Array:
    """Weighted-sum bags f32[B, D]."""
    B, F = indices.shape
    V, D = table.shape
    safe = jnp.where(indices >= 0, indices, 0).reshape(-1)       # [B*F]
    w = jnp.where(indices >= 0, weights, 0.0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * F,),
        in_specs=[
            pl.BlockSpec((1, D), lambda i, idx: (idx[i], 0)),     # table row
            pl.BlockSpec((1, F), lambda i, idx: (i // F, 0)),     # weights row
        ],
        out_specs=pl.BlockSpec((1, D), lambda i, idx: (i // F, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, F=F),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
    )(safe, table, w)
