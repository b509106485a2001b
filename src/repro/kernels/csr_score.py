"""Pallas TPU kernel for exact padded-CSR scoring.

Used twice in the system:
  * TPU-native exact LinScan (document-ordered scan of the whole store);
  * Algorithm 7's exact rerank (same kernel over the gathered k' rows).

The dense query vector (n up to a few hundred thousand → ≤1 MiB fp32) stays
resident in VMEM across all document tiles; each grid step streams a
``(TC, P)`` block of indices/values, gathers ``q[idx]`` and reduces the
masked products along P.  Arithmetic intensity is ~1 FLOP per 6 bytes — this
kernel is memory-bound by design, and its roofline term is the exact-scan
baseline Sinnamon's sketch is compared against in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE_C = 1024


def _kernel(q_ref, idx_ref, val_ref, out_ref):
    qd = q_ref[...]                         # [n] resident
    idx = idx_ref[...]                      # [TC, P]
    val = val_ref[...].astype(jnp.float32)  # [TC, P]
    valid = idx >= 0
    safe = jnp.where(valid, idx, 0)
    gathered = jnp.take(qd, safe, axis=0)   # [TC, P]
    out_ref[...] = jnp.sum(jnp.where(valid, gathered * val, 0.0), axis=-1)


@functools.partial(jax.jit, static_argnames=("tile_c", "interpret"))
def csr_score(
    q_dense: jax.Array,          # f32[n]
    indices: jax.Array,          # int32[C, P]
    values: jax.Array,           # [C, P]
    *,
    tile_c: int = DEFAULT_TILE_C,
    interpret: bool,
) -> jax.Array:
    """Exact scores f32[C] for one query."""
    C, P = indices.shape
    n = q_dense.shape[0]
    if C % tile_c != 0:
        raise ValueError(f"C={C} must be a multiple of tile_c={tile_c}")
    return pl.pallas_call(
        _kernel,
        grid=(C // tile_c,),
        in_specs=[
            pl.BlockSpec((n,), lambda c: (0,)),
            pl.BlockSpec((tile_c, P), lambda c: (c, 0)),
            pl.BlockSpec((tile_c, P), lambda c: (c, 0)),
        ],
        out_specs=pl.BlockSpec((tile_c,), lambda c: (c,)),
        out_shape=jax.ShapeDtypeStruct((C,), jnp.float32),
        interpret=interpret,
    )(q_dense, indices, values)
