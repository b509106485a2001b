"""Vector storage (the "S" box of the paper's Figure 1).

The vector database keeps every active vector in raw (exactly recoverable)
form; the retrieval engine fetches candidates from it for the exact rerank of
Algorithm 7.  On TPU the natural representation is **padded CSR** over slots:

    indices : int32[C, P]   active coordinates, padded with -1
    values  : f32/bf16[C, P]

Fetching k' candidates is a row gather; the exact rerank then matches each
row's coordinates against the sparse query by comparison
(:func:`exact_scores_rows`).  Against a dense query, exact inner products
are a gather of ``q_dense[indices]`` plus a masked dot; that primitive
scanned over *all* slots is the TPU-native exact LinScan
("document-ordered scan"; see DESIGN.md §2).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array


class VecStore(NamedTuple):
    indices: Array   # int32[C, P], pad = -1
    values: Array    # [C, P]

    @property
    def capacity(self) -> int:
        return self.indices.shape[0]

    @property
    def max_nnz(self) -> int:
        return self.indices.shape[1]


def empty(capacity: int, max_nnz: int, dtype=jnp.float32) -> VecStore:
    return VecStore(
        indices=jnp.full((capacity, max_nnz), -1, dtype=jnp.int32),
        values=jnp.zeros((capacity, max_nnz), dtype=dtype),
    )


def write(store: VecStore, slot, idx: Array, val: Array) -> VecStore:
    return VecStore(
        indices=store.indices.at[slot].set(idx),
        values=store.values.at[slot].set(val.astype(store.values.dtype)),
    )


def erase(store: VecStore, slot) -> VecStore:
    return VecStore(
        indices=store.indices.at[slot].set(-1),
        values=store.values.at[slot].set(0),
    )


def densify_query(n: int, q_idx: Array, q_val: Array) -> Array:
    """Scatter a padded sparse query into a dense R^n vector."""
    valid = q_idx >= 0
    safe = jnp.where(valid, q_idx, 0)
    contrib = jnp.where(valid, q_val.astype(jnp.float32), 0.0)
    return jnp.zeros((n,), jnp.float32).at[safe].add(contrib, mode="drop")


def exact_scores_rows(idx: Array, val: Array, q_idx: Array,
                      q_val: Array) -> Array:
    """Exact ⟨q, x⟩ for pre-gathered CSR rows (idx int32[K, P], val [K, P]).

    The row-level Algorithm 7 rerank primitive: both the resident path
    (:func:`exact_scores_sparse`) and the tiered path
    (``TieredVecStore.gather_rows`` → rerank) delegate here, so tiering is
    bit-identical to the resident baseline by construction.

    Each stored coordinate finds its query value by comparison with all ψ_q
    query coordinates, summed over the query axis: O(K·P·ψ_q) compare-selects
    on the vector unit and no gather (on a TPU an element gather costs far
    more than ψ_q compare-selects at every query pad in use).  XLA fuses the
    compare, select and sum into one reduction, so no ``[K, P, ψ_q]`` block
    is written.  Duplicate query coordinates add up inside the sum (the
    total densify_query's scatter-add gives); a negative coordinate on
    either side is a pad, and the query's pads carry no value, so no pad
    adds anything.  f32[K].
    """
    val = val.astype(jnp.float32)
    q_val = jnp.where(q_idx >= 0, q_val.astype(jnp.float32), 0.0)
    hit = idx[..., None] == q_idx                    # [K, P, ψ_q], fused
    qd = jnp.sum(jnp.where(hit, q_val, 0.0), axis=-1)    # [K, P]
    return jnp.sum(qd * val, axis=-1)


def exact_scores_sparse(store: VecStore, slots: Array, q_idx: Array,
                        q_val: Array) -> Array:
    """Exact ⟨q, x_s⟩ for the given slots WITHOUT densifying the query.

    The Algorithm 7 rerank used by every scoring backend: gathers only the
    k' candidate CSR rows and matches their coordinates against the sparse
    query by comparison (:func:`exact_scores_rows`) — O(k'·P·ψ_q)
    compare-selects, no gather per coordinate and no R^n scatter, so a
    batched rerank never allocates a ``[B, n]`` dense query block.
    Duplicate query coordinates add up (the same result densify_query's
    scatter-add produces).  f32[len(slots)].
    """
    return exact_scores_rows(store.indices[slots], store.values[slots],
                             q_idx, q_val)


def exact_scores(store: VecStore, slots: Array, q_dense: Array) -> Array:
    """Exact ⟨q, x_s⟩ for the given slots (Algorithm 7 rerank). f32[len(slots)]."""
    idx = store.indices[slots]                       # [K, P]
    val = store.values[slots].astype(jnp.float32)    # [K, P]
    valid = idx >= 0
    qv = q_dense[jnp.where(valid, idx, 0)]           # [K, P]
    return jnp.sum(jnp.where(valid, qv * val, 0.0), axis=-1)


def exact_scores_all(store: VecStore, q_dense: Array) -> Array:
    """Exact scores for every slot — the TPU-native exact LinScan. f32[C]."""
    valid = store.indices >= 0
    qv = q_dense[jnp.where(valid, store.indices, 0)]
    return jnp.sum(jnp.where(valid, qv * store.values.astype(jnp.float32), 0.0),
                   axis=-1)
