"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``*_ref`` takes exactly the same (already prepared/padded) operands as
its kernel and is the correctness contract: tests sweep shapes/dtypes and
assert allclose between kernel (interpret mode on CPU; compiled on TPU) and
these references.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def sinnamon_score_ref(
    qv: jax.Array,        # f32[B, L]      query values (sorted, 0-padded)
    rows: jax.Array,      # int32[B, L, h] sketch rows per coordinate (π_o(j))
    qbits: jax.Array,     # uint32[B, L, W] membership words per coordinate
    u: jax.Array,         # [m, C]         upper-bound sketch
    l: Optional[jax.Array],  # [m, C] or None (Sinnamon+)
) -> jax.Array:
    """Upper-bound scores f32[B, C] — dense Algorithm 6."""
    C = u.shape[1]
    uf = u.astype(jnp.float32)
    lf = None if l is None else l.astype(jnp.float32)

    def one_query(qv1, rows1, qbits1):
        def body(t, acc):
            r = rows1[t]                                   # [h]
            ub = jnp.min(uf[r], axis=0)                    # [C]
            lb = jnp.zeros_like(ub) if lf is None else jnp.max(lf[r], axis=0)
            v = qv1[t]
            contrib = jnp.where(v > 0, v * ub, v * lb)
            words = qbits1[t]                              # [W]
            shifts = jnp.arange(32, dtype=jnp.uint32)
            mask = ((words[:, None] >> shifts) & 1).reshape(C).astype(jnp.bool_)
            return acc + jnp.where(mask, contrib, 0.0)

        return jax.lax.fori_loop(0, qv1.shape[0], body,
                                 jnp.zeros((C,), jnp.float32))

    return jax.vmap(one_query)(qv, rows, qbits)


def sinnamon_topk_ref(
    qv: jax.Array,        # f32[B, L]
    rows: jax.Array,      # int32[B, L, h]  (UN-offset: always indexes [0, m))
    qbits: jax.Array,     # uint32[B, L, W]
    gate: jax.Array,      # f32[1, C]: 0 keep / -inf excluded
    u: jax.Array,         # [m, C]
    l: Optional[jax.Array],
    kprime: int,
):
    """Dense oracle for the fused path: score, gate, global lax.top_k.

    Independent formulation: decodes BOTH sketch sides per coordinate and
    where-selects by query sign (the fused path gathers one-sided — the two
    are elementwise identical), sums all coordinate contributions in one
    dense [B, L, C] pass, then takes a global top-k.  Returns
    (vals f32[B, kprime], slots int32[B, kprime]) in lax.top_k order
    (score desc, ties by slot asc) — the contract sinnamon_score_topk (and
    the XLA twin under the same top-k) must reproduce bit-for-bit.
    """
    B, Lq = qv.shape
    C = u.shape[1]
    uf = u.astype(jnp.float32)
    ub = jnp.min(uf[rows], axis=-2)                         # [B, L, C]
    if l is None:
        lb = jnp.zeros_like(ub)
    else:
        lb = jnp.max(l.astype(jnp.float32)[rows], axis=-2)
    contrib = jnp.where(qv[..., None] > 0, qv[..., None] * ub,
                        qv[..., None] * lb)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    mask = ((qbits[..., :, None] >> shifts) & 1).reshape(B, Lq, C) != 0
    s = jnp.sum(jnp.where(mask, contrib, 0.0), axis=1)      # [B, C]
    s = jnp.where(gate == 0.0, s, -jnp.inf)
    vals, slots = jax.lax.top_k(s, kprime)
    return vals, slots.astype(jnp.int32)


def csr_score_ref(
    q_dense: jax.Array,   # f32[n]
    indices: jax.Array,   # int32[C, P], pad = -1
    values: jax.Array,    # [C, P]
) -> jax.Array:
    """Exact scores f32[C] of one dense query against padded-CSR documents."""
    valid = indices >= 0
    safe = jnp.where(valid, indices, 0)
    qv = q_dense[safe]
    return jnp.sum(jnp.where(valid, qv * values.astype(jnp.float32), 0.0),
                   axis=-1)


def embed_bag_ref(
    table: jax.Array,     # [V, D]
    indices: jax.Array,   # int32[B, F], pad = -1
    weights: jax.Array,   # f32[B, F]  (0 at padded positions; mean folded in)
) -> jax.Array:
    """Weighted-sum embedding bag f32[B, D]."""
    valid = indices >= 0
    safe = jnp.where(valid, indices, 0)
    rows = table[safe].astype(jnp.float32)                  # [B, F, D]
    w = jnp.where(valid, weights, 0.0)
    return jnp.einsum("bfd,bf->bd", rows, w)
