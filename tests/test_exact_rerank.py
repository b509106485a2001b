"""The exact rerank at the serving widths equals the dense-scatter oracle.

The batched rerank (``jax.vmap(vecstore.exact_scores_rows)``, which the
resident, tiered and sharded searches all run) must give exactly
``exact_scores(store, slots, densify_query(...))`` for rows and queries at
the two cells' widths: k' rows of P = 176 coordinates against a query pad
of 96 (SPLADE-like text) and rows of P = 160 against a query pad of 160
(G100).  Pads interleave on both sides.  Values are multiples of 1/8, so
every partial sum is exact in float32 and an inequality is a matching
fault, never summation order.  Needs no optional dependency.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.storage import vecstore

N = 4096        # coordinate space
B, K = 2, 48    # queries, candidate rows per query
CASES = ["duplicates", "query_longer_than_row", "all_pad_query",
         "negative_values"]


def _eighths(rng, shape, signed):
    lo = -16 if signed else 1
    return (rng.integers(lo, 17, shape) / 8.0).astype(np.float32)


def _query(rng, width, case):
    """One padded query: its coordinates (pads -1, interleaved) and values.
    Pads carry junk values, which must add nothing."""
    q_idx = np.full(width, -1, np.int32)
    q_val = _eighths(rng, width, signed=True)
    if case == "all_pad_query":
        return q_idx, q_val
    nnz = width if case == "query_longer_than_row" else width * 2 // 3
    coords = rng.choice(N, nnz, replace=False)
    if case == "duplicates":                 # a third repeat a few coords
        coords[: nnz // 3] = rng.choice(coords[nnz // 3:], nnz // 3)
    q_idx[rng.choice(width, nnz, replace=False)] = coords
    q_val[q_idx >= 0] = _eighths(rng, nnz, signed=case == "negative_values")
    return q_idx, q_val


def _rows(rng, width, q_coords, case):
    """K padded CSR rows, about half of each row's coordinates shared with
    the query; one row is all pads.  Pads carry junk values here too."""
    idx = np.full((K, width), -1, np.int32)
    val = _eighths(rng, (K, width), signed=True)
    q_coords = np.unique(q_coords[q_coords >= 0])
    rest = np.setdiff1d(np.arange(N), q_coords)
    short = case == "query_longer_than_row"
    for k in range(1, K):
        nnz = int(rng.integers(1, width // 8 if short else width + 1))
        shared = rng.choice(q_coords, min(len(q_coords), nnz // 2),
                            replace=False)
        coords = np.concatenate(
            [shared, rng.choice(rest, nnz - len(shared), replace=False)])
        pos = rng.choice(width, nnz, replace=False)
        idx[k, pos] = coords
        val[k, pos] = _eighths(rng, nnz, signed=case == "negative_values")
    return idx, val


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("P,W", [(176, 96), (160, 160)],
                         ids=["splade", "g100"])
def test_batched_rerank_equals_dense_oracle(P, W, case):
    rng = np.random.default_rng([P, W, CASES.index(case)])
    queries = [_query(rng, W, case) for _ in range(B)]
    rows = [_rows(rng, P, qi, case) for qi, _ in queries]
    q_idx = jnp.asarray(np.stack([qi for qi, _ in queries]))
    q_val = jnp.asarray(np.stack([qv for _, qv in queries]))
    idx = jnp.asarray(np.stack([ri for ri, _ in rows]))
    val = jnp.asarray(np.stack([rv for _, rv in rows]), jnp.bfloat16)

    got = np.asarray(jax.jit(jax.vmap(vecstore.exact_scores_rows))(
        idx, val, q_idx, q_val))
    for b in range(B):
        store = vecstore.VecStore(indices=idx[b], values=val[b])
        want = vecstore.exact_scores(
            store, jnp.arange(K),
            vecstore.densify_query(N, q_idx[b], q_val[b]))
        np.testing.assert_array_equal(got[b], np.asarray(want))
    assert np.all(got[:, 0] == 0.0)                  # the all-pad row
    if case == "all_pad_query":
        assert np.all(got == 0.0)
    else:
        assert np.count_nonzero(got) > B * (K - 1) // 2
    if case == "negative_values":
        assert np.any(got < 0) and np.any(got > 0)
