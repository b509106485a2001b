"""Property-based VecStore scoring tests (optional `hypothesis` dev dep).

The invariant under test is the rerank oracle equivalence behind the whole
tier-transparency story: for ANY padded CSR rows and ANY sparse query —
duplicate query coordinates, pads in arbitrary positions, all-pad rows,
negative values — the sparse rerank (:func:`exact_scores_sparse`, which
both the resident and the tiered path delegate to through
:func:`exact_scores_rows`: a comparison of every stored coordinate with the
query's coordinates, summed over the query) must equal the dense-scatter
oracle ``exact_scores(store, slots, densify_query(...))`` EXACTLY.

Values are drawn as multiples of 1/8 so every partial sum is exact in
float32 — equality failures mean a real matching bug, never
summation-order ULP noise.  ``tests/test_exact_rerank.py`` checks the same
oracle at the serving widths without this optional dependency.
"""

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="optional dev dep; property tests skip without it")
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from repro.storage import vecstore  # noqa: E402

N = 64          # coordinate space — small so duplicates/collisions are common


def _eighths(rng, shape):
    """Exactly-representable values (multiples of 1/8 in [-4, 4])."""
    return (rng.integers(-32, 33, shape) / 8.0).astype(np.float32)


def _store(rng, rows, max_nnz, all_pad_row=False):
    """Padded CSR rows: unique coords per row, pads anywhere (not just
    trailing), optionally one fully padded row."""
    idx = np.full((rows, max_nnz), -1, np.int32)
    val = np.zeros((rows, max_nnz), np.float32)
    for r in range(rows):
        if all_pad_row and r == 0:
            continue
        nnz = int(rng.integers(0, max_nnz + 1))
        pos = rng.choice(max_nnz, nnz, replace=False)   # pads interleave
        idx[r, pos] = rng.choice(N, nnz, replace=False)
        val[r, pos] = _eighths(rng, nnz)
    return vecstore.VecStore(indices=jnp.asarray(idx),
                             values=jnp.asarray(val))


def _query(rng, length, dup_frac):
    """Sparse query with pads anywhere and a controllable duplicate rate."""
    q_idx = np.full(length, -1, np.int32)
    q_val = np.zeros(length, np.float32)
    nnz = int(rng.integers(0, length + 1))
    pos = rng.choice(length, nnz, replace=False)
    coords = rng.choice(N, nnz, replace=True if dup_frac else False)
    if dup_frac and nnz > 1:                        # force real duplicates
        ndup = max(1, int(nnz * dup_frac))
        coords[:ndup] = coords[-1]
    q_idx[pos] = coords
    q_val[pos] = _eighths(rng, nnz)
    return jnp.asarray(q_idx), jnp.asarray(q_val)


@given(seed=st.integers(0, 10_000),
       rows=st.integers(1, 8), max_nnz=st.integers(1, 12),
       qlen=st.integers(1, 12),
       dup_frac=st.sampled_from([0.0, 0.3, 0.9]),
       all_pad_row=st.booleans())
@settings(max_examples=60, deadline=None)
def test_sparse_rerank_equals_dense_oracle(seed, rows, max_nnz, qlen,
                                           dup_frac, all_pad_row):
    rng = np.random.default_rng(seed)
    store = _store(rng, rows, max_nnz, all_pad_row=all_pad_row)
    q_idx, q_val = _query(rng, qlen, dup_frac)
    slots = jnp.asarray(rng.permutation(rows))      # every row, shuffled

    got = vecstore.exact_scores_sparse(store, slots, q_idx, q_val)
    want = vecstore.exact_scores(store, slots,
                                 vecstore.densify_query(N, q_idx, q_val))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if all_pad_row:
        empty_pos = int(np.where(np.asarray(slots) == 0)[0][0])
        assert float(np.asarray(got)[empty_pos]) == 0.0


@given(seed=st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_negative_values_and_empty_query(seed):
    """All-negative corpora score exactly; a fully padded query scores
    everything 0 through both paths."""
    rng = np.random.default_rng(seed)
    store = _store(rng, 4, 8)
    store = store._replace(values=-jnp.abs(store.values))
    q_idx = jnp.full((6,), -1, jnp.int32)
    q_val = jnp.zeros((6,), jnp.float32)
    slots = jnp.arange(4)
    got = vecstore.exact_scores_sparse(store, slots, q_idx, q_val)
    assert np.all(np.asarray(got) == 0.0)

    qi, qv = _query(rng, 8, dup_frac=0.0)
    qv = -jnp.abs(qv)
    got = vecstore.exact_scores_sparse(store, slots, qi, qv)
    want = vecstore.exact_scores(store, slots,
                                 vecstore.densify_query(N, qi, qv))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
