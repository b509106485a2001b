"""The plain reference against brute force on a small corpus, and its
rounding against the number formats."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import draw, reference  # noqa: E402


def test_bfloat16_directed_rounding_matches_the_value_grid():
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.lognormal(0, 3, 50000),
                        -rng.lognormal(0, 3, 50000)]).astype(np.float32)
    grid = jnp.asarray(reference.grid("bfloat16"))
    for up in (True, False):
        fast = np.asarray(reference.round_directed(jnp.asarray(x), None, up))
        slow = np.asarray(reference.round_directed(jnp.asarray(x), grid, up))
        assert np.array_equal(fast, slow)
        assert np.all(fast >= x) if up else np.all(fast <= x)


@pytest.mark.parametrize("dtype", ["bfloat16", "float8_e4m3fn"])
def test_store_rounding_is_round_to_nearest(dtype):
    x = np.random.default_rng(1).normal(0, 2, 20000).astype(np.float32)
    got = np.asarray(reference.round_store(jnp.asarray(x), dtype))
    want = x.astype(np.dtype(dtype)).astype(np.float32)
    assert np.array_equal(got, want)


def brute_force(idx, val, q_idx, q_val, maps, m, signed, k, kprime, shards):
    """Everything in float64 on the host, document by document."""
    n_docs = len(idx)
    owner = reference.shard_of(np.arange(n_docs), shards)
    out = []
    for qi, qv in zip(q_idx, q_val):
        rows = [(idx[d], val[d]) for d in range(n_docs)]
        ub = np.array([reference.upper_bound(r, qi, qv, maps, m, "bfloat16",
                                             signed) for r in rows])
        ex = np.array([reference.exact_score(r, qi, qv, "bfloat16")
                       for r in rows])
        cands = []
        for s in range(shards):
            mine = np.nonzero(owner == s)[0]
            order = np.lexsort((mine, -ub[mine]))[:kprime]
            cands.extend(mine[order].tolist())
        cands = np.array(cands)
        best = cands[np.argsort(-ex[cands], kind="stable")[:k]]
        out.append(best)
    return np.array(out)


@pytest.mark.parametrize("signed,shards", [(False, 1), (True, 1), (False, 4)])
def test_search_matches_brute_force(signed, shards):
    law = draw.Law(n=300, psi_doc=20, psi_query=12,
                   value_dist="gaussian" if signed else "lognormal",
                   value_param=1.0, nonneg=not signed,
                   activation="uniform" if signed else "zipf")
    chunks = draw.corpus_chunks(11, law, 512, 32, 256, jax.devices()[:1])
    q_idx, q_val = draw.query_pool(11, law, 6, 32)
    m, k, kprime = 16, 5, 40
    ids, scores, _ = reference.search(
        chunks, q_idx, q_val, n=law.n, m=m, h=1, map_seed=7, signed=signed,
        cell_dtype="bfloat16", store_dtype="bfloat16", k=k, kprime=kprime,
        shards=shards)
    idx = np.concatenate([np.asarray(c[1]) for c in chunks])
    val = np.concatenate([np.asarray(c[2]) for c in chunks])
    maps = reference.mappings(7, law.n, m, 1)
    want = brute_force(idx, val, q_idx, q_val, maps, m, signed, k, kprime,
                       shards)
    assert np.array_equal(ids, want)
    rows = reference.doc_rows(chunks, ids.ravel())
    for b in range(len(q_idx)):
        for d, s in zip(ids[b], scores[b]):
            assert s == pytest.approx(reference.exact_score(
                rows[int(d)], q_idx[b], q_val[b], "bfloat16"), rel=1e-5)
