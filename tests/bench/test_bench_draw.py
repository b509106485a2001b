"""The benchmark's synthetic law gives every vector the non-zeros it is
dealt: ψ distinct coordinates in sorted order, ψ ~ Poisson(ψ̄), so the mean
width is the configuration's ψ̄, also where Zipf activations repeat the
head coordinates; and the same seed gives the same vectors."""

import os
import sys
from pathlib import Path

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402

from bench import catalog, draw  # noqa: E402


def _law(config: str) -> tuple:
    cfg = catalog._load_json(catalog.BENCH_DIR / "configs" / f"{config}.json")
    return draw.Law.from_config(cfg), cfg["index"]


@pytest.mark.parametrize("config,side", [
    ("splade_msmarco_f32", "doc"), ("splade_msmarco_f32", "query"),
    ("g100_f32", "doc")])
def test_vectors_carry_the_stated_width(config, side):
    law, ix = _law(config)
    psi = law.psi_doc if side == "doc" else law.psi_query
    pad = ix["doc_pad"] if side == "doc" else ix["query_pad"]
    cdf = jax.numpy.asarray(draw.coord_cdf(law))
    idx, val = draw.draw_chunk(draw.seed_key(2**32 + 5, 1), cdf, law, 2048,
                               psi, pad)
    idx, val = np.asarray(idx), np.asarray(val)
    nnz = (idx >= 0).sum(axis=1)
    # the mean of 2048 Poisson(ψ̄) counts lies within 4 standard errors
    assert abs(nnz.mean() - psi) < 4 * np.sqrt(psi / len(nnz))
    assert nnz.max() < pad
    for row, v, k in zip(idx, val, nnz):
        assert (row[:k] >= 0).all() and (row[k:] == -1).all()
        assert (np.diff(row[:k]) > 0).all()          # distinct, sorted
        assert (v[:k] != 0).all() and (v[k:] == 0).all()
    if law.nonneg:
        assert (val >= 0).all()


def test_same_seed_same_corpus():
    law, ix = _law("splade_msmarco_f32")
    a = draw.corpus_chunks(7, law, 512, ix["doc_pad"], 256, jax.devices()[:1])
    b = draw.corpus_chunks(7, law, 512, ix["doc_pad"], 256, jax.devices()[:1])
    c = draw.corpus_chunks(8, law, 512, ix["doc_pad"], 256, jax.devices()[:1])
    for (_, ia, va), (_, ib, vb) in zip(a, b):
        assert np.array_equal(ia, ib) and np.array_equal(va, vb)
    assert not np.array_equal(a[0][1], c[0][1])
