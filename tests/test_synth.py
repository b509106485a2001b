"""The vectorized corpus draw (``synth.sample_sparse_bulk``) follows the law
of the per-vector draw ``sample_sparse_batch``."""

import numpy as np
import pytest

from repro.data import synth


def _check_layout(idx, val, spec, pad):
    valid = idx >= 0
    counts = valid.sum(1)
    assert idx.shape == val.shape and idx.dtype == np.int32
    assert val.dtype == np.float32
    assert counts.min() >= 1 and counts.max() <= pad
    # valid coordinates first, then padding (-1, value 0)
    assert np.array_equal(valid, np.arange(pad)[None] < counts[:, None])
    assert np.all(val[~valid] == 0.0) and np.all(val[valid] != 0.0)
    assert idx[valid].max() < spec.n
    # strictly ascending, hence distinct, coordinates per vector
    steps = np.diff(np.where(valid, idx, spec.n + np.arange(pad)), axis=1)
    assert np.all(steps > 0)
    if spec.nonneg:
        assert val.min() >= 0.0


@pytest.mark.parametrize("spec,pad", [(synth.SPLADE_LIKE, 128),
                                      (synth.G100, 160)],
                         ids=["splade_like", "g100"])
def test_bulk_draw_matches_loop_draw_law(spec, pad):
    n = 2000
    idx, val = synth.sample_sparse_bulk(3, spec, n, spec.psi_doc, pad,
                                        chunk=512)
    _check_layout(idx, val, spec, pad)
    ref_idx, ref_val = synth.sample_sparse_batch(3, spec, n, spec.psi_doc,
                                                 pad)
    mean, ref_mean = (idx >= 0).sum(1).mean(), (ref_idx >= 0).sum(1).mean()
    assert abs(mean - ref_mean) < 0.03 * ref_mean
    vals, ref_vals = val[idx >= 0], ref_val[ref_idx >= 0]
    assert abs(vals.mean() - ref_vals.mean()) < 0.05 * abs(ref_vals).mean()
    if spec.activation == "zipf":
        # the head of the Zipf law is shared: coordinate 0 is the most active
        hits = np.bincount(idx[idx >= 0], minlength=spec.n)
        assert hits.argmax() == 0


def test_bulk_draw_is_deterministic_in_the_seed():
    spec = synth.SPLADE_LIKE
    a = synth.make_corpus_bulk(7, spec, 300, pad=128)
    b = synth.make_corpus_bulk(7, spec, 300, pad=128)
    c = synth.make_corpus_bulk(8, spec, 300, pad=128)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    q = synth.make_queries_bulk(7, spec, 64, pad=64)
    _check_layout(*q, spec, 64)
    assert not np.array_equal(q[0], a[0][:64, :64])
