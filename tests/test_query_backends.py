"""ISSUE 4 tentpole contracts.

* The Pallas backend (tiled scoring kernel + top-k; on CPU its XLA twin) returns
  BIT-IDENTICAL ids and exact scores to the reference backend across dirty /
  recycled slots, filter masks, anytime budgets, positive-only mode, bucket
  hashing and non-tile-aligned capacities.
* The vectorized single-dispatch batch mutations reproduce the sequential
  lax.scan oracles leaf-for-leaf.
* External ids are int64 end-to-end: values >= 2**31 survive the engine, the
  sharded locator path and a snapshot round-trip without wrapping.
* QueryServer latency stats are fixed-size registry histograms.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core.engine import EngineSpec, SinnamonIndex
from repro.data import synth
from repro.distributed import mesh as meshlib
from repro.kernels import ops, ref, sinnamon_score
from repro.obs import metrics as obs_metrics
from repro.serving.serve import QueryServer
from repro.serving.sharded import ShardedSinnamonIndex

DS = synth.SparseDatasetSpec("t", n=500, psi_doc=24, psi_query=12,
                             value_dist="gaussian")

SPECS = {
    "plain": dict(m=16, h=2),
    "buckets": dict(m=16, h=1, index_buckets=96),
    "fp32": dict(m=24, h=1, dtype="float32"),
}


def _spec(capacity, **kw):
    return EngineSpec(n=DS.n, capacity=capacity, max_nnz=48,
                      value_dtype="float32", seed=3, **kw)


def _churned_index(spec_kw, n_docs=140, capacity=192, seed=0):
    """Index with real streaming history: inserts, deletes, recycled (dirty)
    slots via re-insert — the state shape the §4.3 paths produce."""
    idx, val = synth.make_corpus(seed, DS, n_docs + 20, pad=48)
    index = SinnamonIndex(_spec(capacity, **spec_kw))
    index.insert_many(list(range(n_docs)), idx[:n_docs], val[:n_docs])
    for d in range(0, n_docs, 7):                   # delete ~1/7th
        index.delete(d)
    extra = list(range(n_docs, n_docs + 20))        # recycle into dirty slots
    index.insert_many(extra, idx[n_docs:], val[n_docs:])
    return index


@pytest.mark.parametrize("spec_kw", list(SPECS.values()),
                         ids=list(SPECS.keys()))
@pytest.mark.parametrize("budget", [None, 5])
def test_pallas_bit_identical_to_reference(spec_kw, budget):
    index = _churned_index(spec_kw)
    qi, qv = synth.make_queries(1, DS, 6, pad=24)
    mask = np.ones(index.spec.capacity, bool)
    mask[::3] = False
    for filt in (None, jnp.asarray(mask)):
        r_ids, r_sc = index.search_many(qi, qv, k=10, kprime=60,
                                        budget=budget, filter_mask=filt,
                                        backend="reference")
        p_ids, p_sc = index.search_many(qi, qv, k=10, kprime=60,
                                        budget=budget, filter_mask=filt,
                                        backend="pallas")
        np.testing.assert_array_equal(r_ids, p_ids)
        np.testing.assert_array_equal(r_sc, p_sc)
        g_ids, g_sc = index.search_many(qi, qv, k=10, kprime=60,
                                        budget=budget, filter_mask=filt,
                                        backend="grouped")
        np.testing.assert_array_equal(r_ids, g_ids)
        np.testing.assert_allclose(r_sc, g_sc, rtol=1e-5, atol=1e-6)


def test_pallas_bit_identical_positive_only():
    ds = dataclasses.replace(DS, nonneg=True, value_dist="lognormal",
                             value_param=0.5)
    idx, val = synth.make_corpus(11, ds, 128, pad=48)
    spec = EngineSpec(n=ds.n, m=16, capacity=128, max_nnz=48, h=1,
                      positive_only=True, value_dtype="float32")
    index = SinnamonIndex(spec)
    index.insert_many(list(range(128)), idx, val)
    qi, qv = synth.make_queries(12, ds, 6, pad=24)
    r_ids, r_sc = index.search_many(qi, qv, k=10, kprime=60,
                                    backend="reference")
    p_ids, p_sc = index.search_many(qi, qv, k=10, kprime=60,
                                    backend="pallas")
    np.testing.assert_array_equal(r_ids, p_ids)
    np.testing.assert_array_equal(r_sc, p_sc)


def test_pallas_identical_at_odd_capacity_after_grow():
    """grow() to a non-tile-aligned capacity: the wrappers pad the slot axis
    and gate the padding to -inf, so every backend still agrees exactly —
    including k' = full capacity where the -inf tail is part of the result."""
    index = _churned_index(SPECS["plain"], n_docs=100, capacity=128)
    index.grow(224)                                 # not a tile multiple
    qi, qv = synth.make_queries(3, DS, 4, pad=24)
    for kprime in (60, 224):
        r_ids, r_sc = index.search_many(qi, qv, k=12, kprime=kprime,
                                        backend="reference")
        p_ids, p_sc = index.search_many(qi, qv, k=12, kprime=kprime,
                                        backend="pallas")
        np.testing.assert_array_equal(r_ids, p_ids)
        np.testing.assert_array_equal(r_sc, p_sc)


def test_kernel_wrappers_pad_and_slice_odd_capacity():
    """Direct wrapper calls at an odd (post-grow) capacity with an explicit
    tile size that does NOT divide C: both the dense and the fused wrapper
    must pad-and-slice rather than raise."""
    index = _churned_index(SPECS["plain"], n_docs=100, capacity=128)
    index.grow(160)
    qi, qv = synth.make_queries(4, DS, 3, pad=24)
    qvp, rows, qbits = ops.prepare_query_operands(
        index.state, jnp.asarray(qi), jnp.asarray(qv), spec=index.spec)
    dense = ops.sinnamon_score_batch(index.state, qvp, rows, qbits,
                                     tile_c=128)
    assert dense.shape == (3, 160)
    want = eng.score_batch(index.state, index.spec, jnp.asarray(qi),
                           jnp.asarray(qv))
    np.testing.assert_allclose(np.asarray(dense), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    vals, slots = ops.sinnamon_topk_batch(index.state, index.spec,
                                          jnp.asarray(qi), jnp.asarray(qv),
                                          40, ok=index.state.active,
                                          tile_c=128)
    s = jnp.where(index.state.active[None], want, -jnp.inf)
    rv = np.sort(np.asarray(s))[:, ::-1][:, :40]
    np.testing.assert_allclose(np.asarray(vals), rv, rtol=1e-5, atol=1e-5)
    assert int(np.asarray(slots).max()) < 160       # padding never leaks
    # interpret-mode kernel and XLA twin agree through the full wrapper
    kv, ks = ops.sinnamon_topk_batch(index.state, index.spec,
                                     jnp.asarray(qi), jnp.asarray(qv),
                                     40, ok=index.state.active, tile_c=128,
                                     use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(slots))
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(vals))


def test_fused_topk_kernel_matches_dense_oracle(rng):
    """Kernel-level contract: interpret-mode kernel == XLA twin == gated
    dense oracle + lax.top_k, bit for bit (odd tile counts, kprime > tile_c,
    one-sided and positive-only decode).  Kernel and twin write the same
    gated scores; one top-k selects over all slots."""
    for (B, L, h, m, C, tile, kprime) in [(2, 5, 2, 8, 384, 128, 40),
                                          (3, 7, 1, 16, 512, 128, 200),
                                          (1, 4, 3, 8, 256, 256, 10),
                                          (5, 6, 2, 8, 640, 128, 300)]:
        W = C // 32
        qv = rng.normal(0, 1, (B, L)).astype(np.float32)
        qv[:, -1] = 0.0
        rows = rng.integers(0, m, (B, L, h)).astype(np.int32)
        qbits = rng.integers(0, 2**32, (B, L, W), dtype=np.uint32)
        u = rng.normal(0, 1, (m, C)).astype(np.float32)
        ll = (rng.normal(0, 1, (m, C)) - 1).astype(np.float32)
        gate = np.where(rng.random((1, C)) < 0.8, 0.0,
                        -np.inf).astype(np.float32)
        pos = jnp.asarray(qv) > 0
        for l in (jnp.asarray(ll), None):
            rv, rs = ref.sinnamon_topk_ref(
                jnp.asarray(qv), jnp.asarray(rows), jnp.asarray(qbits),
                jnp.asarray(gate), jnp.asarray(u), l, kprime)
            if l is not None:
                skm = jnp.concatenate([jnp.asarray(u), l], axis=0)
                prow = jnp.where(pos[..., None], jnp.asarray(rows),
                                 jnp.asarray(rows) + m)
                one_sided = True
            else:
                skm, prow, one_sided = jnp.asarray(u), jnp.asarray(rows), False
            operands = (jnp.asarray(qv), prow, jnp.asarray(qbits),
                        jnp.asarray(gate), skm)
            gv, gs = sinnamon_score.sinnamon_score_topk(
                *operands, kp=kprime, tile_c=tile,
                one_sided=one_sided, interpret=True)
            np.testing.assert_array_equal(np.asarray(gs), np.asarray(rs))
            np.testing.assert_array_equal(np.asarray(gv), np.asarray(rv))
            ks = sinnamon_score.tile_scores(*operands, tile_c=tile,
                                            one_sided=one_sided,
                                            interpret=True)
            ts = sinnamon_score.scores_xla(*operands, one_sided=one_sided)
            np.testing.assert_array_equal(np.asarray(ts), np.asarray(ks))
            tv, ts = sinnamon_score.topk_candidates(ts, kprime)
            np.testing.assert_array_equal(np.asarray(ts), np.asarray(rs))
            np.testing.assert_array_equal(np.asarray(tv), np.asarray(rv))


# ---------------------------------------------------------------------------
# Vectorized batch mutations == sequential scan oracles
# ---------------------------------------------------------------------------

def _tree_equal(a, b):
    for name, x, y in zip(eng.SinnamonState._fields, a, b):
        if name == "store":
            np.testing.assert_array_equal(np.asarray(x.indices),
                                          np.asarray(y.indices))
            np.testing.assert_array_equal(np.asarray(x.values),
                                          np.asarray(y.values))
        elif x is not None:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)


@pytest.mark.parametrize("spec_kw", list(SPECS.values()),
                         ids=list(SPECS.keys()))
def test_vectorized_batches_match_scan_oracle(spec_kw):
    rng = np.random.default_rng(7)
    idx, val = synth.make_corpus(5, DS, 80, pad=48)
    index = _churned_index(spec_kw, n_docs=96, capacity=160, seed=4)
    spec = index.spec

    free = [index._free[-(i + 1)] for i in range(16)]  # unique free slots
    slots = jnp.asarray(np.asarray(free, np.int32))
    eids = jnp.asarray(eng.pack_ids64(
        rng.integers(0, 2**62, 16).astype(np.int64)))
    i16, v16 = jnp.asarray(idx[:16]), jnp.asarray(val[:16])

    _tree_equal(
        eng.insert_batch(index.state, spec, slots, eids, i16, v16),
        eng.insert_batch_scan(index.state, spec, slots, eids, i16, v16))

    mask = jnp.asarray(rng.random(16) < 0.6)
    _tree_equal(
        eng.insert_batch_masked(index.state, spec, slots, eids, i16, v16,
                                mask),
        eng.insert_batch_masked_scan(index.state, spec, slots, eids, i16,
                                     v16, mask))

    # delete a mix of occupied slots (unique, as delete_many guarantees)
    live = [index._id2slot[d] for d in list(index._id2slot)[:16]]
    dslots = jnp.asarray(np.asarray(live, np.int32))
    dmask = jnp.asarray(rng.random(16) < 0.7)
    _tree_equal(
        eng.delete_batch_masked(index.state, spec, dslots, dmask),
        eng.delete_batch_masked_scan(index.state, spec, dslots, dmask))


# ---------------------------------------------------------------------------
# int64 external ids end-to-end
# ---------------------------------------------------------------------------

BIG_IDS = [2**31 + 5, 2**40 + 7, 2**62 + 123, 3]


def test_ids_int64_roundtrip_single():
    idx, val = synth.make_corpus(8, DS, 8, pad=48)
    index = SinnamonIndex(_spec(32, m=16, h=2))
    index.insert_many(BIG_IDS, idx[:4], val[:4])
    assert sorted(index.doc_ids()) == sorted(BIG_IDS)
    qi, qv = synth.make_queries(9, DS, 1, pad=24)
    ids, _ = index.search(qi[0], qv[0], k=4, kprime=8)
    assert ids.dtype == np.int64
    assert set(ids.tolist()) == set(BIG_IDS)        # no int32 wrap
    # device state carries the full 64-bit value (packed words round-trip)
    packed = np.asarray(index.state.ids)
    slot = index._id2slot[2**40 + 7]
    assert int(eng.unpack_ids64(packed)[slot]) == 2**40 + 7
    index.delete(2**40 + 7)
    assert 2**40 + 7 not in index
    ids2, _ = index.search(qi[0], qv[0], k=3, kprime=8)
    assert 2**40 + 7 not in ids2.tolist()


def test_ids_int64_sharded_and_locators():
    from repro.distributed import topk
    idx, val = synth.make_corpus(10, DS, 8, pad=48)
    mesh = meshlib.single_device_mesh(("data", "model"))
    index = ShardedSinnamonIndex(_spec(64, m=16, h=2), mesh)
    index.insert_many(BIG_IDS, idx[:4], val[:4])
    qi, qv = synth.make_queries(11, DS, 2, pad=24)
    ids, _, loc = index.search_many(qi, qv, k=4, kprime=16,
                                    return_locators=True)
    assert ids.dtype == np.int64
    assert set(ids[0].tolist()) == set(BIG_IDS)
    sh, sl = topk.unpack_shard_slot(loc)
    for e, s, slot in zip(ids[0], np.asarray(sh)[0], np.asarray(sl)[0]):
        assert index.route(int(e)) == int(s)
        assert index._id2slot[int(e)] == (int(s), int(slot))


def test_ids_int64_snapshot_roundtrip(tmp_path):
    from repro.persist import snapshot as snaplib
    idx, val = synth.make_corpus(12, DS, 8, pad=48)
    index = SinnamonIndex(_spec(32, m=16, h=2))
    index.insert_many(BIG_IDS, idx[:4], val[:4])
    snaplib.save(str(tmp_path), index, wal_lsn=3)
    restored, lsn = snaplib.load_single(str(tmp_path))
    assert lsn == 3
    assert sorted(restored.doc_ids()) == sorted(BIG_IDS)
    np.testing.assert_array_equal(np.asarray(restored.state.ids),
                                  np.asarray(index.state.ids))
    qi, qv = synth.make_queries(13, DS, 1, pad=24)
    a, _ = index.search(qi[0], qv[0], k=4, kprime=8)
    b, _ = restored.search(qi[0], qv[0], k=4, kprime=8)
    np.testing.assert_array_equal(a, b)


def test_pack_unpack_ids64_lossless():
    vals = np.asarray([0, -1, 1, 2**31 - 1, 2**31, 2**32 + 9, -2**63,
                       2**63 - 1], np.int64)
    np.testing.assert_array_equal(eng.unpack_ids64(eng.pack_ids64(vals)),
                                  vals)


# ---------------------------------------------------------------------------
# QueryServer latency accounting (fixed-size registry histograms)
# ---------------------------------------------------------------------------

def test_latency_histogram_is_bounded():
    h = obs_metrics.Histogram(obs_metrics.Buckets(1.0, 2.0, 4))
    for v in range(1000):
        h.observe(float(v))
    assert h.count == 1000
    # storage is the fixed bucket array, independent of sample volume
    assert len(h.bucket_counts) == 4 + 1
    h.reset()
    assert h.count == 0
    h.observe(5.0)
    assert h.count == 1 and h.snapshot()["min"] == 5.0


def test_query_server_stats_stay_bounded():
    idx, val = synth.make_corpus(14, DS, 64, pad=48)
    index = SinnamonIndex(_spec(64, m=16, h=2))
    index.insert_many(list(range(64)), idx, val)
    reg = obs_metrics.MetricsRegistry()
    srv = QueryServer(index, k=5, kprime=16, registry=reg)
    qi, qv = synth.make_queries(15, DS, 8, pad=24)
    for _ in range(5):
        srv.query_many(qi, qv)
    assert srv.stats["queries"] == 40
    hist = srv._latency_hist(srv._backend_label())
    assert hist.count == 40                 # one sample per query...
    # ...but storage stays the fixed bucket array, not a per-sample list
    assert len(hist.bucket_counts) == obs_metrics.DEFAULT_LATENCY_BUCKETS.count + 1
    pcts = srv.latency_percentiles()
    assert set(pcts) == {"p50", "p90", "p99"}
    assert all(v >= 0 for v in pcts.values())
    srv.reset_stats()
    assert srv.stats["queries"] == 0
    assert srv.latency_percentiles() == {}
