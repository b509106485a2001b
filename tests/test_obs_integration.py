"""Telemetry threaded through the live system.

* A trace-context stage is also a span of the JAX profiler's trace, under
  its mapped name (``server/device``, ``engine/launch``, ...).
* Under ``QueryServer.query_many`` the batch context gets the index's
  ``launch`` and ``fetch`` stages, inside its ``device`` stage, for the
  single-device, sharded and tiered indexes.
* The fused search program carries the ``operands``, ``scan``, ``topk``
  and ``rerank`` named scopes in its op metadata, on every backend.
* A churn-then-query stream over a durable index populates the WAL,
  snapshot, drift and recovery surfaces of one injected registry.
* The /metrics endpoint serves a parseable Prometheus exposition of all of
  the above; the event log captures every query batch with its stages.
* BackgroundCompactor outcomes land in ``repro_compactor_outcomes_total``.
"""

import glob
import json
import re
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core.engine import EngineSpec, SinnamonIndex, TieredSinnamonIndex
from repro.data import synth
from repro.distributed import mesh as meshlib
from repro.obs import EventLog, MetricsRegistry, MetricsServer
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import parse_exposition
from repro.obs.trace import TraceContext
from repro.persist import compact as compactlib
from repro.persist.durable import DurableSinnamonIndex
from repro.serving.serve import QueryServer
from repro.serving.sharded import ShardedSinnamonIndex

DS = synth.SparseDatasetSpec("t", n=400, psi_doc=20, psi_query=10,
                             value_dist="gaussian")
N_DOCS = 96


def _spec(capacity=128):
    return EngineSpec(n=DS.n, m=12, capacity=capacity, max_nnz=32, h=2,
                      seed=3, value_dtype="float32")


def _churn(index, idx, val):
    """Insert / delete / re-insert so recycled columns carry real drift."""
    index.insert_many(list(range(64)), idx[:64], val[:64])
    for e in (3, 17, 40, 41):
        index.delete(e)
    index.insert_many(list(range(64, N_DOCS)), idx[64:N_DOCS],
                      val[64:N_DOCS])


@pytest.fixture(scope="module")
def corpus():
    idx, val = synth.make_corpus(0, DS, N_DOCS, pad=32)
    qi, qv = synth.make_queries(1, DS, 8, pad=16)
    return idx, val, qi, qv


@pytest.fixture(scope="module")
def index(corpus):
    idx, val, _, _ = corpus
    index = SinnamonIndex(_spec())
    _churn(index, idx, val)
    return index


# ---------------------------------------------------------------------------
# trace-context stages on the profiler's clock
# ---------------------------------------------------------------------------

class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` with a session on."""
    opened: list = []

    def __init__(self, name):
        self.name = name

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        _FakeAnnotation.opened.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


def test_stage_annotates_the_profiler_under_its_mapped_name(monkeypatch):
    monkeypatch.setattr(obs_trace, "_annotation", _FakeAnnotation)
    _FakeAnnotation.opened = []
    ctx = TraceContext()
    with ctx.stage("device"), ctx.activate():
        with obs_trace.stage("launch"):
            pass
        with obs_trace.stage("fetch"):
            pass
    with ctx.stage("work"):                 # unmapped: keeps its own name
        pass
    assert _FakeAnnotation.opened == ["server/device", "engine/launch",
                                      "engine/fetch", "work"]
    assert [name for name, _, _ in ctx.stages] == ["launch", "fetch",
                                                   "device", "work"]


def test_stage_is_a_span_of_a_cpu_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    ctx = TraceContext()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with ctx.stage("device"), ctx.activate():
            with obs_trace.stage("fetch"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = {ev.name: (ev.start_ns, ev.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name in ("server/device", "engine/fetch")}
    assert set(spans) == {"server/device", "engine/fetch"}
    (ds, dd), (fs, fd) = spans["server/device"], spans["engine/fetch"]
    assert ds <= fs and fs + fd <= ds + dd and fd >= 2e6
    assert ctx.stage_ms()["fetch"] >= 2.0


def _indexes(kind, corpus):
    idx, val, _, _ = corpus
    if kind == "sharded":
        mesh = meshlib.single_device_mesh(("data", "model"))
        index = ShardedSinnamonIndex(_spec(), mesh)
    elif kind == "tiered":
        index = TieredSinnamonIndex(_spec(), tier_chunk_slots=16,
                                    cache_chunks=2)
    else:
        index = SinnamonIndex(_spec())
    _churn(index, idx, val)
    return index


@pytest.mark.parametrize("kind", ["single", "sharded", "tiered"])
def test_query_many_records_launch_and_fetch_inside_device(corpus, kind):
    _, _, qi, qv = corpus
    srv = QueryServer(_indexes(kind, corpus), k=5, kprime=32,
                      registry=MetricsRegistry())
    ctx = TraceContext(tenant="batch")
    res = srv.query_many(qi, qv, ctx=ctx)
    assert res.ids.shape == (len(qi), 5)
    (device,) = [st for st in ctx.stages if st[0] == "device"]
    inner = [st for st in ctx.stages if st[0] != "device"]
    want = ["launch", "fetch"] + (["promote", "launch", "fetch"]
                                  if kind == "tiered" else [])
    assert [name for name, _, _ in inner] == want
    lo, hi = device[1], device[1] + device[2]
    for _name, start, dur in inner:
        assert lo <= start and start + dur <= hi + 1e-6
    assert obs_trace.active() is None


_SCOPES = ("operands", "scan", "topk", "rerank")


@pytest.mark.parametrize("backend", ["reference", "grouped", "pallas"])
def test_search_program_carries_named_scopes(backend):
    spec = _spec()
    search = jax.jit(eng.search_batch, static_argnums=(1, 4, 5, 6),
                     static_argnames=("score_fn", "backend"))
    qi = jnp.zeros((4, 16), jnp.int32)
    qv = jnp.ones((4, 16), jnp.float32)
    text = search.lower(jax.eval_shape(lambda: eng.init(spec)), spec, qi,
                        qv, 5, 32, None, None,
                        backend=backend).as_text(debug_info=True)
    names = set(re.findall(r'loc\("(jit\(search_batch\)/[^"]*)"', text))
    for scope in _SCOPES:
        # inside a vmap the scope reads ``vmap(<scope>)``
        rx = re.compile(rf"(^|[/(]){scope}([/)]|$)")
        assert any(rx.search(n) for n in names), (scope, sorted(names)[:20])
    rerank = [n for n in names if re.search(r"(^|[/(])rerank([/)]|$)", n)]
    assert rerank
    # the rerank matches coordinates by comparison: no binary-search loop
    assert not any("searchsorted" in n or "while" in n for n in rerank), \
        sorted(rerank)


# ---------------------------------------------------------------------------
# engine gauges, event log, HTTP endpoint
# ---------------------------------------------------------------------------

def test_engine_gauges_reflect_live_index(corpus, index):
    _, _, qi, qv = corpus
    reg = MetricsRegistry()
    QueryServer(index, k=5, kprime=32, registry=reg).query_many(qi, qv)
    snap = reg.snapshot()                  # runs the collector
    lbl = {"index": "index"}               # install_engine_gauges name label
    assert reg.gauge("repro_engine_live_docs", labels=lbl).value == 92
    assert reg.gauge("repro_engine_capacity_slots", labels=lbl).value == 128
    comps = {s["labels"]["component"]: s["value"]
             for s in snap["repro_engine_bytes"]["series"]}
    assert set(comps) == {"sketch", "inverted_index", "storage"}
    assert all(v > 0 for v in comps.values())
    assert reg.gauge("repro_engine_dirty_columns", labels=lbl).value >= 4


def test_event_log_captures_traced_queries(tmp_path, corpus, index):
    _, _, qi, qv = corpus
    path = str(tmp_path / "events.jsonl")
    with EventLog(path) as log:
        srv = QueryServer(index, k=5, kprime=32, registry=MetricsRegistry(),
                          event_log=log)
        for _ in range(4):
            srv.query_many(qi, qv)
    with open(path) as f:
        events = [json.loads(line) for line in f]
    queries = [e for e in events if e["event"] == "query"]
    assert len(queries) == 4
    for e in queries:
        assert [s["stage"] for s in e["stages"]] == ["launch", "fetch",
                                                     "device"]
        ms = {s["stage"]: s["ms"] for s in e["stages"]}
        assert ms["launch"] + ms["fetch"] <= ms["device"] + 1e-3
    assert all("ts" in e and e["level"] == "INFO" for e in queries)


def test_metrics_http_endpoint_serves_parseable_exposition(corpus, index):
    _, _, qi, qv = corpus
    reg = MetricsRegistry()
    srv = QueryServer(index, k=5, kprime=32, registry=reg)
    srv.query_many(qi, qv)
    with MetricsServer(registry=reg, port=0) as ms:
        with urllib.request.urlopen(ms.url + "/metrics", timeout=10) as r:
            assert "text/plain" in r.headers["Content-Type"]
            text = r.read().decode()
        with urllib.request.urlopen(ms.url + "/metrics.json",
                                    timeout=10) as r:
            doc = json.loads(r.read().decode())
        with urllib.request.urlopen(ms.url + "/healthz", timeout=10) as r:
            assert r.read() == b"ok\n"
    flat = parse_exposition(text)          # raises on malformed lines
    names = {name for name, _ in flat}
    for required in ("repro_query_latency_ms_count", "repro_queries_total",
                     "repro_engine_live_docs", "repro_engine_bytes"):
        assert required in names, required
    assert doc["repro_query_latency_ms"]["type"] == "histogram"


# ---------------------------------------------------------------------------
# durable churn-then-query: WAL / snapshot / drift / recovery surfaces
# ---------------------------------------------------------------------------

def test_durable_churn_populates_persistence_metrics(tmp_path, corpus):
    idx, val, qi, qv = corpus
    wd, sd = str(tmp_path / "wal"), str(tmp_path / "snap")
    reg = MetricsRegistry()
    old = obs_metrics.set_registry(reg)    # WAL/snapshot bind to the global
    try:
        live = DurableSinnamonIndex.open(_spec(), wal_dir=wd,
                                         snapshot_dir=sd)
        _churn(live, idx, val)
        live.snapshot()

        # write path: engine op counters + WAL record accounting
        assert reg.counter("repro_engine_ops_total",
                           labels={"op": "insert_many"}).value == 2
        assert reg.counter("repro_engine_ops_total",
                           labels={"op": "delete"}).value == 4
        assert reg.counter("repro_wal_records_total",
                           labels={"kind": "insert"}).value == 2
        assert reg.counter("repro_wal_records_total",
                           labels={"kind": "delete"}).value == 4
        assert reg.counter("repro_wal_appended_bytes_total").value > 0
        assert reg.histogram("repro_wal_append_ms").count >= 6
        assert reg.histogram("repro_wal_fsync_ms").count >= 6

        # snapshot surface
        assert reg.counter("repro_snapshots_total",
                           labels={"outcome": "written"}).value >= 1
        assert reg.histogram("repro_snapshot_ms").count >= 1

        # drift surface: recycled slots under churn carry stale maxima
        drift = compactlib.drift_metrics(live, reg)
        assert reg.gauge("repro_sketch_drift_max").value \
            == drift["max_overestimate"]
        assert reg.gauge("repro_sketch_dirty_active_slots").value \
            == drift["dirty_active"] >= 1

        # queries still served; engine gauges see WAL/snapshot sidecars
        QueryServer(live, k=5, kprime=32, registry=reg).query_many(qi, qv)
        snap = reg.snapshot()
        assert ("repro_wal_last_lsn" in snap
                and "repro_snapshot_age_s" in snap)

        # recovery surface: reopen replays the tail past the snapshot
        rec = DurableSinnamonIndex.open(_spec(), wal_dir=wd,
                                        snapshot_dir=sd)
        assert reg.counter("repro_recoveries_total").value >= 2
        assert reg.gauge("repro_recovery_replay_ms").value >= 0
        np.testing.assert_array_equal(np.asarray(rec.state.active),
                                      np.asarray(live.state.active))
    finally:
        obs_metrics.set_registry(old)


def test_background_compactor_outcomes(tmp_path, corpus):
    idx, val, _, _ = corpus
    wd = str(tmp_path / "wal")
    reg = MetricsRegistry()
    live = DurableSinnamonIndex.open(_spec(), wal_dir=wd)
    _churn(live, idx, val)
    assert compactlib.drift_metrics(live, reg)["max_overestimate"] > 0
    comp = compactlib.BackgroundCompactor(live, threshold=0.0,
                                          interval_s=0.02,
                                          registry=reg).start()
    try:
        deadline = time.time() + 30
        while comp.compactions == 0 and time.time() < deadline:
            time.sleep(0.05)
    finally:
        comp.stop()
    assert comp.compactions >= 1
    assert reg.counter("repro_compactor_outcomes_total",
                       labels={"outcome": "compacted"}).value >= 1
    # a quiesced compaction restores the zero-drift invariant
    assert reg.gauge("repro_compaction_drift_after").value == 0.0
    assert reg.histogram("repro_compaction_ms").count >= 1
    assert compactlib.drift_metrics(live, reg)["max_overestimate"] == 0.0


def test_maybe_compact_publishes_before_after(corpus):
    idx, val, _, _ = corpus
    reg = MetricsRegistry()
    index = SinnamonIndex(_spec())
    _churn(index, idx, val)
    pre = compactlib.maybe_compact(index, threshold=0.0, registry=reg)
    assert pre is not None and pre["max_overestimate"] > 0
    assert reg.gauge("repro_compaction_drift_before").value \
        == pre["max_overestimate"]
    assert reg.gauge("repro_compaction_drift_after").value == 0.0
