"""ISSUE 8 contracts: trace context, flight recorder, SLO monitor, and the
health/readiness + debug HTTP surfaces.

* `TraceContext` accumulates per-stage timings and annotations and seals
  into a flat record dict.
* `FlightRecorder` tail-samples at completion: non-ok outcomes always
  retained, slowest decile retained once warm, the rest head-sampled; the
  ring is bounded and retained records spill to the event log.
* `EventLog` rotates by size without ever splitting a line; `read_events`
  tolerates a torn FINAL line (crash shape) but raises on interior
  corruption.
* `SLOMonitor` computes multi-window burn rates from registry counts with
  an injected clock, and emits one edge-triggered `slo_burn` WARN per
  episode.
* Histogram exemplars survive exposition, parsing, and snapshot merge.
* `/healthz` is pure liveness; `/readyz` aggregates latched flags + live
  checks into 200/503 with per-check reasons; `/debug/*` dispatches by
  prefix and validates query params.
* `/metrics` stays parseable under concurrent scrapes during write churn.
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from repro.obs import (Buckets, EventLog, FlightRecorder, MetricsRegistry,
                       MetricsServer, ReadyState, TraceContext,
                       merge_snapshots, parse_exposition, read_events)
from repro.obs import trace as obs_trace
from repro.obs.server import build_endpoints, dispatch
from repro.obs.slo import SLOMonitor, SLOSpec


def _rec(outcome="ok", total_ms=1.0, trace_id=None, tenant="default",
         **extra):
    d = {"trace_id": trace_id or f"q-t-{id(extra) % 100000:x}",
         "tenant": tenant, "outcome": outcome, "total_ms": total_ms,
         "stages": []}
    d.update(extra)
    return d


# ---------------------------------------------------------------------------
# TraceContext
# ---------------------------------------------------------------------------

def test_trace_context_stages_and_seal():
    ctx = TraceContext(tenant="t0")
    assert ctx.trace_id.startswith("q-")
    ctx.add_stage("quota", 0.5, start_ms=0.0)
    with ctx.stage("work"):
        pass
    ctx.add_stage("work", 2.0)            # repeated names accumulate
    ctx.annotate(batch_id="b-1", width_bucket=32)
    with ctx.activate():
        with obs_trace.stage("launch"):      # records into the active ctx
            pass
    with obs_trace.stage("fetch"):           # no active ctx: not recorded
        pass
    ctx.finish("ok", total_ms=7.25)
    d = ctx.to_dict()
    assert d["outcome"] == "ok" and d["total_ms"] == 7.25
    assert d["batch_id"] == "b-1" and d["width_bucket"] == 32
    names = [s["stage"] for s in d["stages"]]
    assert names == ["quota", "work", "work", "launch"]
    assert d["stages"][0]["start_ms"] == 0.0
    assert "start_ms" not in d["stages"][2]     # externally timed: dur only
    assert d["stages"][3]["start_ms"] >= d["stages"][1]["start_ms"]
    assert obs_trace.active() is None
    assert ctx.stage_ms()["work"] >= 2.0
    # finish() without total_ms uses the context's own wall clock
    ctx2 = TraceContext().finish("error", error="boom")
    assert ctx2.total_ms >= 0.0
    assert ctx2.to_dict()["error"] == "boom"


# ---------------------------------------------------------------------------
# FlightRecorder retention
# ---------------------------------------------------------------------------

def test_recorder_keeps_every_non_ok_outcome():
    rec = FlightRecorder(capacity=16, sample_rate=0.0, spill=False,
                         registry=MetricsRegistry())
    for i, outcome in enumerate(["error", "expired", "rejected_throttled",
                                 "rejected_queue_full"]):
        assert rec.record(_rec(outcome, trace_id=f"q-{i}")) == "outcome"
    assert len(rec) == 4
    assert rec.get("q-2")["outcome"] == "rejected_throttled"
    assert rec.recent(outcome="rejected") and all(
        r["outcome"].startswith("rejected")
        for r in rec.recent(outcome="rejected"))


def test_recorder_tail_retains_slowest_decile():
    reg = MetricsRegistry()
    rec = FlightRecorder(capacity=256, sample_rate=0.0, spill=False,
                         min_tail_samples=32, registry=reg)
    # 32 fast OK requests warm the p90 threshold (recomputed at the 32nd,
    # which must itself sit below the fresh threshold to stay dropped)
    for i in range(31):
        assert rec.record(_rec("ok", total_ms=1.0, trace_id=f"q-w{i}")) \
            is None
    assert rec.record(_rec("ok", total_ms=0.5, trace_id="q-w31")) is None
    assert rec.tail_threshold_ms == pytest.approx(1.0)
    assert rec.record(_rec("ok", total_ms=50.0, trace_id="q-slow")) == "tail"
    assert rec.record(_rec("ok", total_ms=0.5, trace_id="q-fast")) is None
    assert rec.get("q-slow")["retained"] == "tail"
    assert rec.get("q-fast") is None
    snap = json.loads(reg.to_json())
    retained = {s["labels"]["reason"]: s["value"]
                for s in snap["repro_recorder_retained_total"]["series"]}
    assert retained == {"tail": 1}
    assert snap["repro_recorder_dropped_total"]["series"][0]["value"] == 33


def test_recorder_head_sampling_and_ring_eviction():
    rec = FlightRecorder(capacity=4, sample_rate=1.0, spill=False,
                         registry=MetricsRegistry())
    for i in range(6):
        assert rec.record(_rec("ok", trace_id=f"q-{i}")) == "sampled"
    assert len(rec) == 4
    assert rec.get("q-0") is None and rec.get("q-1") is None  # evicted
    assert rec.get("q-5") is not None
    assert rec.stats()["seen"] == 6 and rec.stats()["ring_size"] == 4
    # sample_rate=0.25 keeps every 4th
    quarter = FlightRecorder(capacity=64, sample_rate=0.25, spill=False,
                             registry=MetricsRegistry())
    kept = sum(1 for i in range(40)
               if quarter.record(_rec("ok", total_ms=None,
                                      trace_id=f"q-{i}")))
    assert kept == 10


def test_recorder_spills_retained_records_to_event_log(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with EventLog(path) as log:
        rec = FlightRecorder(capacity=8, sample_rate=0.0, event_log=log,
                             spill=True, registry=MetricsRegistry())
        rec.record(_rec("error", trace_id="q-err"))
        rec.record(_rec("ok", trace_id="q-ok"))       # dropped, no spill
    events = read_events(path)
    assert [e["event"] for e in events] == ["request_trace"]
    assert events[0]["trace_id"] == "q-err"
    assert events[0]["level"] == "WARN"


def test_recorder_batches_and_filters():
    rec = FlightRecorder(capacity=32, sample_rate=1.0, spill=False,
                         registry=MetricsRegistry())
    rec.record(_rec("ok", total_ms=3.0, trace_id="q-a", tenant="t0"))
    rec.record(_rec("ok", total_ms=9.0, trace_id="q-b", tenant="t1"))
    rec.record_batch({"batch_id": "b-1", "trace_ids": ["q-a", "q-b"],
                      "size": 2})
    assert rec.get_batch("b-1")["size"] == 2
    assert rec.recent_batches() == [{"batch_id": "b-1",
                                     "trace_ids": ["q-a", "q-b"], "size": 2}]
    assert [r["trace_id"] for r in rec.recent(tenant="t1")] == ["q-b"]
    assert [r["trace_id"] for r in rec.recent(min_ms=5.0)] == ["q-b"]
    assert [r["trace_id"] for r in rec.recent(limit=1)] == ["q-b"]  # newest


# ---------------------------------------------------------------------------
# EventLog rotation + torn-line tolerance
# ---------------------------------------------------------------------------

def test_event_log_rotates_by_size_without_splitting_lines(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    with EventLog(path, max_bytes=256, keep=2) as log:
        for i in range(40):
            log.emit("tick", i=i)
        assert log.rotations >= 2
        segs = log.segments()
    assert segs[-1] == path and f"{path}.1" in segs
    # every surviving file parses whole — no torn interior lines
    for seg in segs:
        with open(seg) as f:
            for line in f:
                json.loads(line)
    events = read_events(path, include_rotated=True)
    ids = [e["i"] for e in events]
    assert ids == sorted(ids) and ids[-1] == 39    # oldest-first, contiguous
    assert len(ids) <= 40                          # keep=2 dropped the oldest


def test_read_events_tolerates_torn_tail_rejects_interior(tmp_path):
    path = str(tmp_path / "torn.jsonl")
    with EventLog(path) as log:
        log.emit("a")
        log.emit("b")
    with open(path, "a") as f:
        f.write('{"ts": 1, "level": "INFO", "eve')   # crash mid-append
    events = read_events(path)
    assert [e["event"] for e in events] == ["a", "b"]
    bad = str(tmp_path / "corrupt.jsonl")
    with open(bad, "w") as f:
        f.write('{"ts": 1, "level": "INFO", "event": "a"}\n')
        f.write("NOT JSON\n")                        # interior corruption
        f.write('{"ts": 2, "level": "INFO", "event": "b"}\n')
    with pytest.raises(ValueError, match="malformed interior"):
        read_events(bad)


# ---------------------------------------------------------------------------
# SLO monitor
# ---------------------------------------------------------------------------

def _count(reg, outcome, n):
    reg.counter("repro_frontend_requests_total", "outcomes",
                labels={"tenant": "t", "outcome": outcome}).inc(n)


def test_slo_burn_rates_multi_window(tmp_path):
    reg = MetricsRegistry()
    log = EventLog(str(tmp_path / "slo.jsonl"))
    t = [0.0]
    mon = SLOMonitor(SLOSpec(latency_ms=100.0, availability_target=0.999),
                     reg, fast_window_s=60.0, slow_window_s=600.0,
                     burn_warn=2.0, event_log=log, clock=lambda: t[0])
    mon.tick()                                   # baseline sample at t=0
    _count(reg, "ok", 90)
    _count(reg, "error", 10)                     # 90% availability
    t[0] = 10.0
    out = mon.tick()
    fast = out["availability"]["windows"]["fast"]
    assert fast["good"] == 90 and fast["total"] == 100
    assert fast["compliance"] == pytest.approx(0.9)
    assert fast["burn_rate"] == pytest.approx(0.1 / 0.001, rel=1e-3)
    # both windows burning -> exactly ONE edge-triggered WARN
    t[0] = 20.0
    mon.tick()
    warns = [e for e in read_events(log.path) if e["event"] == "slo_burn"]
    assert len(warns) == 1 and warns[0]["level"] == "WARN"
    # far beyond the slow window the bad episode ages out -> re-armed
    t[0] = 2000.0
    mon.tick()
    assert not mon._burning
    _count(reg, "error", 50)
    t[0] = 2010.0
    mon.tick()
    warns = [e for e in read_events(log.path) if e["event"] == "slo_burn"]
    assert len(warns) == 2                       # second episode, second WARN
    snap = json.loads(reg.to_json())
    burn = {tuple(sorted(s["labels"].items())): s["value"]
            for s in snap["repro_slo_burn_rate"]["series"]}
    assert len(burn) == 4                        # 2 objectives x 2 windows
    log.close()


def test_slo_latency_objective_reads_histogram_and_report_schema():
    reg = MetricsRegistry()
    t = [0.0]
    mon = SLOMonitor(SLOSpec(latency_ms=100.0, latency_target=0.99), reg,
                     clock=lambda: t[0])
    mon.tick()                                   # baseline before traffic
    h = reg.histogram("repro_frontend_latency_ms", "lat",
                      labels={"tenant": "t"})
    for _ in range(98):
        h.observe(1.0)
    h.observe(500.0)
    h.observe(900.0)                             # 98/100 under 100ms
    t[0] = 10.0
    rep = mon.report()
    assert rep["objectives"]["latency_ms"] == 100.0
    assert set(rep["windows"]) == {"fast", "slow"}
    lat = rep["slos"]["latency"]
    assert lat["bound_ms"] >= 100.0              # snapped UP to a bucket edge
    fast = lat["windows"]["fast"]
    assert fast["total"] == 100 and fast["good"] >= 98
    assert fast["burn_rate"] <= 2.001
    for key in ("burn_rate", "compliance", "good", "total", "window_s"):
        assert key in fast


# ---------------------------------------------------------------------------
# exemplars
# ---------------------------------------------------------------------------

def test_exemplars_survive_exposition_parse_and_merge():
    reg = MetricsRegistry()
    h = reg.histogram("repro_exemplar_test_ms", "h",
                      buckets=Buckets(1.0, 2.0, 8))
    h.observe(3.2, exemplar="q-abc-1")
    h.observe(3.3)                               # same bucket, no exemplar
    text = reg.exposition()
    line = next(ln for ln in text.splitlines() if "# {" in ln)
    assert 'trace_id="q-abc-1"' in line and line.rstrip().endswith("3.2")
    parse_exposition(text)                       # suffix validates + strips
    snap = json.loads(reg.to_json())
    series = snap["repro_exemplar_test_ms"]["series"][0]
    (ex,) = series["exemplars"].values()
    assert ex == {"trace_id": "q-abc-1", "value": 3.2}
    # merge: exemplars union, later source wins per bucket
    reg2 = MetricsRegistry()
    h2 = reg2.histogram("repro_exemplar_test_ms", "h",
                        buckets=Buckets(1.0, 2.0, 8))
    h2.observe(3.4, exemplar="q-abc-2")
    merged = merge_snapshots(reg.snapshot(), reg2.snapshot())
    series = merged["repro_exemplar_test_ms"]["series"][0]
    assert series["count"] == 3
    (ex,) = series["exemplars"].values()
    assert ex["trace_id"] == "q-abc-2"


# ---------------------------------------------------------------------------
# readiness + debug endpoint dispatch
# ---------------------------------------------------------------------------

def test_ready_state_flags_and_live_checks():
    ready = ReadyState()
    ready.mark("engine", False, "recovering")
    ok, detail = ready()
    assert not ok and detail["engine"] == {"ok": False,
                                           "reason": "recovering"}
    ready.mark("engine", True)
    depth = [0]
    ready.add_check("queue", lambda: (depth[0] < 10, f"depth={depth[0]}"))
    assert ready()[0]
    depth[0] = 50
    ok, detail = ready()
    assert not ok and detail["queue"]["reason"] == "depth=50"
    ready.add_check("boom", lambda: 1 / 0)       # raising check = not ready
    ok, detail = ready()
    assert not ok and "check raised" in detail["boom"]["reason"]


def test_debug_endpoint_dispatch_and_param_validation():
    reg = MetricsRegistry()
    rec = FlightRecorder(capacity=8, sample_rate=0.0, spill=False,
                         registry=reg)
    rec.record(_rec("error", trace_id="q-x", tenant="t9"))
    rec.record_batch({"batch_id": "b-x", "size": 1})
    eps = build_endpoints(reg, recorder=rec)
    status, body, _ = dispatch(eps, "/debug/trace/q-x")
    assert status == 200 and json.loads(body)["outcome"] == "error"
    status, body, _ = dispatch(eps, "/debug/trace/b-x")   # batch ids resolve
    assert status == 200 and json.loads(body)["size"] == 1
    status, body, _ = dispatch(eps, "/debug/trace/q-nope")
    assert status == 404 and json.loads(body)["error"] == "not_found"
    status, body, _ = dispatch(eps, "/debug/trace/")
    assert status == 400
    status, body, _ = dispatch(eps, "/debug/requests?tenant=t9&limit=5")
    doc = json.loads(body)
    assert status == 200 and doc["count"] == 1
    assert doc["recorder"]["seen"] == 1    # batches don't count as requests
    status, body, _ = dispatch(eps, "/debug/requests?limit=abc")
    assert status == 400 and json.loads(body)["error"] == "bad_request"
    assert dispatch(eps, "/debug/nothing") is None        # unrouted -> 404
    status, _, _ = dispatch(eps, "/healthz")
    assert status == 200


def test_metrics_server_healthz_vs_readyz():
    reg = MetricsRegistry()
    ready = ReadyState()
    ready.mark("engine", False, "index build/recovery in progress")
    with MetricsServer(reg, port=0, ready=ready) as srv:
        assert urllib.request.urlopen(
            srv.url + "/healthz", timeout=10).read() == b"ok\n"
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(srv.url + "/readyz", timeout=10)
        assert exc.value.code == 503
        doc = json.loads(exc.value.read())
        assert doc["ready"] is False
        assert doc["checks"]["engine"]["reason"].startswith("index build")
        ready.mark("engine", True)
        doc = json.loads(urllib.request.urlopen(
            srv.url + "/readyz", timeout=10).read())
        assert doc["ready"] is True


def test_concurrent_scrapes_during_write_churn():
    reg = MetricsRegistry()
    stop = threading.Event()

    def churn(i):
        h = reg.histogram("repro_churn_test_ms", "h")
        c = reg.counter("repro_churn_test_total", "c",
                        labels={"writer": str(i)})
        v = 0.1
        while not stop.is_set():
            h.observe(v, exemplar=f"q-{i}")
            c.inc()
            v = v * 1.1 if v < 1e3 else 0.1

    writers = [threading.Thread(target=churn, args=(i,), daemon=True)
               for i in range(4)]
    for w in writers:
        w.start()
    try:
        with MetricsServer(reg, port=0) as srv:
            def scrape(out):
                for _ in range(5):
                    text = urllib.request.urlopen(
                        srv.url + "/metrics", timeout=10).read().decode()
                    out.append(parse_exposition(text))

            results = [[] for _ in range(4)]
            scrapers = [threading.Thread(target=scrape, args=(r,))
                        for r in results]
            for s in scrapers:
                s.start()
            for s in scrapers:
                s.join(timeout=30)
                assert not s.is_alive()
    finally:
        stop.set()
        for w in writers:
            w.join(timeout=5)
    for r in results:
        assert len(r) == 5                       # every scrape parsed clean
        for flat in r:
            names = {n for n, _l in flat}
            assert "repro_churn_test_ms_count" in names
