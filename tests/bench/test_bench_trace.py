"""The trace reduction: busy union, idle share, kernel, program and
collective sums, and the labelled idle gaps, on a small trace whose answers
are counted by hand."""

import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import catalog, xtrace  # noqa: E402

MS = 1_000_000


def plane(i, ops, modules):
    return {"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]}


# Two chips, a 100 ms window, two dispatches of the search program.
TRACE = {
    "window_ns": [0, 100 * MS],
    "start_unix_ns": 0,
    "planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "frontend-dispatch", "events": [
                ["PjitFunction(search_batch)", 58 * MS, 1 * MS]]}]},
        plane(0,
              ops=[["fusion.1", 10 * MS, 5 * MS],
                   ["tile_scores.custom-call", 15 * MS, 20 * MS],
                   ["all-gather.3", 30 * MS, 10 * MS],      # overlaps
                   ["fusion.1", 60 * MS, 5 * MS],
                   ["tile_scores.custom-call", 65 * MS, 20 * MS],
                   ["all-gather.3", 85 * MS, 2 * MS]],
              modules=[["jit_search_batch", 10 * MS, 30 * MS],
                       ["jit_search_batch", 60 * MS, 27 * MS]]),
        plane(1,
              ops=[["tile_scores.custom-call", 0, 50 * MS],
                   ["all-gather.3", 50 * MS, 4 * MS]],
              modules=[["jit_search_batch", 0, 54 * MS]]),
        {"name": "/device:TPU:0 SparseCore 0", "lines": [
            {"name": "XLA Ops", "events": [["ignored", 0, 100 * MS]]}]},
    ],
}


def ctx(**kw):
    base = dict(trace=TRACE, chips=2, max_batch=16, queue_ms=[],
                batch_sizes=(0, 0), dispatch_bytes=[],
                peak={"hbm_bytes_per_s": 819e9}, pending=None,
                collectives=r"all-gather|all-reduce")
    base.update(kw)
    return types.SimpleNamespace(**base)


def read(metric, **kw):
    return catalog.metric_reader(metric)(ctx(**kw))


def test_busy_is_the_union_per_chip():
    # chip 0: [10, 40) and [60, 87) -> 57 ms; chip 1: [0, 54) -> 54 ms
    assert xtrace.busy_ns(TRACE) == [57 * MS, 54 * MS]


def test_idle_share():
    assert read("device.idle_share") == pytest.approx(
        100 * (1 - (57 + 54) / 2 / 100))


def test_kernel_time_per_dispatch():
    # three kernel events, 90 ms in all
    assert read("scan.kernel_ms") == pytest.approx(30.0)


def test_search_program_time_per_dispatch():
    assert read("search.device_ms") == pytest.approx((30 + 27 + 54) / 3)


def test_collective_time_per_dispatch_and_chip():
    assert read("shard.collective_ms") == pytest.approx((10 + 2 + 4) / 3)
    assert read("shard.collective_ms", chips=1) is None


def test_roofline_share_of_the_kernel():
    # 8.19 MB per dispatch at 819 GB/s is 0.01 ms, against 30 ms
    assert read("scan_roofline", dispatch_bytes=[8.19e6]) == pytest.approx(
        100 * 0.01 / 30)


def test_front_door_readers():
    assert read("frontend.queue_ms", queue_ms=[1.0, 2.0, 6.0]) == 3.0
    assert read("frontend.batch_fill", batch_sizes=(24, 3)) == 50.0
    assert read("frontend.queue_ms") is None
    assert read("frontend.batch_fill") is None


def test_readers_without_a_trace_return_nothing():
    for name in ("device.idle_share", "scan.kernel_ms", "scan_roofline",
                 "search.device_ms", "shard.collective_ms"):
        assert read(name, trace=None) is None


def test_gaps_and_their_labels():
    planes = xtrace.device_planes(TRACE)
    assert [p["name"] for p in planes] == ["/device:TPU:0", "/device:TPU:1"]
    ivs = [(e[1], e[2]) for e in xtrace.line_events(planes[0], "XLA Ops")]
    gaps = xtrace.idle_gaps(ivs, 0, 100 * MS)
    assert gaps == [(0, 10 * MS), (40 * MS, 20 * MS), (87 * MS, 13 * MS)]
    waiting = lambda s, e: s >= 40 * MS                      # noqa: E731
    labels = xtrace.label_gaps(TRACE, gaps, pending=waiting)
    assert labels == [["host: PjitFunction(search_batch)", 0.02],
                      ["host: no traced event", 0.013],
                      ["no request waiting", 0.01]]


RECORDED = Path(__file__).resolve().parent / "data" / "splade_open_trace.json"


def recorded():
    import json
    return json.loads(RECORDED.read_text())


def test_recorded_trace_busy_union_against_a_bitmap():
    import numpy as np
    trace = recorded()
    lo, hi = trace["window_ns"]
    plane, = xtrace.device_planes(trace)
    ops = xtrace.line_events(plane, xtrace.OPS_LINE)
    covered = np.zeros(hi // 1000 + 1, bool)              # 1 us bins
    for _n, s, d in ops:
        covered[s // 1000:(s + d) // 1000] = True
    busy, = xtrace.busy_ns(trace)
    assert abs(busy / 1000 - covered.sum()) <= len(ops)
    idle = read("device.idle_share", trace=trace, chips=1)
    assert idle == pytest.approx(100 * (1 - busy / hi))
    assert 0 < idle < 10


def test_recorded_trace_kernel_and_program_sums():
    trace = recorded()
    plane, = xtrace.device_planes(trace)
    kernels = [d for n, _s, d in xtrace.line_events(plane, xtrace.OPS_LINE)
               if xtrace.op_name(n).startswith("tile_scores")]
    programs = [d for n, _s, d in xtrace.line_events(plane,
                                                     xtrace.MODULES_LINE)
                if n.startswith("jit_search_batch")]
    assert len(kernels) == len(programs) == 2
    assert read("scan.kernel_ms", trace=trace, chips=1) == pytest.approx(
        sum(kernels) / 2 / 1e6)
    assert read("search.device_ms", trace=trace, chips=1) == pytest.approx(
        sum(programs) / 2 / 1e6)
    assert read("shard.collective_ms", trace=trace, chips=1) is None
    assert xtrace.op_label(
        "%sort.10 = (f32[8]{0}, s32[8]{0}) sort(f32[8]{0} %c), dim={0}") \
        == "sort.10 (sort)"


def test_dispatch_bytes_follow_the_front_doors_batch_records():
    import numpy as np
    from repro.obs.trace import TraceContext
    from bench import roofline, run

    maps = np.array([[0, 1, 2, 3, 0, 1, 2, 3]], np.int32)
    queries = [(np.array([0, 1]), np.array([1.0, 1.0])),
               (np.array([4, 6]), np.array([2.0, 1.0]))]
    results = [types.SimpleNamespace(trace_id=f"q{i}") for i in range(2)]
    batch = TraceContext(tenant="batch", trace_id="b1")
    batch.annotate(trace_ids=["q0", "q1"])
    record = batch.to_dict()
    record["ts"] = 10.5
    collector = run.Collector()
    collector.batches.append(record)
    sut = types.SimpleNamespace(collector=collector, maps=maps)
    win = types.SimpleNamespace(
        results=results, queries=queries, pool_of=np.array([0, 1]), n=2,
        submitted=np.array([10.0, 10.1]), batch_sizes=(2, 1),
        tracer=types.SimpleNamespace(anchor=(10_000_000_000, 0.0)))
    trace = {"window_ns": [0, 2_000_000_000], "start_unix_ns": 10_000_000_000,
             "planes": []}
    cell = types.SimpleNamespace(
        config={"index": {"cell_dtype": "float32", "positive_only": True}},
        chips=1, traffic={"max_batch": 16})
    ctx = run.layer_context(cell, sut, win, trace, {}, 1024)
    want = roofline.scan_bytes([[0, 1], [4, 6]], [[1, 1], [2, 1]], maps,
                               1024, 4, signed=False)
    assert ctx.dispatch_bytes == [want]


def test_window_is_the_recorded_span():
    trace = {"start_unix_ns": 1_000, "planes": [
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["a", 50, 10]]}]},
        plane(0, ops=[["op", 100, 400]], modules=[])]}
    xtrace.recorded_span(trace)
    assert trace["window_ns"] == [0, 450]
    assert trace["start_unix_ns"] == 1_050
    assert xtrace.busy_ns(trace) == [400]
