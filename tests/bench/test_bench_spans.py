"""The readers of the program's spans on the profiler's clock
(``server.host_ms``, ``frontend.turnaround_ms``), on a small trace whose
answers are counted by hand and on a trace recorded on the chip."""

import gzip
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import catalog, xtrace  # noqa: E402

MS = 1_000_000

# One chip, a 100 ms window, three dispatches.  Host spans: server/device
# [5, 40), [45, 80), [90, 99); the search program runs [10, 38), [50, 78)
# and [91, 98) on the chip, another program [0, 2).
TRACE = {
    "window_ns": [0, 100 * MS],
    "start_unix_ns": 0,
    "planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "frontend-dispatch", "events": [
                ["server/device", 5 * MS, 35 * MS],
                ["engine/launch", 6 * MS, 3 * MS],
                ["server/device", 45 * MS, 35 * MS],
                ["server/device", 90 * MS, 9 * MS]]},
            {"name": "python3", "events": [
                ["np.asarray(jax.Array)", 41 * MS, 2 * MS]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_insert_batch(1)", 0, 2 * MS],
                ["jit_search_batch(7)", 10 * MS, 28 * MS],
                ["jit_search_batch(7)", 50 * MS, 28 * MS],
                ["jit_search_batch(7)", 91 * MS, 7 * MS]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 10 * MS, 28 * MS],
                ["fusion.1", 50 * MS, 28 * MS],
                ["fusion.1", 91 * MS, 7 * MS]]}]},
    ],
}


def ctx(**kw):
    base = dict(trace=TRACE, chips=1, max_batch=16, queue_ms=[],
                batch_sizes=(0, 0), dispatch_bytes=[],
                peak={"hbm_bytes_per_s": 819e9},
                pending=lambda s, e: True, collectives=r"all-gather")
    base.update(kw)
    return types.SimpleNamespace(**base)


def read(metric, **kw):
    return catalog.metric_reader(metric)(ctx(**kw))


def test_server_host_time_is_the_span_less_the_program():
    # (35 - 28) + (35 - 28) + (9 - 7) ms over three dispatches
    assert read("server.host_ms") == pytest.approx((7 + 7 + 2) / 3)


def test_turnaround_counts_only_gaps_with_a_request_waiting():
    # gaps [40, 45) and [80, 90)
    assert read("frontend.turnaround_ms") == pytest.approx(7.5)
    waiting = lambda s, e: s < 60 * MS                       # noqa: E731
    assert read("frontend.turnaround_ms", pending=waiting) == \
        pytest.approx(5.0)
    assert read("frontend.turnaround_ms",
                pending=lambda s, e: False) is None


def test_span_readers_read_nothing_without_spans_or_trace():
    bare = {"window_ns": [0, 10], "start_unix_ns": 0, "planes": [
        p for p in TRACE["planes"] if p["name"] != "/host:CPU"]}
    for name in ("server.host_ms", "frontend.turnaround_ms"):
        assert read(name, trace=None) is None
        assert read(name, trace=bare) is None
    assert read("frontend.turnaround_ms", pending=None) is None


def test_span_readers_on_the_older_recorded_trace_read_nothing():
    """A program without the spans (the recorded trace predates them)."""
    old = json.loads((Path(__file__).resolve().parent / "data"
                      / "splade_open_trace.json").read_text())
    for name in ("server.host_ms", "frontend.turnaround_ms"):
        assert read(name, trace=old) is None


RECORDED = Path(__file__).resolve().parent / "data" / "splade_open_spans.json"


def recorded():
    """Two dispatches of ``splade_f32.open`` recorded on a TPU v5e, with
    the program's spans; ``requests_ns`` holds each request's (submitted,
    dispatched) times on the trace's clock."""
    trace = json.loads(RECORDED.read_text())
    sub, disp = np.array(trace.pop("requests_ns")).T
    return trace, lambda s, e: bool(np.any((sub < e) & (disp > s)))


def test_recorded_spans_lie_on_the_device_clock():
    trace, _ = recorded()
    spans = {}
    for name, s, d in xtrace.host_events(trace):
        spans.setdefault(name, []).append((s, s + d))
    dev = spans["server/device"]
    assert len(dev) == 2
    for name in ("engine/launch", "engine/fetch"):
        assert len(spans[name]) == 2
        for (s, e), (ds, de) in zip(spans[name], dev):
            assert ds <= s and e <= de
    plane, = xtrace.device_planes(trace)
    runs = [e for e in xtrace.line_events(plane, xtrace.MODULES_LINE)
            if e[0].startswith("jit_search_batch")]
    assert len(runs) == 2
    for (ds, de), (_n, rs, rd) in zip(dev, runs):
        # each program execution lies within its dispatch's span, give or
        # take the profiler's host-device clock alignment (about 1.4 ms)
        assert ds - 2 * MS <= rs and rs + rd <= de


def test_recorded_span_readers():
    trace, pending = recorded()
    dev = sorted((s, s + d) for n, s, d in xtrace.host_events(trace)
                 if n == "server/device")
    plane, = xtrace.device_planes(trace)
    runs = [(e[1], e[2]) for e in xtrace.line_events(plane,
                                                     xtrace.MODULES_LINE)]
    host = [(e - s) - xtrace.union_ns(runs, s, e) for s, e in dev]
    got = read("server.host_ms", trace=trace, pending=pending)
    assert got == pytest.approx(sum(host) / 2 / 1e6)
    assert 1.0 < got < 10.0
    assert pending(dev[0][1], dev[1][0])
    assert read("frontend.turnaround_ms", trace=trace, pending=pending) \
        == pytest.approx((dev[1][0] - dev[0][1]) / 1e6)
    assert read("search.device_ms", trace=trace) > 150.0
