"""Observability overhead benchmark (the ISSUE 6 acceptance gate).

Measures the serving-path cost of the metrics registry by timing the SAME
query stream through two QueryServer configurations over one shared index:

* ``off``    — ``NULL_REGISTRY`` injected, no recorder: every metric call
  is a no-op attribute chain, the zero-instrumentation baseline.
* ``on``     — a real ``MetricsRegistry`` PLUS the full ISSUE 8 stack:
  per-query latency histograms and counters, a per-batch `TraceContext`
  (with its ``device``, ``launch`` and ``fetch`` stages), a tail-sampled
  `FlightRecorder`, and a ticking `SLOMonitor` (the always-on production
  path).

Rounds alternate off/on so drift (thermal, allocator state) hits both
equally, and p50s come from external ``perf_counter`` timing around
``query_many`` — the registry never times itself.

Beside them, the cost of one trace-context stage (a span) in µs: with no
profiler session (``span_off_us``) and inside a ``jax.profiler`` trace,
where each stage is also a ``TraceAnnotation`` (``span_on_us``).

Gate: ``on`` p50 at batch 8 must be within 5% of ``off`` p50
(``obs_overhead/gate``); the row errors the run (and CI) when exceeded.
"""

from __future__ import annotations

import time

import numpy as np

_BATCH = 8
_ROUNDS = 40
_GATE_PCT = 5.0
_SPANS = 20000


def _bench(docs=2048, batch=_BATCH, rounds=_ROUNDS):
    from benchmarks.query_path import _QUERIES, _build
    from repro.obs import FlightRecorder, NULL_REGISTRY, MetricsRegistry
    from repro.obs.slo import SLOMonitor, SLOSpec
    from repro.serving.serve import QueryServer

    index, _, _, qi, qv = _build(docs)

    # the production configuration the gate must hold with: registry +
    # flight recorder + ticking SLO monitor
    reg = MetricsRegistry()
    rec = FlightRecorder(capacity=512, sample_rate=0.05, registry=reg,
                         spill=False)
    on_slo = SLOMonitor(SLOSpec(), reg).start(interval_s=0.25)
    servers = {
        "off": QueryServer(index, k=10, kprime=100, registry=NULL_REGISTRY),
        "on": QueryServer(index, k=10, kprime=100, registry=reg,
                          recorder=rec),
    }
    for srv in servers.values():                     # compile warmup
        for _ in range(8):
            srv.query_many(qi[:batch], qv[:batch])

    samples = {name: [] for name in servers}
    for _ in range(rounds):
        # interleave so machine drift is shared, not attributed to one mode
        for name, srv in servers.items():
            t0 = time.perf_counter()
            for lo in range(0, _QUERIES, batch):
                srv.query_many(qi[lo:lo + batch], qv[lo:lo + batch])
            samples[name].append((time.perf_counter() - t0) * 1e3
                                 / _QUERIES)
    on_slo.stop()
    return ({name: float(np.median(v)) for name, v in samples.items()},
            {name: float(np.percentile(v, 99)) for name, v in samples.items()})


def span_us(n: int = _SPANS) -> dict:
    """µs per trace-context stage, with the profiler off and on."""
    import tempfile

    import jax

    from repro.obs.trace import TraceContext

    def per_span():
        ctx = TraceContext()
        t0 = time.perf_counter()
        for _ in range(n):
            with ctx.stage("launch"):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    out = {"off": per_span()}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["on"] = per_span()
        finally:
            jax.profiler.stop_trace()
    return out


def obs_overhead():
    """Registry on/off p50/p99 per-query latency, the <=5% gate, and the
    cost of one span with the profiler off and on."""
    p50, p99 = _bench()
    spans = span_us()
    overhead_pct = (p50["on"] / max(p50["off"], 1e-9) - 1.0) * 100.0
    rows = [
        (f"obs_overhead/b{_BATCH}/off_p50_ms", f"{p50['off']:.4f}",
         "NULL_REGISTRY baseline"),
        (f"obs_overhead/b{_BATCH}/on_p50_ms", f"{p50['on']:.4f}",
         "metrics + flight recorder + SLO monitor on"),
        (f"obs_overhead/b{_BATCH}/off_p99_ms", f"{p99['off']:.4f}", ""),
        (f"obs_overhead/b{_BATCH}/on_p99_ms", f"{p99['on']:.4f}", ""),
        (f"obs_overhead/b{_BATCH}/overhead_pct", f"{overhead_pct:.2f}",
         f"% (gate <= {_GATE_PCT})"),
        ("obs_overhead/span_off_us", f"{spans['off']:.3f}",
         "one trace-context stage, no profiler session"),
        ("obs_overhead/span_on_us", f"{spans['on']:.3f}",
         "one stage, also a TraceAnnotation in a profiler trace"),
    ]
    if overhead_pct > _GATE_PCT:
        raise RuntimeError(
            f"metrics overhead {overhead_pct:.2f}% > {_GATE_PCT}% gate "
            f"(off p50 {p50['off']:.4f}ms vs on p50 {p50['on']:.4f}ms)")
    rows.append((f"obs_overhead/b{_BATCH}/gate", "PASS",
                 f"on within {_GATE_PCT}% of off"))
    return rows


ALL = [obs_overhead]


if __name__ == "__main__":
    # Standalone entry: `python benchmarks/obs_overhead.py [--json PATH]`.
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import run as _run

    sys.argv = [sys.argv[0], "obs_overhead"] + sys.argv[1:]
    _run.main()
