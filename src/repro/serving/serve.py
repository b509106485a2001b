"""Host-side serving drivers for the retrieval engine.

``QueryServer`` — batched query serving over a (possibly sharded) Sinnamon
index with the paper's anytime budget as the latency lever.  ``query`` /
``query_many`` return a typed :class:`repro.serving.results.QueryResult`
(ids, scores, k, backend, trace id) — the level-2 host surface over the
level-1 functional ``engine.search`` / ``search_batch`` (see
docs/serving.md).  Every query reports into a metrics registry
(`repro.obs`): latency/batch histograms per scoring backend.  The batch's
trace context is active around the device call, so the index records its
``launch`` and ``fetch`` stages into it; with a profiler session running
those stages, and the ``device`` stage around them, are spans of the
profiler's trace, and the fused program's ``operands`` / ``scan`` /
``topk`` / ``rerank`` named scopes split its device time (see
docs/observability.md).  Concurrent-client admission,
dynamic batching and quotas live one level up, in
``repro.serving.frontend``; under overload the front door asks for
degraded answers (``query_many(..., degrade=N)``: shrunken rerank budget,
then sketch-only scoring — see docs/robustness.md).
"""

from __future__ import annotations

import time
from typing import Optional, Union

from repro.core.engine import SinnamonIndex
from repro.fault import failpoints as _fp
from repro.obs import events as obs_events
from repro.obs import metrics as obs_metrics
from repro.obs import recorder as obs_recorder
from repro.obs.instrument import install_engine_gauges
from repro.obs.trace import TraceContext
from repro.serving.results import QueryResult
from repro.serving.sharded import ShardedSinnamonIndex


class QueryServer:
    """Serves one index — single-device or mesh-sharded; both expose the same
    ``search`` / ``search_many`` surface, so the server is layout-agnostic.

    ``score_backend`` picks the index's scoring backend per server
    (``reference | grouped | pallas``; None -> process default, see
    repro.kernels.ops.resolve_backend).

    Telemetry: every query records into ``registry`` (default: the
    process-global `repro.obs.metrics.get_registry()`; inject
    ``NULL_REGISTRY`` to turn metrics off); each batch also emits a
    ``query`` event to the active event log.  Engine health gauges for
    ``index`` are installed on construction (weakref — dropping the server
    and index detaches them).

    Durable indexes (repro.persist.durable) serve through the same surface,
    and the server keeps answering during snapshots and background
    compaction: searches read the immutable state ref without taking the
    index's op lock, so maintenance never blocks the query path.
    """

    def __init__(self, index: Union[SinnamonIndex, ShardedSinnamonIndex],
                 k: int = 10, kprime: int = 1000,
                 budget: Optional[int] = None, score_fn=None,
                 score_backend: Optional[str] = None,
                 registry=None, event_log=None,
                 index_name: str = "index", recorder=None):
        self.index = index
        self.k, self.kprime, self.budget = k, kprime, budget
        self.score_fn = score_fn
        self.score_backend = score_backend
        self.registry = (obs_metrics.get_registry() if registry is None
                         else registry)
        self.event_log = event_log
        self.recorder = recorder
        self.stats = {"queries": 0}
        self.last_latency_ms = 0.0       # most recent per-query latency
        self._handles: dict = {}
        install_engine_gauges(index, self.registry, name=index_name)

    # -- metric handles (cached per label set) -------------------------------
    def _backend_label(self) -> str:
        if self.score_fn is not None:
            return "custom"
        from repro.kernels import ops as _ops
        backend = self.score_backend
        if backend is None:     # index default (repro.api) > process default
            backend = getattr(self.index, "default_backend", None)
        return _ops.resolve_backend(backend)

    def _hist(self, name: str, help_text: str, labels=None, buckets=None):
        key = (name, tuple(sorted((labels or {}).items())))
        h = self._handles.get(key)
        if h is None:
            h = self.registry.histogram(name, help_text, labels=labels,
                                        buckets=buckets)
            self._handles[key] = h
        return h

    def _latency_hist(self, backend: str):
        return self._hist("repro_query_latency_ms",
                          "Per-query serving latency.",
                          labels={"backend": backend})

    def _recorder(self):
        return self.recorder if self.recorder is not None \
            else obs_recorder.get_recorder()

    def _fail(self, ctx: TraceContext, owns: bool, e: BaseException) -> None:
        """Seal + record an errored context this server owns."""
        if not owns:
            return      # the front door owns the context's lifecycle
        ctx.finish("error", error=repr(e))
        rec = self._recorder()
        if rec is not None:
            rec.record(ctx)

    # -- serving -------------------------------------------------------------
    def query(self, q_idx, q_val, ctx: Optional[TraceContext] = None) \
            -> QueryResult:
        """Serve one query.  Returns a :class:`repro.serving.QueryResult`
        (``[k]`` ids/scores; unpackable as the legacy ``(ids, scores)``).

        ``ctx`` is an optional propagated :class:`TraceContext`; without
        one the server opens (and records) its own, so the result's
        ``trace_id`` resolves at ``/debug/trace/<id>`` whenever a flight
        recorder is installed."""
        backend = self._backend_label()
        owns = ctx is None
        if owns:
            ctx = TraceContext()
        try:
            with ctx.stage("device"):
                t0 = time.perf_counter()
                _fp.fire("device.dispatch")
                ids, scores = self.index.search(
                    q_idx, q_val, k=self.k, kprime=self.kprime,
                    budget=self.budget, score_fn=self.score_fn,
                    backend=self.score_backend)
                dt_ms = (time.perf_counter() - t0) * 1e3
        except Exception as e:
            self._fail(ctx, owns, e)
            raise
        self._record(1, dt_ms, backend, ctx=ctx, owns=owns)
        return QueryResult(ids=ids, scores=scores, k=len(ids),
                           backend=backend, trace_id=ctx.trace_id)

    def query_many(self, q_idx, q_val,
                   ctx: Optional[TraceContext] = None,
                   degrade: int = 0) -> QueryResult:
        """Batched serving path: [B, Lq] queries in ONE device dispatch.

        Amortizes dispatch + (on a sharded index) the candidate merge across
        the batch; per-query latency is recorded as batch time / B, so the
        percentile accounting stays comparable with :meth:`query`.  Returns
        one batched :class:`QueryResult` (``[B, k]``; ``.row(i)`` slices out
        a per-request result).

        With a caller-provided ``ctx`` (the front door's batch context) the
        server only annotates it — the caller seals and records it; without
        one the server owns the context end to end.

        ``degrade`` (the front door's ladder level): 1 shrinks the rerank
        candidate pool to k'/4; ≥2 answers sketch-only when the index
        supports it (scores become upper bounds).  Any degraded answer is
        stamped ``degraded=True`` and annotated on the trace.  Each level
        maps to one fixed jit specialization, so the ladder never causes
        per-request recompiles.
        """
        bn = len(q_idx)
        backend = self._backend_label()
        owns = ctx is None
        if owns:
            ctx = TraceContext()
        sketch_only = (degrade >= 2 and self.score_fn is None
                       and hasattr(self.index, "search_many_sketch"))
        try:
            with ctx.stage("device"), ctx.activate():
                t0 = time.perf_counter()
                _fp.fire("device.dispatch")
                if sketch_only:
                    ids, scores = self.index.search_many_sketch(
                        q_idx, q_val, k=self.k, budget=self.budget,
                        backend=self.score_backend)
                else:
                    kprime = self.kprime
                    if degrade >= 1:
                        if kprime is None:
                            kprime = max(5 * self.k, self.k)
                        kprime = max(self.k, kprime // 4)
                    # Rerank-bearing paths only: a stalled/broken rerank
                    # is exactly what sketch-only degradation sidesteps.
                    _fp.fire("device.rerank")
                    ids, scores = self.index.search_many(
                        q_idx, q_val, k=self.k, kprime=kprime,
                        budget=self.budget, score_fn=self.score_fn,
                        backend=self.score_backend)
                dt_ms = (time.perf_counter() - t0) * 1e3
        except Exception as e:
            self._fail(ctx, owns, e)
            raise
        if degrade > 0:
            ctx.annotate(degraded=True, degrade_level=int(degrade),
                         sketch_only=sketch_only)
        self._record(bn, dt_ms, backend, ctx=ctx, owns=owns)
        return QueryResult(ids=ids, scores=scores, k=ids.shape[-1],
                           backend=backend, trace_id=ctx.trace_id,
                           degraded=degrade > 0)

    def _record(self, bn: int, dt_ms: float, backend: str,
                ctx: Optional[TraceContext] = None,
                owns: bool = False) -> None:
        per_query = dt_ms / bn
        self.stats["queries"] += bn
        self.last_latency_ms = per_query
        retained = None
        if ctx is not None:
            ctx.annotate(backend=backend, batch=bn)
            if owns:
                ctx.finish("ok", total_ms=dt_ms)
                rec = self._recorder()
                if rec is not None:
                    retained = rec.record(ctx)
        # exemplar only when the id actually resolves in the recorder ring
        self._latency_hist(backend).observe(
            per_query, n=bn,
            exemplar=ctx.trace_id if (ctx is not None and retained) else None)
        self._hist("repro_query_batch_docs", "Queries per serving batch.",
                   buckets=obs_metrics.DEFAULT_COUNT_BUCKETS).observe(bn)
        self.registry.counter("repro_queries_total", "Queries served.",
                              labels={"backend": backend}).inc(bn)
        log = self.event_log if self.event_log is not None \
            else obs_events.get_event_log()
        if log is not None:
            log.emit("query", batch=bn, ms=round(dt_ms, 4), backend=backend,
                     trace_id=ctx.trace_id if ctx is not None else None,
                     stages=ctx.to_dict()["stages"] if ctx is not None
                     else None)

    # -- stats ---------------------------------------------------------------
    def latency_percentiles(self):
        """Compat shim over the registry latency histogram (the one shared
        percentile implementation — `obs.metrics.Histogram.percentile`)."""
        h = self._latency_hist(self._backend_label())
        if h.count == 0:
            return {}
        return {f"p{p}": h.percentile(p) for p in (50, 90, 99)}

    def reset_stats(self) -> None:
        """Zero the query counter and this server's latency samples
        (the shared-registry histogram for the current backend label)."""
        self.stats["queries"] = 0
        self._latency_hist(self._backend_label()).reset()
