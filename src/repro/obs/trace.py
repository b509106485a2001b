"""Propagated per-request trace context, timed on the profiler's clock too.

`TraceContext` is the *propagated* per-request context: created at the
front door (`ServingFrontend.submit`) or at `QueryServer.query*`, threaded
through quota check → admission queue → batch assembly → device dispatch →
response, accumulating per-stage wall-clock timings and annotations (which
coalesced batch the request rode in, its outcome).  Finished contexts go to
the flight recorder (`repro.obs.recorder`) so a ``QueryResult.trace_id``
resolves to a full stage breakdown at ``/debug/trace/<id>``.

Every stage timed with :meth:`TraceContext.stage` is also written into the
JAX profiler's trace, while a profiler session runs, as a
``jax.profiler.TraceAnnotation`` named ``PROFILER_NAMES[stage]`` — so the
host side of a dispatch (``server/device``, ``engine/launch``,
``engine/fetch``, ...) lies on the same clock as the device operations it
launched.  With no session running a stage costs two ``perf_counter``
calls, one ``is_enabled`` check and a list append.  JAX is looked up only
once a process has imported it: this module stays importable without it.

Code below the server records into the batch's context without taking it
as a parameter: ``QueryServer.query_many`` activates the context
(:meth:`TraceContext.activate`) on its thread, and the index's search calls
the module-level :func:`stage`, which times into whatever context is active
(or, with none, only annotates the profiler's trace).
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from typing import Optional

__all__ = ["PROFILER_NAMES", "TraceContext", "active", "new_trace_id",
           "stage"]

#: Context stage -> name of its span in the profiler's trace.  Stages not
#: listed keep their own name there.
PROFILER_NAMES = {
    "assembly": "frontend/assembly",
    "device": "server/device",
    "launch": "engine/launch",
    "fetch": "engine/fetch",
    "promote": "engine/promote",
    "respond": "frontend/respond",
}

_trace_counter = itertools.count(1)
_trace_lock = threading.Lock()
_local = threading.local()          # .ctx: the thread's active context
_annotation = None                  # TraceAnnotation class once resolved


def new_trace_id() -> str:
    """Process-unique, monotonically increasing query trace id."""
    with _trace_lock:
        n = next(_trace_counter)
    return f"q-{os.getpid():x}-{n:x}"


def _profiler_span(name: str):
    """An entered ``TraceAnnotation`` for stage ``name`` while a profiler
    session runs, else None.  A process that has not imported JAX runs no
    session, so JAX is never imported here."""
    global _annotation
    ann = _annotation
    if ann is None:
        profiler = sys.modules.get("jax.profiler")
        if profiler is None:
            return None
        ann = _annotation = profiler.TraceAnnotation
    if not ann.is_enabled():
        return None
    span = ann(PROFILER_NAMES.get(name, name))
    span.__enter__()
    return span


class _CtxSpan:
    __slots__ = ("_ctx", "_name", "_t0", "_span")

    def __init__(self, ctx: Optional["TraceContext"], name: str):
        self._ctx = ctx
        self._name = name

    def __enter__(self):
        self._span = _profiler_span(self._name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
        ctx = self._ctx
        if ctx is not None:
            ctx.stages.append((self._name, (self._t0 - ctx._t0) * 1e3,
                               (t1 - self._t0) * 1e3))
        return False


class _Activation:
    __slots__ = ("_ctx", "_prev")

    def __init__(self, ctx: "TraceContext"):
        self._ctx = ctx

    def __enter__(self):
        self._prev = getattr(_local, "ctx", None)
        _local.ctx = self._ctx
        return self._ctx

    def __exit__(self, exc_type, exc, tb):
        _local.ctx = self._prev
        return False


def active() -> Optional["TraceContext"]:
    """The context activated on this thread, if any."""
    return getattr(_local, "ctx", None)


def stage(name: str) -> _CtxSpan:
    """Context manager timing one stage into this thread's active context
    (see :meth:`TraceContext.activate`); with no active context it only
    annotates the profiler's trace."""
    return _CtxSpan(getattr(_local, "ctx", None), name)


class TraceContext:
    """One request's propagated trace: id, stage timings, annotations.

    Stages are ``(name, start_ms, dur_ms)`` with ``start_ms`` relative to
    context creation (``None`` where only a duration was recorded, such as
    the batch stages every rider of a coalesced dispatch inherits).  A
    context is built up by exactly one thread at a time (submit thread,
    then the dispatcher) — the hand-off happens through the admission
    queue, so no locking is needed.

    The context is deliberately cheap to create and finish (a couple of
    ``perf_counter`` calls and list appends): every request gets one, and
    the *retention* decision is the flight recorder's, made at completion
    — tail sampling, not head sampling.
    """

    __slots__ = ("trace_id", "tenant", "ts", "_t0", "stages",
                 "annotations", "outcome", "error", "total_ms")

    def __init__(self, tenant: str = "default",
                 trace_id: Optional[str] = None):
        self.trace_id = trace_id if trace_id is not None else new_trace_id()
        self.tenant = tenant
        self.ts = time.time()                # wall-clock anchor (unix)
        self._t0 = time.perf_counter()       # monotonic anchor
        self.stages: list = []               # [name, start_ms|None, dur_ms]
        self.annotations: dict = {}
        self.outcome: Optional[str] = None
        self.error: Optional[str] = None
        self.total_ms: Optional[float] = None

    # -- recording -----------------------------------------------------------
    def stage(self, name: str) -> _CtxSpan:
        """Context manager timing one stage of this request; while a
        profiler session runs it is also a span of the profiler's trace
        (``PROFILER_NAMES``)."""
        return _CtxSpan(self, name)

    def activate(self) -> _Activation:
        """Context manager making this the thread's active context, which
        the module-level :func:`stage` records into."""
        return _Activation(self)

    def add_stage(self, name: str, dur_ms: float,
                  start_ms: Optional[float] = None) -> None:
        """Record a stage timed externally (e.g. with the frontend's
        injectable clock); ``start_ms`` is relative to context creation."""
        self.stages.append((name, start_ms, float(dur_ms)))

    def elapsed_ms(self) -> float:
        return (time.perf_counter() - self._t0) * 1e3

    def annotate(self, **fields) -> None:
        """Attach key/value annotations (batch id, width bucket, ...)."""
        self.annotations.update(fields)

    def finish(self, outcome: str, total_ms: Optional[float] = None,
               error: Optional[str] = None) -> "TraceContext":
        """Seal the context: outcome + total latency.  ``total_ms`` defaults
        to the context's own elapsed wall clock."""
        self.outcome = outcome
        self.error = error
        self.total_ms = self.elapsed_ms() if total_ms is None \
            else float(total_ms)
        return self

    # -- reading -------------------------------------------------------------
    def stage_ms(self) -> dict:
        """{stage: dur_ms}; repeated stage names accumulate."""
        out: dict = {}
        for name, _start, dur in self.stages:
            out[name] = out.get(name, 0.0) + dur
        return out

    def to_dict(self) -> dict:
        d = {
            "trace_id": self.trace_id,
            "tenant": self.tenant,
            "ts": round(self.ts, 6),
            "outcome": self.outcome,
            "total_ms": None if self.total_ms is None
            else round(self.total_ms, 4),
            "stages": [
                {"stage": name,
                 **({} if start is None
                    else {"start_ms": round(start, 4)}),
                 "ms": round(dur, 4)}
                for name, start, dur in self.stages
            ],
        }
        if self.error is not None:
            d["error"] = self.error
        if self.annotations:
            d.update(self.annotations)
        return d

    def __repr__(self) -> str:
        return (f"TraceContext({self.trace_id!r}, tenant={self.tenant!r}, "
                f"outcome={self.outcome!r}, stages={len(self.stages)})")
