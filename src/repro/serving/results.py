"""Typed query results for the serving surface.

The two-level serving API (documented in docs/serving.md):

* **Level 1 — functional**: ``repro.core.engine.search`` /
  ``search_batch`` are pure jittable functions of ``(state, spec)``;
  they return device arrays and exist for composition (shard_map bodies,
  custom pipelines).
* **Level 2 — host serving**: ``QueryServer.query`` / ``query_many`` (and
  the async front door, ``repro.serving.frontend``) own host concerns —
  metrics, tracing, padding — and return a :class:`QueryResult`.

``QueryResult`` is frozen (the arrays it carries are the response; mutate
copies, not the result) and remains unpackable as the legacy
``(ids, scores)`` tuple so existing call sites keep working during the
migration to the typed surface.

``trace_id`` generation lives in ``repro.obs.trace`` (re-exported here for
compatibility) so every serving layer draws from ONE id namespace: a
result's trace id resolves against the flight recorder at
``/debug/trace/<id>`` regardless of which layer created it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.obs.trace import new_trace_id

__all__ = ["QueryResult", "new_trace_id"]


@dataclasses.dataclass(frozen=True, eq=False)
class QueryResult:
    """One query (or query batch) answer from the serving surface.

    ``ids``/``scores`` are ``[k]`` for :meth:`QueryServer.query` and
    ``[B, k]`` for :meth:`QueryServer.query_many` / coalesced front-door
    batches.  ``backend`` is the resolved scoring backend that produced the
    candidates (``reference | grouped | pallas | custom``); ``trace_id``
    correlates the response with metric samples and event-log entries.

    Tuple-compat shim: iterating/indexing yields ``(ids, scores)`` so legacy
    ``ids, scores = server.query(...)`` call sites keep working.
    """

    ids: np.ndarray
    scores: np.ndarray
    k: int
    backend: str
    trace_id: str
    #: True when the answer was produced under the serving degradation
    #: ladder (shrunken rerank budget or sketch-only scoring) — scores may
    #: be upper bounds rather than exact inner products.
    degraded: bool = False

    # -- legacy (ids, scores) tuple compatibility ---------------------------
    def __iter__(self):
        return iter((self.ids, self.scores))

    def __getitem__(self, i):
        return (self.ids, self.scores)[i]

    def __len__(self) -> int:
        return 2

    # -- batch helpers -------------------------------------------------------
    @property
    def batch_size(self) -> Optional[int]:
        """B for a batched result, None for a single-query result."""
        return self.ids.shape[0] if self.ids.ndim == 2 else None

    def row(self, i: int, k: Optional[int] = None,
            trace_id: Optional[str] = None) -> "QueryResult":
        """Per-request slice of a batched result (optionally trimmed to a
        smaller ``k``); the front door uses this to split a coalesced batch
        back into individual responses."""
        if self.ids.ndim != 2:
            raise ValueError("row() is only defined on batched results")
        kk = self.k if k is None else min(int(k), self.k)
        return QueryResult(ids=self.ids[i, :kk], scores=self.scores[i, :kk],
                           k=kk, backend=self.backend,
                           trace_id=trace_id or self.trace_id,
                           degraded=self.degraded)
