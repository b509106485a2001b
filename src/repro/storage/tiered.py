"""Frequency-aware hot/cold tiering for the raw VecStore (ROADMAP item 2).

Sinnamon's sketch is the small, always-resident part (~22–25 bytes/vector in
the paper); the raw padded-CSR rows that only the Algorithm 7 exact rerank
reads dominate memory.  :class:`TieredVecStore` lets that raw store outgrow
device memory:

* the **host backing store** (numpy, authoritative, write-through) holds every
  row, partitioned into fixed-size *chunks* of ``chunk_slots`` consecutive
  slots;
* a **bounded device-side chunk cache** holds at most ``cache_chunks`` chunks
  as one ``[L, chunk_slots, P]`` array pair, sized from ``device_budget_bytes``;
* **LFU-with-aging** eviction: per-chunk access counters, halved every
  ``aging_every`` accesses so long-cold chunks lose their historical score
  (the CacheEmbedding ``freq_aware_embedding`` policy);
* **candidate-driven prefetch**: after the sketch scan returns ``[B, k']``
  candidate slots, :meth:`prefetch`/:meth:`gather_rows` promote the unique
  chunks before the rerank gathers rows;
* a **pinned set** protects chunks touched by in-flight inserts from eviction.

Writes are write-through (host first, then the resident device copy), so a
demotion is a pure map drop — nothing is ever flushed, and crash recovery
(repro.persist) sees exactly one logical store.  Promotions fire the
``vecstore.read`` failpoint *before* any cache-map mutation, so an injected
read fault can never leave a poisoned (mapped-but-unfilled) cache line.

Bit-identity contract: :meth:`gather_rows` returns exactly the rows the
resident ``VecStore`` holds, and the rerank consumes them through the same
``exact_scores_rows`` primitive — so tiered search results are bit-identical
to the fully-resident baseline (enforced by tests/test_tiered_store.py).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fault import failpoints as _fp
from repro.obs import metrics as obs_metrics


def chunk_bytes(chunk_slots: int, max_nnz: int, value_dtype) -> int:
    """Device bytes one resident chunk occupies (int32 indices + values)."""
    return chunk_slots * max_nnz * (4 + jnp.dtype(value_dtype).itemsize)


class _TierMetrics:
    """Process-global tier counters, lazily (re)bound to the current metrics
    registry — the same pattern as engine._WritePathMetrics, so
    ``obs.metrics.set_registry`` in tests takes effect on existing stores."""

    __slots__ = ("_registry", "hits", "misses", "promotions", "evictions",
                 "prefetched", "fallbacks")

    def __init__(self):
        self._registry = None

    def bind(self) -> "_TierMetrics":
        reg = obs_metrics.get_registry()
        if reg is not self._registry:
            self.hits = reg.counter(
                "repro_tier_hits_total",
                "Chunk-cache hits (unique chunks already device-resident).")
            self.misses = reg.counter(
                "repro_tier_misses_total",
                "Chunk-cache misses (chunk cold at access time).")
            self.promotions = reg.counter(
                "repro_tier_promotions_total",
                "Cold chunks copied host -> device cache.")
            self.evictions = reg.counter(
                "repro_tier_evictions_total",
                "Resident chunks demoted (LFU-with-aging victim drop).")
            self.prefetched = reg.counter(
                "repro_tier_prefetch_total",
                "Chunks promoted by candidate-driven prefetch.")
            self.fallbacks = reg.counter(
                "repro_tier_fallback_total",
                "Row gathers served straight from host backing "
                "(every cache line pinned).")
            self._registry = reg
        return self


@jax.jit
def _gather_rows_dev(ci, cv, lines, offs):
    return ci[lines, offs], cv[lines, offs]


@jax.jit
def _set_chunks_dev(ci, cv, lines, hidx, hval):
    return ci.at[lines].set(hidx), cv.at[lines].set(hval)


@jax.jit
def _set_rows_dev(ci, cv, lines, offs, idx, val):
    return (ci.at[lines, offs].set(idx),
            cv.at[lines, offs].set(val.astype(cv.dtype)))


class TieredVecStore:
    """Chunked host-RAM CSR row store behind a bounded device chunk cache.

    ``capacity``/``max_nnz`` mirror the resident ``VecStore[C, P]`` geometry.
    Exactly one of ``device_budget_bytes`` / ``cache_chunks`` sizes the cache
    (``cache_chunks`` wins when both are given); the budget is rounded down
    to whole chunks with a floor of one line.  ``device`` commits the cache
    (and every gather output) to a specific device — the per-shard caches of
    the sharded index use this.  All methods are thread-safe.
    """

    def __init__(self, capacity: int, max_nnz: int, *,
                 value_dtype="bfloat16", chunk_slots: int = 256,
                 device_budget_bytes: Optional[int] = None,
                 cache_chunks: Optional[int] = None,
                 device=None, aging_every: int = 4096):
        if chunk_slots < 1:
            raise ValueError("chunk_slots must be >= 1")
        self.max_nnz = max_nnz
        self.chunk_slots = chunk_slots
        self._vdtype = jnp.dtype(value_dtype)
        self._device = device
        self.aging_every = aging_every
        if cache_chunks is None:
            if device_budget_bytes is None:
                raise ValueError("size the cache with device_budget_bytes "
                                 "or cache_chunks")
            cache_chunks = max(1, int(device_budget_bytes)
                               // chunk_bytes(chunk_slots, max_nnz,
                                              self._vdtype))
        self.cache_chunks = int(cache_chunks)

        self.capacity = 0
        self._h_idx = np.zeros((0, max_nnz), np.int32)
        self._h_val = np.zeros((0, max_nnz), self._vdtype)
        self._freq = np.zeros((0,), np.float64)
        self._line_by_chunk = np.zeros((0,), np.int32)
        self._resize_backing(capacity)

        L, S, P = self.cache_chunks, chunk_slots, max_nnz
        self._c_idx = self._put(np.full((L, S, P), -1, np.int32))
        self._c_val = self._put(np.zeros((L, S, P), self._vdtype))
        self._chunk_by_line = np.full((L,), -1, np.int64)
        self._free_lines = list(range(L - 1, -1, -1))
        self._pinned: set[int] = set()
        self._accesses = 0
        self._lock = threading.RLock()
        self._m = _TierMetrics()
        # instance-local counters for stats()/benchmarks (the registry
        # counters aggregate across stores)
        self._hits = self._misses = self._promotions = 0
        self._evictions = self._prefetched = self._fallbacks = 0

    # -- geometry -------------------------------------------------------------
    @property
    def num_chunks(self) -> int:
        return self._h_idx.shape[0] // self.chunk_slots

    @property
    def value_dtype(self):
        return self._vdtype

    def device_bytes(self) -> int:
        return (self._c_idx.size * self._c_idx.dtype.itemsize
                + self._c_val.size * self._c_val.dtype.itemsize)

    def host_bytes(self) -> int:
        return self._h_idx.nbytes + self._h_val.nbytes

    def resident_chunks(self) -> int:
        return self.cache_chunks - len(self._free_lines)

    def _resize_backing(self, new_capacity: int) -> None:
        S = self.chunk_slots
        padded = -(-new_capacity // S) * S       # whole chunks
        grow = padded - self._h_idx.shape[0]
        if grow < 0:
            raise ValueError("TieredVecStore cannot shrink")
        if grow:
            self._h_idx = np.concatenate(
                [self._h_idx, np.full((grow, self.max_nnz), -1, np.int32)])
            self._h_val = np.concatenate(
                [self._h_val, np.zeros((grow, self.max_nnz), self._vdtype)])
            nc = padded // S
            self._freq = np.concatenate(
                [self._freq, np.zeros((nc - self._freq.size,), np.float64)])
            self._line_by_chunk = np.concatenate(
                [self._line_by_chunk,
                 np.full((nc - self._line_by_chunk.size,), -1, np.int32)])
        self.capacity = new_capacity

    def _put(self, arr):
        return (jax.device_put(arr, self._device) if self._device is not None
                else jnp.asarray(arr))

    # -- LFU with aging -------------------------------------------------------
    def _touch(self, chunks: np.ndarray) -> None:
        self._freq[chunks] += 1.0
        self._accesses += len(chunks)
        if self._accesses >= self.aging_every:
            self._freq *= 0.5                    # age: historical heat decays
            self._accesses = 0

    def _pick_victim(self) -> Optional[int]:
        """Least-frequently-used resident unpinned chunk (ties: lowest id)."""
        best, best_key = None, None
        for line in range(self.cache_chunks):
            c = int(self._chunk_by_line[line])
            if c < 0 or c in self._pinned:
                continue
            key = (self._freq[c], c)
            if best_key is None or key < best_key:
                best, best_key = c, key
        return best

    def _evict(self, chunk: int) -> None:
        line = int(self._line_by_chunk[chunk])
        self._line_by_chunk[chunk] = -1
        self._chunk_by_line[line] = -1
        self._free_lines.append(line)
        self._evictions += 1
        self._m.bind().evictions.inc()

    def _ensure_resident(self, chunks, count=None) -> bool:
        """Promote every chunk in ``chunks`` (host -> device cache).

        Returns False (promoting nothing further) if the cache is fully
        pinned before all chunks fit — the caller falls back to a direct
        host gather.  The ``vecstore.read`` failpoint fires before any
        cache-map mutation for the new chunks, so a failed promotion never
        leaves a chunk marked resident ("no cache poisoning").
        """
        need = [int(c) for c in chunks if self._line_by_chunk[c] < 0]
        if not need:
            return True
        evictable = sum(1 for line in range(self.cache_chunks)
                        if self._chunk_by_line[line] >= 0
                        and int(self._chunk_by_line[line]) not in self._pinned)
        if len(need) > len(self._free_lines) + evictable:
            return False    # can't fit: don't churn the cache for nothing
        lines = []
        for c in need:
            if not self._free_lines:
                victim = self._pick_victim()
                if victim is None:               # everything pinned
                    self._free_lines.extend(reversed(lines))
                    return False
                self._evict(victim)
            lines.append(self._free_lines.pop())
        try:
            _fp.fire("vecstore.read")            # injected cold-read faults
            S = self.chunk_slots
            view_i = self._h_idx.reshape(self.num_chunks, S, self.max_nnz)
            view_v = self._h_val.reshape(self.num_chunks, S, self.max_nnz)
            self._c_idx, self._c_val = _set_chunks_dev(
                self._c_idx, self._c_val, self._put(np.asarray(lines, np.int32)),
                self._put(view_i[need]), self._put(view_v[need]))
        except BaseException:
            self._free_lines.extend(reversed(lines))   # lines stay unmapped
            raise
        for c, line in zip(need, lines):         # commit only after the copy
            self._line_by_chunk[c] = line
            self._chunk_by_line[line] = c
        self._promotions += len(need)
        self._m.bind().promotions.inc(len(need))
        if count is not None:
            count.inc(len(need))
        return True

    # -- pinning --------------------------------------------------------------
    def _chunks_of(self, slots: np.ndarray) -> np.ndarray:
        return np.unique(np.asarray(slots, np.int64) // self.chunk_slots)

    def pin(self, chunks) -> None:
        with self._lock:
            self._pinned.update(int(c) for c in chunks)

    def unpin(self, chunks) -> None:
        with self._lock:
            for c in chunks:
                self._pinned.discard(int(c))

    @contextmanager
    def pinning(self, slots):
        """Pin the chunks covering ``slots`` for the duration of the block."""
        chunks = self._chunks_of(slots)
        added = [int(c) for c in chunks if int(c) not in self._pinned]
        self.pin(added)
        try:
            yield
        finally:
            self.unpin(added)

    # -- reads ----------------------------------------------------------------
    def gather_rows(self, slots) -> Tuple[jax.Array, jax.Array]:
        """Device rows for ``slots`` (flat int array) — the rerank feed.

        Promotes the unique cold chunks first (LFU eviction as needed); when
        the cache is fully pinned the rows are served straight from the host
        backing instead (prefetch-miss fallback) so a query never blocks on
        an unevictable cache.  Returns (int32[K, P], value_dtype[K, P]).
        """
        with self._lock:
            slots = np.asarray(slots, np.int64).reshape(-1)
            chunks = self._chunks_of(slots)
            self._touch(chunks)
            m = self._m.bind()
            hits = int(np.sum(self._line_by_chunk[chunks] >= 0))
            self._hits += hits
            self._misses += len(chunks) - hits
            m.hits.inc(hits)
            m.misses.inc(len(chunks) - hits)
            if self._ensure_resident(chunks):
                lines = self._line_by_chunk[slots // self.chunk_slots]
                offs = slots % self.chunk_slots
                return _gather_rows_dev(
                    self._c_idx, self._c_val,
                    self._put(lines.astype(np.int32)),
                    self._put(offs.astype(np.int32)))
            self._fallbacks += 1
            m.fallbacks.inc()
            return (self._put(self._h_idx[slots]),
                    self._put(self._h_val[slots]))

    def prefetch(self, slots) -> int:
        """Promote the chunks covering candidate ``slots`` (best effort).

        Returns the number of chunks promoted.
        """
        with self._lock:
            chunks = self._chunks_of(slots)
            self._touch(chunks)
            before = self._promotions
            self._ensure_resident(chunks, count=self._m.bind().prefetched)
            n = self._promotions - before
            self._prefetched += n
            return n

    def read_indices(self, slots) -> np.ndarray:
        """Host read of index rows (no promotion) — the delete bit-clear feed."""
        with self._lock:
            return self._h_idx[np.asarray(slots, np.int64)].copy()

    def read_rows(self, slots) -> Tuple[np.ndarray, np.ndarray]:
        """Host read of full rows (no promotion) — compaction/drift feed."""
        with self._lock:
            slots = np.asarray(slots, np.int64)
            return self._h_idx[slots].copy(), self._h_val[slots].copy()

    # -- writes (write-through) ----------------------------------------------
    def write_rows(self, slots, idx_rows, val_rows, *, pin: bool = False):
        """Write CSR rows: host backing first, then any resident device copy.

        With ``pin=True`` the touched chunks are left pinned (caller unpins
        once the in-flight insert's device work is dispatched); the pinned
        chunk ids are returned either way.
        """
        with self._lock:
            slots = np.asarray(slots, np.int64).reshape(-1)
            idx_rows = np.asarray(idx_rows, np.int32).reshape(
                slots.size, self.max_nnz)
            val_rows = np.asarray(val_rows).astype(self._vdtype).reshape(
                slots.size, self.max_nnz)
            self._h_idx[slots] = idx_rows
            self._h_val[slots] = val_rows
            chunks = self._chunks_of(slots)
            self._touch(chunks)
            if pin:
                self.pin(chunks)
            lines = self._line_by_chunk[slots // self.chunk_slots]
            res = lines >= 0
            if res.any():
                self._c_idx, self._c_val = _set_rows_dev(
                    self._c_idx, self._c_val,
                    self._put(lines[res].astype(np.int32)),
                    self._put((slots[res] % self.chunk_slots).astype(np.int32)),
                    self._put(idx_rows[res]), self._put(val_rows[res]))
            return chunks

    def erase_rows(self, slots) -> None:
        slots = np.asarray(slots, np.int64).reshape(-1)
        self.write_rows(
            slots, np.full((slots.size, self.max_nnz), -1, np.int32),
            np.zeros((slots.size, self.max_nnz), self._vdtype))

    # -- bulk / lifecycle -----------------------------------------------------
    def to_arrays(self) -> Tuple[np.ndarray, np.ndarray]:
        """The full logical store as host arrays [capacity, P] (snapshots)."""
        with self._lock:
            return (self._h_idx[:self.capacity].copy(),
                    self._h_val[:self.capacity].copy())

    def load_rows(self, indices: np.ndarray, values: np.ndarray) -> None:
        """Replace the whole backing store (snapshot restore).

        Tiering state resets to access-free defaults: empty cache, zero
        frequencies, nothing pinned — recovery never trusts pre-crash heat.
        """
        with self._lock:
            indices = np.asarray(indices, np.int32)
            self.capacity = 0
            self._h_idx = np.zeros((0, self.max_nnz), np.int32)
            self._h_val = np.zeros((0, self.max_nnz), self._vdtype)
            self._freq = np.zeros((0,), np.float64)
            self._line_by_chunk = np.zeros((0,), np.int32)
            self._resize_backing(indices.shape[0])
            self._h_idx[:indices.shape[0]] = indices
            self._h_val[:indices.shape[0]] = np.asarray(values).astype(
                self._vdtype)
            L = self.cache_chunks
            self._chunk_by_line = np.full((L,), -1, np.int64)
            self._free_lines = list(range(L - 1, -1, -1))
            self._pinned.clear()
            self._accesses = 0

    def grow(self, new_capacity: int) -> None:
        """Extend the host backing (cache geometry is unchanged)."""
        with self._lock:
            self._resize_backing(new_capacity)

    # -- reporting ------------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            total = self._hits + self._misses
            return {
                "hits": self._hits, "misses": self._misses,
                "promotions": self._promotions, "evictions": self._evictions,
                "prefetched": self._prefetched, "fallbacks": self._fallbacks,
                "hit_rate": (self._hits / total) if total else 0.0,
                "resident_chunks": self.resident_chunks(),
                "cache_chunks": self.cache_chunks,
                "num_chunks": self.num_chunks,
                "resident_bytes": self.device_bytes(),
                "host_bytes": self.host_bytes(),
            }
