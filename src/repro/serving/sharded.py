"""Sharded Sinnamon serving: the paper's engine as an SPMD program.

Corpus slots are sharded over the (pod, model) mesh axes, the query batch over
data.  Scoring and the exact rerank are fully shard-local; only (k'-sized)
candidate tuples cross shards (see repro.distributed.topk).

This module now covers the full *streaming* lifecycle at sharded scale:

* ``make_search_step``  — batched SPMD search (the original serve step),
  returning external ids plus packed (shard, slot) locators.
* ``make_insert_step`` / ``make_delete_step`` — collective-free shard-local
  updates: the host routes each document to its owning shard (hash of the
  external id), pads the per-shard update batches to one rectangle, and every
  shard applies only its masked slice.
* ``make_grow_step``    — shard-local capacity growth (each shard pads its own
  slot range; the re-laid-out global state falls out of the out_specs).
* ``ShardedSinnamonIndex`` — the host wrapper that owns routing, per-shard
  slot free lists, and the id → (shard, slot) map, mirroring the
  single-device ``SinnamonIndex`` API (insert/delete/search/grow).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import engine as eng
from repro.distributed import mesh as meshlib
from repro.distributed import topk
from repro.obs import trace as obs_trace
from repro.storage import vecstore


def _corpus_spec(mesh: Mesh):
    corpus = meshlib.corpus_axes(mesh)
    return corpus if len(corpus) > 1 else (corpus[0] if corpus else None)


def state_pspecs(mesh: Mesh, positive_only: bool = False) -> eng.SinnamonState:
    """PartitionSpecs for every SinnamonState leaf (corpus over pod+model).

    ``positive_only`` here means "the state has no ``l`` leaf" — pass
    ``spec.upper_only``, which also covers the §3.3 lite sketch variant.
    """
    c = _corpus_spec(mesh)
    return eng.SinnamonState(
        mappings=P(),                      # replicated
        u=P(None, c),
        l=None if positive_only else P(None, c),
        bits=P(None, c),
        store=vecstore.VecStore(indices=P(c), values=P(c)),
        active=P(c),
        ids=P(c, None),                    # uint32[C, 2] packed int64 ids
        dirty=P(c),
    )


def state_shardings(mesh: Mesh, positive_only: bool = False):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        state_pspecs(mesh, positive_only),
                        is_leaf=lambda x: isinstance(x, P))


def _merge_local_exact(mesh: Mesh, corpus, state: eng.SinnamonState,
                       exact, slots, k: int):
    """Shared tail of every sharded search: package shard-local exact scores
    into (gid lo/hi, locator) payloads and run the hierarchical top-k merge.
    Factored out so the tiered rows-based rerank step merges bit-identically
    with the resident fused step."""
    gids = state.ids[slots]                              # [b, kl, 2]
    shard = meshlib.linear_index(mesh, corpus)
    loc = topk.pack_shard_slot(shard, slots)
    payload = (gids[..., 0], gids[..., 1], loc)
    if corpus:
        vals, (lo, hi, loc) = topk.merge_over_axes(exact, payload, corpus, k)
        return vals, jnp.stack([lo, hi], axis=-1), loc
    vals, pos = jax.lax.top_k(exact, k)
    take = lambda p: jnp.take_along_axis(p, pos, axis=-1)
    return (vals, jnp.stack([take(payload[0]), take(payload[1])],
                            axis=-1), take(loc))


def make_search_step(mesh: Mesh, local_spec: eng.EngineSpec, *,
                     k: int, kprime_local: int,
                     budget: Optional[int] = None,
                     score_fn=None, backend: Optional[str] = None):
    """Build the jittable SPMD search step.

    local_spec.capacity is the *per-shard* slot count.  Returns
    ``step(state, q_idx[B, Lq], q_val[B, Lq])
        -> (scores[B, k], ids[B, k, 2], locators[B, k])``
    with the batch sharded over 'data' and outputs replicated over corpus
    axes.  ``ids`` are packed uint32 (lo, hi) words of the external int64 id
    (decode with engine.unpack_ids64); ``locators`` packs (shard, local slot)
    per hit (see topk.pack_shard_slot) so follow-up work routes straight back
    to the owning shard.

    ``backend`` selects the shard-local candidate backend (reference |
    grouped | pallas — the fused kernel runs per shard; only candidate
    tuples cross shards through the existing hierarchical merge).  The exact
    rerank gathers only the k' candidate CSR rows per shard — no [B, n]
    dense query block on any path.  The shard-local stages carry the
    engine's named scopes (``operands``, ``scan``, ``topk``), and the rerank
    with the cross-shard merge runs in ``rerank``.
    """
    from repro.kernels import ops as _ops

    corpus = meshlib.corpus_axes(mesh)
    qspec = P("data") if "data" in mesh.axis_names else P()
    backend = _ops.resolve_backend(backend) if score_fn is None else None

    def local_search(state: eng.SinnamonState, q_idx, q_val):
        kl = min(kprime_local, local_spec.capacity)
        if score_fn is not None:
            # Custom scorers keep the original BATCHED sharded contract:
            # score_fn(state, spec, q_idx[b, Lq], q_val[b, Lq], budget)
            # -> [b, C].
            scores = score_fn(state, local_spec, q_idx, q_val, budget)
            scores = jnp.where(state.active[None, :], scores, -jnp.inf)
            ub, slots = jax.lax.top_k(scores, kl)            # [b, kl]
        else:
            ub, slots = eng.topk_candidates(state, local_spec, q_idx, q_val,
                                            kl, budget,
                                            backend=backend)  # [b, kl]
        with jax.named_scope("rerank"):
            exact = jax.vmap(
                lambda s, i, v: vecstore.exact_scores_sparse(state.store, s,
                                                             i, v)
            )(slots, q_idx, q_val)                           # [b, kl]
            exact = jnp.where(jnp.isneginf(ub), -jnp.inf, exact)
            return _merge_local_exact(mesh, corpus, state, exact, slots, k)

    sharded = jax.shard_map(
        local_search, mesh=mesh,
        in_specs=(state_pspecs(mesh, local_spec.upper_only), qspec, qspec),
        out_specs=(qspec, qspec, qspec),
        check_vma=False,
    )
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# Collective-free SPMD updates
# ---------------------------------------------------------------------------
# Update batches arrive as [S, B, ...] rectangles whose leading axis is
# sharded over the corpus axes: shard s sees only its own [1, B, ...] slice,
# applies the mask-valid entries against its local slots, and no bytes ever
# cross shards.  The host (ShardedSinnamonIndex) is responsible for routing —
# entry (s, b) must actually belong to shard s.

def make_insert_step(mesh: Mesh, local_spec: eng.EngineSpec):
    """``step(state, slots[S,B], ids[S,B,2], idx[S,B,P], val[S,B,P],
    mask[S,B])`` → state, with every array's leading axis sharded over the
    corpus axes (``ids`` are packed uint32 lo/hi words, engine.pack_ids64)."""
    c = _corpus_spec(mesh)
    sspec = state_pspecs(mesh, local_spec.upper_only)
    uspec = P(c)

    def local_insert(state, slots, eids, idx, val, mask):
        return eng.insert_batch_masked(state, local_spec, slots[0], eids[0],
                                       idx[0], val[0], mask[0])

    sharded = jax.shard_map(
        local_insert, mesh=mesh,
        in_specs=(sspec, uspec, uspec, uspec, uspec, uspec),
        out_specs=sspec, check_vma=False)
    return jax.jit(sharded)


def make_delete_step(mesh: Mesh, local_spec: eng.EngineSpec):
    """``step(state, slots[S,B], mask[S,B])`` → state (shard-local deletes)."""
    c = _corpus_spec(mesh)
    sspec = state_pspecs(mesh, local_spec.upper_only)
    uspec = P(c)

    def local_delete(state, slots, mask):
        return eng.delete_batch_masked(state, local_spec, slots[0], mask[0])

    sharded = jax.shard_map(
        local_delete, mesh=mesh,
        in_specs=(sspec, uspec, uspec),
        out_specs=sspec, check_vma=False)
    return jax.jit(sharded)


def make_grow_step(mesh: Mesh, local_spec: eng.EngineSpec,
                   new_local_capacity: int):
    """``step(state)`` → state with every shard grown to new_local_capacity.

    Each shard pads its own slot range (pure shard-local grow_state); the
    out_specs re-assemble the blocks into the grown global layout, so slot
    numbering *within a shard* is preserved and no collective is emitted.
    """
    new_spec = dataclasses.replace(local_spec, capacity=new_local_capacity)
    sspec_in = state_pspecs(mesh, local_spec.upper_only)

    def local_grow(state):
        return eng.grow_state(state, local_spec, new_spec)

    sharded = jax.shard_map(local_grow, mesh=mesh, in_specs=(sspec_in,),
                            out_specs=sspec_in, check_vma=False)
    return jax.jit(sharded), new_spec


def make_compact_step(mesh: Mesh, local_spec: eng.EngineSpec):
    """``step(state)`` → state with every shard's dirty sketch columns rebuilt
    from its local VecStore slice (shard-local; no collectives)."""
    sspec = state_pspecs(mesh, local_spec.upper_only)

    def local_compact(state):
        return eng.compact_state(state, local_spec)

    sharded = jax.shard_map(local_compact, mesh=mesh, in_specs=(sspec,),
                            out_specs=sspec, check_vma=False)
    return jax.jit(sharded)


def make_drift_step(mesh: Mesh, local_spec: eng.EngineSpec):
    """``step(state)`` → f32[C_global] per-slot sketch overestimate."""
    c = _corpus_spec(mesh)
    sspec = state_pspecs(mesh, local_spec.upper_only)

    def local_drift(state):
        return eng.slot_drift(state, local_spec)

    sharded = jax.shard_map(local_drift, mesh=mesh, in_specs=(sspec,),
                            out_specs=P(c), check_vma=False)
    return jax.jit(sharded)


def shard_state(state: eng.SinnamonState, mesh: Mesh):
    """Place a host-built (global) state onto the mesh."""
    return jax.device_put(state, state_shardings(mesh, state.l is None))


def init_sharded_state(global_spec: eng.EngineSpec, mesh: Mesh, *,
                       store_rows: Optional[int] = None):
    """A fresh global state created directly in its shards: no device ever
    holds more than its own slice (a global state built on one device
    first would not fit it at multi-chip corpus sizes)."""
    init = jax.jit(lambda: eng.init(global_spec, store_rows=store_rows),
                   out_shardings=state_shardings(mesh, global_spec.upper_only))
    return init()


# ---------------------------------------------------------------------------
# Tiered-store SPMD steps (split search + rows-based mutation/maintenance)
# ---------------------------------------------------------------------------
# The tiered sharded index keeps raw CSR rows in per-shard host-backed
# TieredVecStores; ``state.store`` is a zero-row placeholder, so every step
# that used to read it gets a rows-based twin whose row inputs arrive as
# [S, ...] rectangles (leading axis sharded over the corpus axes).  Search
# splits in two: a candidates step (sketch-only), a host-side per-shard
# chunk-cache gather, then a rerank step that reuses _merge_local_exact so
# the merge is bit-identical to make_search_step.

def _block_spec(mesh: Mesh):
    """PartitionSpec for [S, B, ...] blocks: S over corpus, B over data."""
    c = _corpus_spec(mesh)
    bax = meshlib.batch_axes(mesh)
    return P(c, bax[0]) if bax else P(c)


def make_candidates_step(mesh: Mesh, local_spec: eng.EngineSpec, *,
                         kprime_local: int, budget: Optional[int] = None,
                         backend: Optional[str] = None):
    """``step(state, q_idx[B, Lq], q_val[B, Lq])
    -> (ub f32[S, B, kl], slots int32[S, B, kl])`` — the sketch-only front
    half of a tiered sharded search (leading axis sharded over corpus)."""
    from repro.kernels import ops as _ops

    qspec = P("data") if "data" in mesh.axis_names else P()
    bspec = _block_spec(mesh)
    backend = _ops.resolve_backend(backend)

    def local_cand(state, q_idx, q_val):
        kl = min(kprime_local, local_spec.capacity)
        ub, slots = eng.topk_candidates(state, local_spec, q_idx, q_val, kl,
                                        budget, backend=backend)
        return ub[None], slots[None]

    sharded = jax.shard_map(
        local_cand, mesh=mesh,
        in_specs=(state_pspecs(mesh, local_spec.upper_only), qspec, qspec),
        out_specs=(bspec, bspec), check_vma=False)
    return jax.jit(sharded)


def make_rerank_rows_step(mesh: Mesh, local_spec: eng.EngineSpec, *, k: int):
    """``step(state, ub[S, B, kl], slots[S, B, kl], ridx[S, B, kl, P],
    rval[S, B, kl, P], q_idx, q_val) -> (scores[B, k], ids[B, k, 2],
    locators[B, k])`` — the rows-fed exact rerank + hierarchical merge."""
    corpus = meshlib.corpus_axes(mesh)
    qspec = P("data") if "data" in mesh.axis_names else P()
    bspec = _block_spec(mesh)
    sspec = state_pspecs(mesh, local_spec.upper_only)

    def local_rerank(state, ub, slots, ridx, rval, q_idx, q_val):
        with jax.named_scope("rerank"):
            ub, slots = ub[0], slots[0]                  # [b, kl]
            exact = jax.vmap(vecstore.exact_scores_rows)(ridx[0], rval[0],
                                                         q_idx, q_val)
            exact = jnp.where(jnp.isneginf(ub), -jnp.inf, exact)
            return _merge_local_exact(mesh, corpus, state, exact, slots, k)

    sharded = jax.shard_map(
        local_rerank, mesh=mesh,
        in_specs=(sspec, bspec, bspec, bspec, bspec, qspec, qspec),
        out_specs=(qspec, qspec, qspec), check_vma=False)
    return jax.jit(sharded)


def make_delete_rows_step(mesh: Mesh, local_spec: eng.EngineSpec):
    """``step(state, slots[S,B], idx[S,B,P], mask[S,B])`` → state — the
    delete step with the bit-clear coordinate rows supplied by the host."""
    c = _corpus_spec(mesh)
    sspec = state_pspecs(mesh, local_spec.upper_only)
    uspec = P(c)

    def local_delete(state, slots, idx, mask):
        return eng.delete_batch_rows(state, local_spec, slots[0], idx[0],
                                     mask[0])

    sharded = jax.shard_map(
        local_delete, mesh=mesh,
        in_specs=(sspec, uspec, uspec, uspec),
        out_specs=sspec, check_vma=False)
    return jax.jit(sharded)


def make_compact_rows_step(mesh: Mesh, local_spec: eng.EngineSpec):
    """``step(state, slots[S,B], idx[S,B,P], val[S,B,P], mask[S,B])`` →
    state with the masked slots' sketch columns rebuilt from the rows."""
    c = _corpus_spec(mesh)
    sspec = state_pspecs(mesh, local_spec.upper_only)
    uspec = P(c)

    def local_compact(state, slots, idx, val, mask):
        return eng.compact_slots_rows(state, local_spec, slots[0], idx[0],
                                      val[0], mask[0])

    sharded = jax.shard_map(
        local_compact, mesh=mesh,
        in_specs=(sspec, uspec, uspec, uspec, uspec),
        out_specs=sspec, check_vma=False)
    return jax.jit(sharded)


def make_drift_rows_step(mesh: Mesh, local_spec: eng.EngineSpec):
    """``step(state, slots[S,B], idx[S,B,P], val[S,B,P])`` → f32[S, B]."""
    c = _corpus_spec(mesh)
    sspec = state_pspecs(mesh, local_spec.upper_only)
    uspec = P(c)

    def local_drift(state, slots, idx, val):
        return eng.slot_drift_rows(state, local_spec, slots[0], idx[0],
                                   val[0])[None]

    sharded = jax.shard_map(
        local_drift, mesh=mesh,
        in_specs=(sspec, uspec, uspec, uspec),
        out_specs=P(c), check_vma=False)
    return jax.jit(sharded)


def _corpus_shard_devices(mesh: Mesh) -> list:
    """One owning device per corpus shard (first device when replicated)."""
    S = meshlib.n_shards(mesh, meshlib.corpus_axes(mesh))
    sh = NamedSharding(mesh, P(_corpus_spec(mesh)))
    out = [None] * S
    for dev, idx in sh.devices_indices_map((S,)).items():
        start = idx[0].start or 0
        if out[start] is None:
            out[start] = dev
    return out


# ---------------------------------------------------------------------------
# Host wrapper
# ---------------------------------------------------------------------------

class ShardedSinnamonIndex:
    """Streaming host-facing index over a mesh-sharded SinnamonState.

    ``spec.capacity`` is the PER-SHARD slot count; global capacity is
    ``spec.capacity * n_shards``.  Documents are routed to an owning shard by
    a multiplicative hash of the external id, so insert, delete and search
    all agree on placement without any shared table beyond the host's
    id → (shard, slot) dict.  All device work is jitted shard_map programs;
    queries go through the hierarchical top-k merge, so only (k'·shards)
    candidate tuples ever cross shards.
    """

    def __init__(self, spec: eng.EngineSpec, mesh: Mesh, *,
                 update_block: int = 32):
        self.mesh = mesh
        self.spec = spec                       # per-shard spec
        self.default_backend: Optional[str] = None  # repro.api facade sets this
        self.corpus = meshlib.corpus_axes(mesh)
        self.n_shards = meshlib.n_shards(mesh, self.corpus)
        self.update_block = update_block
        global_spec = dataclasses.replace(
            spec, capacity=spec.capacity * self.n_shards)
        self.state = init_sharded_state(global_spec, mesh,
                                        store_rows=self._store_rows)
        self._free = [list(range(spec.capacity - 1, -1, -1))
                      for _ in range(self.n_shards)]
        self._id2slot: dict[int, tuple[int, int]] = {}
        self._steps: dict = {}
        self._obs = eng._WritePathMetrics()

    #: Raw-store rows of a fresh state (None = one per slot); the tiered
    #: subclass keeps a zero-row placeholder on the device.
    _store_rows: Optional[int] = None

    # -- routing ------------------------------------------------------------
    def route(self, ext_id: int) -> int:
        """Owning shard of an external id (Knuth multiplicative hash)."""
        return ((int(ext_id) * 2654435761) & 0xFFFFFFFF) % self.n_shards

    def _step(self, key, build):
        if key not in self._steps:
            self._steps[key] = build()
        return self._steps[key]

    # -- streaming updates --------------------------------------------------
    def insert(self, ext_id: int, idx, val) -> None:
        idx = np.asarray(idx, np.int32)
        val = np.asarray(val, np.float32)
        self.insert_many([ext_id], idx[None], val[None])

    def insert_many(self, ext_ids, idx_batch, val_batch) -> None:
        t0 = time.perf_counter()
        ext_ids = [int(e) for e in ext_ids]
        if len(set(ext_ids)) != len(ext_ids):
            # Sequential overwrite semantics: only the LAST occurrence of a
            # duplicated id survives; earlier ones never touch the index.
            last = {e: pos for pos, e in enumerate(ext_ids)}
            keep = sorted(last.values())
            ext_ids = [ext_ids[p] for p in keep]
            idx_batch = np.asarray(idx_batch)[keep]
            val_batch = np.asarray(val_batch)[keep]
        stale = [e for e in ext_ids if e in self._id2slot]
        if stale:
            self.delete_many(stale)
        idx_batch = self._pad(np.asarray(idx_batch, np.int32), -1)
        val_batch = self._pad(np.asarray(val_batch, np.float32), 0)

        per_shard = [[] for _ in range(self.n_shards)]
        for pos, e in enumerate(ext_ids):
            per_shard[self.route(e)].append(pos)
        while any(len(self._free[s]) < len(per_shard[s])
                  for s in range(self.n_shards)):
            self.grow()

        S, B, Pw = self.n_shards, self.update_block, self.spec.max_nnz
        packed = eng.pack_ids64(np.asarray(ext_ids, np.int64))
        offsets = [0] * S
        while any(offsets[s] < len(per_shard[s]) for s in range(S)):
            slots = np.zeros((S, B), np.int32)
            eids = np.full((S, B, 2), 0xFFFFFFFF, np.uint32)
            idxs = np.full((S, B, Pw), -1, np.int32)
            vals = np.zeros((S, B, Pw), np.float32)
            mask = np.zeros((S, B), bool)
            for s in range(S):
                take = per_shard[s][offsets[s]:offsets[s] + B]
                offsets[s] += len(take)
                for b, pos in enumerate(take):
                    slot = self._free[s].pop()
                    slots[s, b] = slot
                    eids[s, b] = packed[pos]
                    idxs[s, b] = idx_batch[pos]
                    vals[s, b] = val_batch[pos]
                    mask[s, b] = True
                    self._id2slot[ext_ids[pos]] = (s, slot)
            self._apply_insert_block(slots, eids, idxs, vals, mask)
        self._obs.record("insert_many", t0, len(ext_ids))

    def _apply_insert_block(self, slots, eids, idxs, vals, mask) -> None:
        step = self._step("insert", lambda: make_insert_step(self.mesh,
                                                             self.spec))
        self.state = step(self.state, jnp.asarray(slots),
                          jnp.asarray(eids), jnp.asarray(idxs),
                          jnp.asarray(vals), jnp.asarray(mask))

    def delete(self, ext_id: int) -> None:
        self.delete_many([ext_id])

    def delete_many(self, ext_ids) -> None:
        t0 = time.perf_counter()
        # dedup: a repeated id is one deletion, not a KeyError mid-mutation
        ext_ids = list(dict.fromkeys(int(e) for e in ext_ids))
        missing = [e for e in ext_ids if e not in self._id2slot]
        if missing:     # fail atomically, before any bookkeeping mutates
            raise KeyError(f"unknown document ids: {missing[:5]}")
        per_shard = [[] for _ in range(self.n_shards)]
        for e in ext_ids:
            s, slot = self._id2slot.pop(e)
            per_shard[s].append(slot)
        S, B = self.n_shards, self.update_block
        offsets = [0] * S
        while any(offsets[s] < len(per_shard[s]) for s in range(S)):
            slots = np.zeros((S, B), np.int32)
            mask = np.zeros((S, B), bool)
            for s in range(S):
                take = per_shard[s][offsets[s]:offsets[s] + B]
                offsets[s] += len(take)
                slots[s, :len(take)] = take
                mask[s, :len(take)] = True
            self._apply_delete_block(slots, mask)
        for s in range(S):
            self._free[s].extend(reversed(per_shard[s]))
        self._obs.record("delete_many", t0, len(ext_ids))

    def _apply_delete_block(self, slots, mask) -> None:
        step = self._step("delete", lambda: make_delete_step(self.mesh,
                                                             self.spec))
        self.state = step(self.state, jnp.asarray(slots), jnp.asarray(mask))

    # -- retrieval ----------------------------------------------------------
    def search(self, q_idx, q_val, k: int, kprime: Optional[int] = None,
               budget: Optional[int] = None, score_fn=None,
               backend: Optional[str] = None):
        q_idx = np.asarray(q_idx, np.int32)
        q_val = np.asarray(q_val, np.float32)
        ids, scores = self.search_many(q_idx[None], q_val[None], k,
                                       kprime=kprime, budget=budget,
                                       score_fn=score_fn, backend=backend)
        return ids[0], scores[0]

    def search_many(self, q_idx, q_val, k: int,
                    kprime: Optional[int] = None,
                    budget: Optional[int] = None, score_fn=None,
                    backend: Optional[str] = None,
                    return_locators: bool = False):
        """Batched search over [B, Lq] queries (one SPMD dispatch).

        ``kprime`` is the per-shard candidate count k'.  ``backend`` picks
        the shard-local scoring backend (None -> process default).  With
        ``return_locators`` the packed (shard, slot) payload of every hit is
        also returned (decode with topk.unpack_shard_slot).  The dispatch
        records ``launch`` and ``fetch`` stages into the thread's active
        trace context (`repro.obs.trace.stage`); the shard-local split lives
        in the program's named scopes.
        """
        from repro.kernels import ops as _ops

        kprime = kprime if kprime is not None else max(5 * k, k)
        kl = min(kprime, self.spec.capacity)
        k = min(k, kl * self.n_shards)
        if backend is None:
            backend = self.default_backend
        backend = _ops.resolve_backend(backend) if score_fn is None else None
        key = ("search", k, kl, budget, score_fn, backend)
        step = self._step(key, lambda: make_search_step(
            self.mesh, self.spec, k=k, kprime_local=kl, budget=budget,
            score_fn=score_fn, backend=backend))
        with obs_trace.stage("launch"):
            scores, ids, loc = step(self.state, jnp.asarray(q_idx),
                                    jnp.asarray(q_val))
        with obs_trace.stage("fetch"):
            ids = eng.unpack_ids64(np.asarray(ids))
            if return_locators:
                return ids, np.asarray(scores), np.asarray(loc)
            return ids, np.asarray(scores)

    # -- capacity management ------------------------------------------------
    def grow(self, new_local_capacity: Optional[int] = None) -> None:
        """Double (or set) every shard's local capacity, shard-locally."""
        t0 = time.perf_counter()
        old_c = self.spec.capacity
        new_c = new_local_capacity or old_c * 2
        if new_c <= old_c or new_c % 32 != 0:
            raise ValueError("new capacity must be a larger multiple of 32")
        step, new_spec = make_grow_step(self.mesh, self.spec, new_c)
        self.state = step(self.state)
        self.spec = new_spec
        self._steps.clear()        # cached steps close over the old capacity
        for s in range(self.n_shards):
            self._free[s] = (list(range(new_c - 1, old_c - 1, -1))
                             + self._free[s])
        self._obs.record("grow", t0)

    # -- maintenance ---------------------------------------------------------
    def compact(self) -> int:
        """Rebuild every shard's dirty sketch columns (shard-local step).

        Returns the number of columns rebuilt across all shards.
        """
        t0 = time.perf_counter()
        n_dirty = int(np.asarray(jnp.sum(self.state.dirty)))
        if n_dirty:
            step = self._step("compact", lambda: make_compact_step(
                self.mesh, self.spec))
            self.state = step(self.state)
        self._obs.record("compact", t0)
        return n_dirty

    def slot_drift(self) -> np.ndarray:
        """Per-slot sketch overestimate vs. a fresh sketch (f32[C_global])."""
        step = self._step("drift", lambda: make_drift_step(self.mesh,
                                                           self.spec))
        return np.asarray(step(self.state))

    # -- misc ----------------------------------------------------------------
    @property
    def size(self) -> int:
        return len(self._id2slot)

    def __contains__(self, ext_id) -> bool:
        """True iff ``ext_id`` is currently live in the index."""
        return int(ext_id) in self._id2slot

    def doc_ids(self) -> list:
        """Sorted external ids of every live document."""
        return sorted(self._id2slot)

    def _pad(self, arr: np.ndarray, fill) -> np.ndarray:
        w = self.spec.max_nnz
        if arr.shape[1] > w:
            raise ValueError(f"document nnz {arr.shape[1]} > max_nnz {w}")
        if arr.shape[1] == w:
            return arr
        out = np.full((arr.shape[0], w), fill, arr.dtype)
        out[:, :arr.shape[1]] = arr
        return out


class TieredShardedSinnamonIndex(ShardedSinnamonIndex):
    """ShardedSinnamonIndex with per-shard hot/cold tiered raw stores.

    ``state.store`` is a zero-row placeholder; each corpus shard owns a
    :class:`repro.storage.tiered.TieredVecStore` committed to that shard's
    device (``device_budget_bytes`` is PER SHARD).  Search runs as two SPMD
    dispatches — sketch-only candidates, then (after a host sync of the
    ``[S, B, k']`` candidate slots drives per-shard chunk promotion) a
    rows-fed rerank step that reuses the exact same hierarchical merge as
    the resident step, so results are bit-identical to
    :class:`ShardedSinnamonIndex`.  ``score_fn`` (the legacy custom-scorer
    hook) is not supported here.
    """

    def __init__(self, spec: eng.EngineSpec, mesh: Mesh, *,
                 update_block: int = 32, tier_chunk_slots: int = 256,
                 device_budget_bytes: Optional[int] = None,
                 cache_chunks: Optional[int] = None):
        from repro.storage.tiered import TieredVecStore
        super().__init__(spec, mesh, update_block=update_block)
        devices = _corpus_shard_devices(mesh)
        self.tiers = [
            TieredVecStore(spec.capacity, spec.max_nnz,
                           value_dtype=spec.value_dtype,
                           chunk_slots=tier_chunk_slots,
                           device_budget_bytes=device_budget_bytes,
                           cache_chunks=cache_chunks,
                           device=devices[s])
            for s in range(self.n_shards)]

    _store_rows = 0

    # -- streaming updates ---------------------------------------------------
    def _apply_insert_block(self, slots, eids, idxs, vals, mask) -> None:
        pinned = []
        for s in range(self.n_shards):
            m = mask[s]
            if m.any():
                pinned.append((s, self.tiers[s].write_rows(
                    slots[s][m], idxs[s][m], vals[s][m], pin=True)))
        try:
            super()._apply_insert_block(slots, eids, idxs, vals, mask)
        finally:
            for s, chunks in pinned:
                self.tiers[s].unpin(chunks)

    def _apply_delete_block(self, slots, mask) -> None:
        S, B = slots.shape
        idxs = np.full((S, B, self.spec.max_nnz), -1, np.int32)
        for s in range(S):
            m = mask[s]
            if m.any():
                idxs[s, m] = self.tiers[s].read_indices(slots[s][m])
        step = self._step("delete_rows", lambda: make_delete_rows_step(
            self.mesh, self.spec))
        self.state = step(self.state, jnp.asarray(slots), jnp.asarray(idxs),
                          jnp.asarray(mask))
        for s in range(S):
            if mask[s].any():
                self.tiers[s].erase_rows(slots[s][mask[s]])

    # -- retrieval -----------------------------------------------------------
    def search_many(self, q_idx, q_val, k: int,
                    kprime: Optional[int] = None,
                    budget: Optional[int] = None, score_fn=None,
                    backend: Optional[str] = None,
                    return_locators: bool = False):
        """Two SPMD dispatches with a candidate-driven per-shard prefetch in
        between, recorded as ``launch`` / ``fetch`` / ``promote`` /
        ``launch`` / ``fetch`` stages of the thread's active trace
        context."""
        from repro.kernels import ops as _ops

        if score_fn is not None:
            raise NotImplementedError(
                "score_fn is not supported on the tiered sharded index")
        kprime = kprime if kprime is not None else max(5 * k, k)
        kl = min(kprime, self.spec.capacity)
        k = min(k, kl * self.n_shards)
        if backend is None:
            backend = self.default_backend
        backend = _ops.resolve_backend(backend)
        cstep = self._step(("tiered_cand", kl, budget, backend),
                           lambda: make_candidates_step(
                               self.mesh, self.spec, kprime_local=kl,
                               budget=budget, backend=backend))
        rstep = self._step(("tiered_rerank", k, kl),
                           lambda: make_rerank_rows_step(self.mesh, self.spec,
                                                         k=k))
        with obs_trace.stage("launch"):
            qi, qv = jnp.asarray(q_idx), jnp.asarray(q_val)
            ub, slots = cstep(self.state, qi, qv)
        with obs_trace.stage("fetch"):
            slots_np = np.asarray(slots)
        with obs_trace.stage("promote"):
            ridx, rval = self._gather_global(slots_np)
        with obs_trace.stage("launch"):
            scores, ids, loc = rstep(self.state, ub, slots, ridx, rval,
                                     qi, qv)
        with obs_trace.stage("fetch"):
            ids = eng.unpack_ids64(np.asarray(ids))
            if return_locators:
                return ids, np.asarray(scores), np.asarray(loc)
            return ids, np.asarray(scores)

    def _gather_global(self, slots_np: np.ndarray):
        """Per-shard chunk-cache gathers assembled into global [S, B, kl, P]
        arrays sharded over the corpus axes.  Each shard's rows are already
        on its own device, so the global array is assembled without host
        round-trips — unless the batch is data-sharded, where one shard's
        block spans several devices and goes through a host stack +
        device_put."""
        S, B, kl = slots_np.shape
        Pw = self.spec.max_nnz
        pieces = [self.tiers[s].gather_rows(slots_np[s].reshape(-1))
                  for s in range(S)]
        sh = NamedSharding(self.mesh, _block_spec(self.mesh))
        shape = (S, B, kl, Pw)
        if any(self.mesh.shape[a] != 1
               for a in meshlib.batch_axes(self.mesh)):
            return tuple(
                jax.device_put(np.stack([np.asarray(p[j]).reshape(B, kl, Pw)
                                         for p in pieces]), sh)
                for j in (0, 1))
        return tuple(
            jax.make_array_from_single_device_arrays(
                shape, sh, [p[j].reshape(1, B, kl, Pw) for p in pieces])
            for j in (0, 1))

    # -- capacity / maintenance ----------------------------------------------
    def grow(self, new_local_capacity: Optional[int] = None) -> None:
        super().grow(new_local_capacity)
        for t in self.tiers:
            t.grow(self.spec.capacity)

    def _maint_blocks(self):
        """Yield (slots[S,B], idx[S,B,P], val[S,B,P], mask[S,B]) blocks of
        dirty slots with their host-read rows, shard-local numbering."""
        dirty = np.asarray(self.state.dirty)
        cap = self.spec.capacity
        per_shard = [np.flatnonzero(dirty[s * cap:(s + 1) * cap])
                     for s in range(self.n_shards)]
        S, B, Pw = self.n_shards, max(self.update_block, 32), self.spec.max_nnz
        vdt = self.tiers[0].value_dtype
        offsets = [0] * S
        while any(offsets[s] < per_shard[s].size for s in range(S)):
            slots = np.zeros((S, B), np.int32)
            mask = np.zeros((S, B), bool)
            idxs = np.full((S, B, Pw), -1, np.int32)
            vals = np.zeros((S, B, Pw), vdt)
            for s in range(S):
                take = per_shard[s][offsets[s]:offsets[s] + B]
                offsets[s] += take.size
                if take.size:
                    slots[s, :take.size] = take
                    mask[s, :take.size] = True
                    ri, rv = self.tiers[s].read_rows(take)
                    idxs[s, :take.size] = ri
                    vals[s, :take.size] = rv
            yield slots, idxs, vals, mask

    def compact(self) -> int:
        t0 = time.perf_counter()
        total = 0
        step = None
        for slots, idxs, vals, mask in self._maint_blocks():
            if step is None:
                step = self._step("tiered_compact",
                                  lambda: make_compact_rows_step(self.mesh,
                                                                 self.spec))
            self.state = step(self.state, jnp.asarray(slots),
                              jnp.asarray(idxs), jnp.asarray(vals),
                              jnp.asarray(mask))
            total += int(mask.sum())
        self._obs.record("compact", t0)
        return total

    def slot_drift(self) -> np.ndarray:
        out = np.zeros((self.spec.capacity * self.n_shards,), np.float32)
        cap = self.spec.capacity
        step = None
        for slots, idxs, vals, mask in self._maint_blocks():
            if step is None:
                step = self._step("tiered_drift",
                                  lambda: make_drift_rows_step(self.mesh,
                                                               self.spec))
            d = np.asarray(step(self.state, jnp.asarray(slots),
                                jnp.asarray(idxs), jnp.asarray(vals)))
            for s in range(self.n_shards):
                out[s * cap + slots[s][mask[s]]] = d[s][mask[s]]
        return out

    # -- persistence hooks ----------------------------------------------------
    def logical_state(self) -> eng.SinnamonState:
        """Global state with the full raw store spliced back in, so tiered
        snapshots are byte-interchangeable with resident ones."""
        cap, Pw = self.spec.capacity, self.spec.max_nnz
        idx = np.full((cap * self.n_shards, Pw), -1, np.int32)
        val = np.zeros((cap * self.n_shards, Pw), self.tiers[0].value_dtype)
        for s, t in enumerate(self.tiers):
            hi, hv = t.to_arrays()
            idx[s * cap:(s + 1) * cap] = hi
            val[s * cap:(s + 1) * cap] = hv
        return self.state._replace(store=vecstore.VecStore(
            indices=idx, values=val))

    def adopt_logical_state(self, state: eng.SinnamonState) -> None:
        """Restore from a full-store global state: raw rows land in the
        per-shard host backings (tiering heat resets to access-free
        defaults), the device state keeps the zero-row placeholder."""
        cap = self.spec.capacity
        idx = np.asarray(state.store.indices)
        val = np.asarray(state.store.values)
        for s, t in enumerate(self.tiers):
            t.load_rows(idx[s * cap:(s + 1) * cap],
                        val[s * cap:(s + 1) * cap])
        ph = vecstore.empty(0, self.spec.max_nnz,
                            dtype=jnp.dtype(self.spec.value_dtype))
        self.state = shard_state(
            jax.tree.map(jnp.asarray, state._replace(store=ph)), self.mesh)
        self._steps.clear()
