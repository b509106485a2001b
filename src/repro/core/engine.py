"""Sinnamon: the approximate streaming SMIPS engine (paper §4).

Functional JAX core (everything jit-able, shardable) + a thin host wrapper
that owns slot allocation / id mapping / capacity growth.

State layout (one shard):
    mappings : int32[h, n]        random coordinate mappings (π_o)
    u, l     : bf16[m, C]         sketch matrix  X̃ = [U; L]   (l=None → Sinnamon+)
    bits     : uint32[n, C/32]    id-only inverted index (bit-packed)
    store    : VecStore[C, P]     raw vectors (exact rerank source)
    active   : bool[C]            slot occupancy
    ids      : uint32[C, 2]       external int64 document ids per slot, packed
                                  as (low, high) 32-bit words — jax runs with
                                  x64 disabled, so a packed pair is how the
                                  full 64-bit id range survives on device
                                  (pack_ids64 / unpack_ids64 convert at the
                                  host boundary; -1 = empty slot)

Retrieval = Algorithm 6 (budgeted, coordinate-at-a-time upper-bound scoring)
          + Algorithm 7 (top-k' candidates → exact rerank → top-k).
Deletion  = bit-clear + slot recycling (paper §4.3): the sketch column is left
            *dirty* and the next insert MERGES into it (max into u, min into l)
            instead of rebuilding it.  That keeps deletion O(ψ) and preserves
            the Theorem 5.1 upper-bound property — the merged column bounds the
            union of the stale and the new document — but the bound gets
            *looser* under sustained churn.  ``dirty`` tracks which columns
            carry stale residue; :func:`compact_state` rebuilds them exactly
            from the raw vectors in the VecStore (see repro.persist.compact).
"""

from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitindex, sketch
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.storage import vecstore

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """Static engine configuration (hashable; safe as a jit static arg).

    The *accuracy levers* (paper §5–§6, measured by ``repro.eval``):

    * ``m`` — sketch half-size; more rows = tighter Theorem 5.1 bounds.
    * ``sketch_kind`` — ``"full"`` stores both U and L; ``"lite"`` (§3.3)
      stores only the upper-bound sketch, halving sketch memory.  On
      non-negative collections lite loses nothing (L is redundant there —
      same as ``positive_only``); on signed collections negative query
      coordinates contribute 0 instead of ``q[j]·lb``, so the score is no
      longer a strict upper bound and recall degrades gracefully instead.
    * ``dtype`` — sketch cell storage: ``f32 | bf16 | f8`` (directed-rounded
      quantization, decoded in the scoring tile loop; see repro.core.sketch).
    """

    n: int                       # ambient dimensionality
    m: int                       # sketch half-size (2m total rows, paper's "2m")
    capacity: int                # document slots C (multiple of 32)
    max_nnz: int                 # padded CSR width P (max ψ_d)
    h: int = 1
    positive_only: bool = False  # Sinnamon+
    # Approximate inverted index (paper §4.1.2 future work, built here):
    # coordinates hash into `index_buckets` bitmap rows; each list becomes a
    # SUPERSET of the exact one, which preserves the Theorem 5.1 upper-bound
    # (a false positive only ever ADDS a non-negative overestimate) while
    # shrinking the index by n/index_buckets. None = exact bitmap.
    index_buckets: "int | None" = None
    sketch_kind: str = "full"    # full | lite (§3.3 upper-bound-only sketch)
    # NB two distinct storage dtypes: `dtype` is the SKETCH CELL width (the
    # quantization lever; launcher flag --value-dtype, eval name
    # "cell_dtype"), while `value_dtype` is the RAW VecStore width that the
    # exact rerank reads — the launcher flag does NOT set value_dtype.
    dtype: str = "bfloat16"      # sketch cell storage dtype (f32|bf16|f8)
    value_dtype: str = "bfloat16"  # raw-value storage dtype (paper uses bf16)
    seed: int = 0

    def __post_init__(self):
        if self.capacity % 32 != 0:
            raise ValueError("capacity must be a multiple of 32")
        if self.sketch_kind not in ("full", "lite"):
            raise ValueError(f"sketch_kind must be 'full' or 'lite', "
                             f"got {self.sketch_kind!r}")
        # Canonicalize lever aliases ("f8" -> "float8_e4m3fn") up front so
        # jit caches and snapshot recipes key on one spelling.
        object.__setattr__(self, "dtype",
                           sketch.resolve_cell_dtype(self.dtype))

    @property
    def upper_only(self) -> bool:
        """True when no lower sketch is stored (Sinnamon+ or lite)."""
        return self.positive_only or self.sketch_kind == "lite"

    @property
    def sketch_spec(self) -> sketch.SketchSpec:
        return sketch.SketchSpec(self.n, self.m, self.h, self.upper_only,
                                 self.dtype)


def coord_rows(spec: EngineSpec, idx: Array) -> Array:
    """Map coordinate ids to bitmap rows (identity, or hashed buckets)."""
    if spec.index_buckets is None:
        return idx
    u = idx.astype(jnp.uint32) * jnp.uint32(2654435761)
    return jnp.where(idx >= 0,
                     (u % jnp.uint32(spec.index_buckets)).astype(jnp.int32),
                     idx)


class SinnamonState(NamedTuple):
    mappings: Array
    u: Array
    l: Optional[Array]
    bits: Array
    store: vecstore.VecStore
    active: Array
    ids: Array       # uint32[C, 2]: packed int64 external ids (lo, hi words)
    dirty: Array     # bool[C]: sketch column carries stale (deleted-doc) residue


# -- 64-bit external ids on a 32-bit device -----------------------------------
# jax_enable_x64 is off (flipping it would re-type every float in the repo),
# so external ids — int64 on the host API — live on device as (lo, hi)
# uint32 pairs.  Packing is lossless over the full int64 range; -1 (empty
# slot) packs to (0xFFFFFFFF, 0xFFFFFFFF).

def pack_ids64(ids) -> np.ndarray:
    """int64[...] -> uint32[..., 2] (lo, hi) words."""
    u = np.asarray(ids, np.int64).view(np.uint64)
    return np.stack([u & np.uint64(0xFFFFFFFF), u >> np.uint64(32)],
                    axis=-1).astype(np.uint32)


def unpack_ids64(packed) -> np.ndarray:
    """uint32[..., 2] (lo, hi) words -> int64[...]."""
    p = np.asarray(packed, np.uint32).astype(np.uint64)
    return (p[..., 0] | (p[..., 1] << np.uint64(32))).view(np.int64)


_EMPTY_ID = np.uint32(0xFFFFFFFF)    # both words of a packed -1


# ---------------------------------------------------------------------------
# Functional core
# ---------------------------------------------------------------------------

def init(spec: EngineSpec, *, store_rows: Optional[int] = None) -> SinnamonState:
    """Fresh state.  ``store_rows=0`` allocates a zero-row VecStore
    placeholder — the tiered index keeps raw rows in a host-side
    TieredVecStore and every batched mutation's ``mode="drop"`` scatter is an
    exact no-op on the empty placeholder, so the functional core needs no
    tiering branches."""
    mappings = jnp.asarray(sketch.make_mappings(spec.seed, spec.n, spec.m, spec.h))
    u = jnp.zeros((spec.m, spec.capacity), dtype=spec.sketch_spec.jdtype)
    l = None if spec.upper_only else jnp.zeros_like(u)
    return SinnamonState(
        mappings=mappings,
        u=u,
        l=l,
        bits=bitindex.empty(spec.index_buckets or spec.n, spec.capacity),
        store=vecstore.empty(spec.capacity if store_rows is None
                             else store_rows, spec.max_nnz,
                             dtype=jnp.dtype(spec.value_dtype)),
        active=jnp.zeros((spec.capacity,), jnp.bool_),
        ids=jnp.full((spec.capacity, 2), _EMPTY_ID, jnp.uint32),
        dirty=jnp.zeros((spec.capacity,), jnp.bool_),
    )


def insert(state: SinnamonState, spec: EngineSpec, slot, ext_id,
           idx: Array, val: Array) -> SinnamonState:
    """Algorithm 5: index one document at ``slot``.

    ``ext_id`` is the packed uint32[2] form of the external int64 id
    (see :func:`pack_ids64`).

    A clean slot gets the document's exact sketch column.  A *dirty* slot
    (recycled after a §4.3 deletion) is MERGED into — max for u, min for l —
    so the column still upper/lower-bounds every value it ever saw.  The bound
    stays valid but loose; the slot stays dirty until compaction rebuilds it.
    """
    u_col, l_col = sketch.encode(state.mappings, spec.m, idx, val,
                                 dtype=spec.dtype,
                                 positive_only=spec.upper_only)
    was_dirty = state.dirty[slot]
    u_col = u_col.astype(state.u.dtype)
    u_col = jnp.where(was_dirty, jnp.maximum(state.u[:, slot], u_col), u_col)
    u = state.u.at[:, slot].set(u_col)
    if state.l is None:
        l = None
    else:
        l_col = l_col.astype(state.l.dtype)
        l_col = jnp.where(was_dirty, jnp.minimum(state.l[:, slot], l_col),
                          l_col)
        l = state.l.at[:, slot].set(l_col)
    bits = bitindex.set_doc(state.bits, coord_rows(spec, idx), slot,
                            on=True)
    store = vecstore.write(state.store, slot, idx, val)
    return state._replace(
        u=u, l=l, bits=bits, store=store,
        active=state.active.at[slot].set(True),
        ids=state.ids.at[slot].set(ext_id),
    )


# -- vectorized batch mutations ----------------------------------------------
# The host allocator guarantees every batch touches UNIQUE slots (free-list
# pops for inserts; deduped id->slot lookups for deletes), which makes whole
# batches expressible as single-dispatch scatters instead of a lax.scan of
# per-document whole-state updates:
#
# * sketch columns: one encode_batch + one dirty-aware merged column scatter;
# * membership bits: one scatter-ADD (insert) / scatter-SUBTRACT (delete) of
#   per-coordinate word masks.  Distinct slots in one batch touch distinct
#   bits even when they share a word, and within one document duplicate
#   bitmap rows (index_buckets collisions) are routed out-of-bounds after the
#   first occurrence, so every (row, word, bit) is touched at most once and
#   add == bitwise-OR / subtract == bit-clear.  This leans on the engine
#   invariant that a free slot's bit column is all-zero (delete clears
#   exactly the rows its stored document set) — the same invariant the
#   sequential path needs for its OR to mean "insert".
# * VecStore / active / ids: one batched row scatter each.
#
# Masked-off entries are routed out-of-bounds and dropped, so the masked
# variants stay exact no-ops per entry (the shard_map-body contract).  The
# lax.scan forms survive as *_scan reference oracles (tests assert tree
# equality between the two on randomized streams).


def _dedup_first(rows: Array) -> Array:
    """bool[..., P]: True at the FIRST occurrence of each row within a doc."""
    eq = rows[..., :, None] == rows[..., None, :]          # [..., P, P]
    earlier = jnp.tril(jnp.ones((rows.shape[-1],) * 2, jnp.bool_), -1)
    return ~jnp.any(eq & earlier, axis=-1)


def _bit_scatter_operands(state, spec, slots, idx, mask):
    """(rows, words, bitmasks) for one batched membership-bit scatter.

    Invalid coordinates, duplicate in-document rows and masked-off documents
    are routed to the out-of-bounds row (dropped by the scatter).
    """
    rows = coord_rows(spec, idx)                           # [B, P]
    keep = (idx >= 0) & mask[:, None] & _dedup_first(rows)
    oob = jnp.int32(state.bits.shape[0])
    safe_rows = jnp.where(keep, rows, oob)
    words = jnp.broadcast_to((slots // bitindex.WORD)[:, None], rows.shape)
    bitm = (jnp.uint32(1) << (slots % bitindex.WORD).astype(jnp.uint32))
    bitm = jnp.broadcast_to(bitm[:, None], rows.shape)
    return safe_rows, words, bitm


def insert_batch_masked(state: SinnamonState, spec: EngineSpec, slots: Array,
                        ext_ids: Array, idx: Array, val: Array,
                        mask: Array) -> SinnamonState:
    """Vectorized batch insert; ``mask=False`` entries are exact no-ops.

    One device dispatch for the whole batch (see the module comment above for
    the uniqueness/invariant preconditions).  ``ext_ids``: packed uint32[B, 2]
    external ids.  This is also the shard_map-body form: each shard receives
    a host-routed, padded slice of the update batch and applies only its own
    entries, so a sharded insert needs no collectives
    (see repro.serving.sharded).
    """
    u_cols, l_cols = sketch.encode_batch(state.mappings, spec.m, idx, val,
                                         dtype=spec.dtype,
                                         positive_only=spec.upper_only)
    cap = state.active.shape[0]
    safe_slots = jnp.where(mask, slots, cap)               # OOB -> dropped

    was_dirty = state.dirty[slots]                         # [B]
    u_new = u_cols.T.astype(state.u.dtype)                 # [m, B]
    u_new = jnp.where(was_dirty[None, :],
                      jnp.maximum(state.u[:, slots], u_new), u_new)
    u = state.u.at[:, safe_slots].set(u_new, mode="drop")
    if state.l is None:
        l = None
    else:
        l_new = l_cols.T.astype(state.l.dtype)
        l_new = jnp.where(was_dirty[None, :],
                          jnp.minimum(state.l[:, slots], l_new), l_new)
        l = state.l.at[:, safe_slots].set(l_new, mode="drop")

    rows, words, bitm = _bit_scatter_operands(state, spec, slots, idx, mask)
    bits = state.bits.at[rows, words].add(bitm, mode="drop")

    store = vecstore.VecStore(
        indices=state.store.indices.at[safe_slots].set(idx, mode="drop"),
        values=state.store.values.at[safe_slots].set(
            val.astype(state.store.values.dtype), mode="drop"))
    return state._replace(
        u=u, l=l, bits=bits, store=store,
        active=state.active.at[safe_slots].set(True, mode="drop"),
        ids=state.ids.at[safe_slots].set(ext_ids, mode="drop"),
    )


def insert_batch(state: SinnamonState, spec: EngineSpec, slots: Array,
                 ext_ids: Array, idx: Array, val: Array) -> SinnamonState:
    """Vectorized batch insert over unique slots (one jit dispatch)."""
    return insert_batch_masked(state, spec, slots, ext_ids, idx, val,
                               jnp.ones(slots.shape, jnp.bool_))


def delete_batch_masked(state: SinnamonState, spec: EngineSpec, slots: Array,
                        mask: Array) -> SinnamonState:
    """Vectorized masked batch delete; the shard_map-body twin of delete.

    Reads the deleted documents' coordinate rows from the resident VecStore;
    the tiered index supplies them from its host backing instead via
    :func:`delete_batch_rows`.
    """
    return delete_batch_rows(state, spec, slots, state.store.indices[slots],
                             mask)


def delete_batch_rows(state: SinnamonState, spec: EngineSpec, slots: Array,
                      idx: Array, mask: Array) -> SinnamonState:
    """Masked batch delete with the coordinate rows ``idx`` [B, P] passed in.

    Bit-clearing is a scatter-SUBTRACT of the same per-coordinate word masks
    the insert scatter added: each targeted bit is guaranteed set (the slot's
    stored document set exactly these rows), so subtraction borrows nothing.
    """
    rows, words, bitm = _bit_scatter_operands(state, spec, slots, idx, mask)
    bits = state.bits.at[rows, words].add(jnp.uint32(0) - bitm, mode="drop")

    cap = state.active.shape[0]
    safe_slots = jnp.where(mask, slots, cap)
    store = vecstore.VecStore(
        indices=state.store.indices.at[safe_slots].set(-1, mode="drop"),
        values=state.store.values.at[safe_slots].set(0, mode="drop"))
    return state._replace(
        bits=bits, store=store,
        active=state.active.at[safe_slots].set(False, mode="drop"),
        ids=state.ids.at[safe_slots].set(jnp.uint32(0xFFFFFFFF), mode="drop"),
        dirty=state.dirty.at[safe_slots].set(True, mode="drop"),
    )


# -- sequential reference oracles (tests only) --------------------------------

def insert_batch_scan(state: SinnamonState, spec: EngineSpec, slots: Array,
                      ext_ids: Array, idx: Array, val: Array) -> SinnamonState:
    """Sequential-semantics batch insert (scan); the vectorized oracle."""

    def body(st, args):
        slot, eid, i, v = args
        return insert(st, spec, slot, eid, i, v), None

    state, _ = jax.lax.scan(body, state, (slots, ext_ids, idx, val))
    return state


def insert_batch_masked_scan(state: SinnamonState, spec: EngineSpec,
                             slots: Array, ext_ids: Array, idx: Array,
                             val: Array, mask: Array) -> SinnamonState:
    """Scan twin of :func:`insert_batch_masked` (reference oracle)."""

    def body(st, args):
        slot, eid, i, v, ok = args
        st = jax.lax.cond(ok, lambda s: insert(s, spec, slot, eid, i, v),
                          lambda s: s, st)
        return st, None

    state, _ = jax.lax.scan(body, state, (slots, ext_ids, idx, val, mask))
    return state


def delete_batch_masked_scan(state: SinnamonState, spec: EngineSpec,
                             slots: Array, mask: Array) -> SinnamonState:
    """Scan twin of :func:`delete_batch_masked` (reference oracle)."""

    def body(st, args):
        slot, ok = args
        st = jax.lax.cond(ok, lambda s: delete(s, spec, slot),
                          lambda s: s, st)
        return st, None

    state, _ = jax.lax.scan(body, state, (slots, mask))
    return state


def delete(state: SinnamonState, spec: EngineSpec, slot) -> SinnamonState:
    """Paper §4.3: clear inverted-index bits; leave the sketch column stale.

    The stale column is marked dirty so the next insert merges rather than
    overwrites, and compaction knows which columns to rebuild.
    """
    idx = state.store.indices[slot]
    bits = bitindex.set_doc(state.bits, coord_rows(spec, idx), slot,
                            on=False)
    store = vecstore.erase(state.store, slot)
    return state._replace(
        bits=bits, store=store,
        active=state.active.at[slot].set(False),
        ids=state.ids.at[slot].set(jnp.uint32(0xFFFFFFFF)),
        dirty=state.dirty.at[slot].set(True),
    )


def grow_state(state: SinnamonState, spec: EngineSpec,
               new_spec: EngineSpec) -> SinnamonState:
    """Pad every per-slot axis from spec.capacity to new_spec.capacity.

    Pure function of the arrays (slot numbering is preserved), so it works
    both as the host-side reallocation of :class:`SinnamonIndex` and as a
    shard-local shard_map body where each shard grows its own slot range.
    """
    c = spec.capacity
    placeholder = state.store.indices.shape[0] == 0    # tiered: stays empty
    st = init(new_spec, store_rows=0 if placeholder else None)
    return SinnamonState(
        mappings=state.mappings,
        u=st.u.at[:, :c].set(state.u),
        l=None if state.l is None else st.l.at[:, :c].set(state.l),
        bits=st.bits.at[:, : c // 32].set(state.bits),
        store=st.store if placeholder else vecstore.VecStore(
            indices=st.store.indices.at[:c].set(state.store.indices),
            values=st.store.values.at[:c].set(state.store.values)),
        active=st.active.at[:c].set(state.active),
        ids=st.ids.at[:c].set(state.ids),
        dirty=st.dirty.at[:c].set(state.dirty),
    )


# ---------------------------------------------------------------------------
# Sketch compaction (repro.persist.compact drives these; pure so they work as
# shard_map bodies too)
# ---------------------------------------------------------------------------

def fresh_sketch(state: SinnamonState, spec: EngineSpec
                 ) -> Tuple[Array, Optional[Array]]:
    """Exact sketch matrix re-encoded from the raw vectors in the VecStore.

    Returns (u[m, C], l[m, C]).  Erased slots encode to all-zero columns.
    This is the Theorem 5.1-tight reference: no recycled-slot residue.
    """
    u, l = sketch.encode_batch(
        state.mappings, spec.m, state.store.indices,
        state.store.values.astype(jnp.float32),
        dtype=spec.dtype, positive_only=spec.upper_only)
    return u.T, None if l is None else l.T


def compact_state(state: SinnamonState, spec: EngineSpec) -> SinnamonState:
    """Rebuild every dirty sketch column exactly from the VecStore.

    Dirty+active columns become the document's fresh sketch; dirty+inactive
    (deleted, not yet recycled) columns become zero.  Clean columns are left
    untouched bit-for-bit.  Pure function of the arrays — usable directly or
    as a shard-local shard_map body (see repro.serving.sharded).
    """
    u_f, l_f = fresh_sketch(state, spec)
    d = state.dirty[None, :]
    u = jnp.where(d, u_f.astype(state.u.dtype), state.u)
    l = None if state.l is None else jnp.where(
        d, l_f.astype(state.l.dtype), state.l)
    return state._replace(u=u, l=l, dirty=jnp.zeros_like(state.dirty))


def slot_drift(state: SinnamonState, spec: EngineSpec) -> Array:
    """Per-slot sketch overestimate vs. a fresh sketch.  f32[C].

    For each active slot: the max over sketch cells of how far the stored
    upper bound sits ABOVE the tight one (plus, symmetrically, how far the
    stored lower bound sits below).  0 for clean slots (up to storage-dtype
    effects when value_dtype != float32) and for inactive slots.
    """
    u_f, l_f = fresh_sketch(state, spec)
    over = jnp.max(jnp.clip(state.u.astype(jnp.float32)
                            - u_f.astype(jnp.float32), 0.0, None), axis=0)
    if state.l is not None:
        over_l = jnp.max(jnp.clip(l_f.astype(jnp.float32)
                                  - state.l.astype(jnp.float32), 0.0, None),
                         axis=0)
        over = jnp.maximum(over, over_l)
    return jnp.where(state.active, over, 0.0)


def compact_slots_rows(state: SinnamonState, spec: EngineSpec, slots: Array,
                       idx_rows: Array, val_rows: Array,
                       mask: Array) -> SinnamonState:
    """Rebuild the sketch columns of ``slots`` from their raw rows.

    The rows-based twin of :func:`compact_state` for stores whose raw rows
    live off-device (TieredVecStore): the host reads the dirty slots' rows
    from the backing store and passes them in; masked-off entries are exact
    no-ops.  Encoding matches :func:`fresh_sketch` cell-for-cell (erased
    rows encode to zero columns), so compacting the dirty set this way is
    bit-identical to :func:`compact_state`.
    """
    u_cols, l_cols = sketch.encode_batch(
        state.mappings, spec.m, idx_rows, val_rows.astype(jnp.float32),
        dtype=spec.dtype, positive_only=spec.upper_only)
    cap = state.active.shape[0]
    safe = jnp.where(mask, slots, cap)                     # OOB -> dropped
    u = state.u.at[:, safe].set(u_cols.T.astype(state.u.dtype), mode="drop")
    l = None if state.l is None else state.l.at[:, safe].set(
        l_cols.T.astype(state.l.dtype), mode="drop")
    dirty = state.dirty.at[safe].set(False, mode="drop")
    return state._replace(u=u, l=l, dirty=dirty)


def slot_drift_rows(state: SinnamonState, spec: EngineSpec, slots: Array,
                    idx_rows: Array, val_rows: Array) -> Array:
    """Sketch overestimate of ``slots`` given their raw rows.  f32[len(slots)].

    Same per-slot math as :func:`slot_drift`, fed from host-read rows instead
    of the resident VecStore (the tiered index only evaluates dirty slots —
    clean slots report 0 by definition there).
    """
    u_cols, l_cols = sketch.encode_batch(
        state.mappings, spec.m, idx_rows, val_rows.astype(jnp.float32),
        dtype=spec.dtype, positive_only=spec.upper_only)
    over = jnp.max(jnp.clip(state.u[:, slots].astype(jnp.float32)
                            - u_cols.T.astype(jnp.float32), 0.0, None), axis=0)
    if state.l is not None:
        over_l = jnp.max(jnp.clip(l_cols.T.astype(jnp.float32)
                                  - state.l[:, slots].astype(jnp.float32),
                                  0.0, None), axis=0)
        over = jnp.maximum(over, over_l)
    return jnp.where(state.active[slots], over, 0.0)


def _sorted_query(q_idx: Array, q_val: Array) -> Tuple[Array, Array]:
    """Order query coordinates by |q[j]| descending, padding (idx<0) last."""
    key = jnp.where(q_idx >= 0, jnp.abs(q_val.astype(jnp.float32)), -1.0)
    order = jnp.argsort(-key)
    return q_idx[order], q_val[order]


def score(state: SinnamonState, spec: EngineSpec, q_idx: Array, q_val: Array,
          budget: Optional[int] = None) -> Array:
    """Algorithm 6: upper-bound scores for every slot.  f32[C].

    ``budget`` is the anytime lever: only the ``budget`` largest-|q[j]|
    coordinates are scored (deterministic adaptation of the paper's wall-clock
    budget T; see DESIGN.md §6).  None = all coordinates (T = ∞).
    """
    with jax.named_scope("operands"):
        q_idx, q_val = _sorted_query(q_idx, q_val)
        rows = coord_rows(spec, q_idx)      # bitmap rows in SORTED order
    steps = q_idx.shape[0] if budget is None else min(budget, q_idx.shape[0])

    def body(t, scores):
        j = q_idx[t]
        v = q_val[t].astype(jnp.float32)
        safe_j = jnp.maximum(j, 0)
        ub, lb = sketch.decode_coord(state.mappings, state.u, state.l, safe_j)
        contrib = jnp.where(v > 0, v * ub, v * lb)
        memb = bitindex.row_mask(state.bits, jnp.maximum(rows[t], 0))
        return scores + jnp.where(memb & (j >= 0), contrib, 0.0)

    with jax.named_scope("scan"):
        scores = jnp.zeros((spec.capacity,), jnp.float32)
        return jax.lax.fori_loop(0, steps, body, scores)


def score_grouped(state: SinnamonState, spec: EngineSpec, q_idx: Array,
                  q_val: Array, budget: Optional[int] = None) -> Array:
    """Beyond-paper scoring schedule (EXPERIMENTS.md §Perf): process all
    budgeted coordinates in ONE fused pass instead of a coordinate-at-a-time
    loop.  Same math as :func:`score`; the sketch/bitmap rows are gathered as
    a single [L, ·] batch and reduced with one einsum-style sum, which lets
    XLA keep the candidate tile resident instead of re-walking scores[C] per
    coordinate (psi_q x fewer accumulator read-modify-writes).
    """
    with jax.named_scope("operands"):
        q_idx, q_val = _sorted_query(q_idx, q_val)
        L = q_idx.shape[0] if budget is None else min(budget, q_idx.shape[0])
        j = q_idx[:L]
        v = q_val[:L].astype(jnp.float32)
        safe = jnp.where(j >= 0, j, 0)
        rows = state.mappings[:, safe]                       # [h, L]
        bit_rows = jnp.maximum(coord_rows(spec, j), 0)
    with jax.named_scope("scan"):
        ub = jnp.min(state.u[rows].astype(jnp.float32), axis=0)  # [L, C]
        if state.l is None:
            lb = jnp.zeros_like(ub)
        else:
            lb = jnp.max(state.l[rows].astype(jnp.float32), axis=0)
        contrib = jnp.where(v[:, None] > 0, v[:, None] * ub,
                            v[:, None] * lb)
        memb = bitindex.unpack_row(state.bits[bit_rows])     # [L, C]
        contrib = jnp.where(memb & (j >= 0)[:, None], contrib, 0.0)
        return jnp.sum(contrib, axis=0)


def score_batch(state, spec, q_idx, q_val, budget=None, grouped=False
                ) -> Array:
    """[B, C] upper-bound scores for a batch of queries."""
    fn = score_grouped if grouped else score
    return jax.vmap(lambda i, v: fn(state, spec, i, v, budget))(q_idx, q_val)


def topk_candidates(state: SinnamonState, spec: EngineSpec, q_idx: Array,
                    q_val: Array, kprime: int, budget: Optional[int] = None,
                    filter_mask: Optional[Array] = None, score_fn=None,
                    backend: Optional[str] = None):
    """Batched candidate generation: the Algorithm 6 front half of search.

    q_idx/q_val: [B, Lq].  Returns (upper_bounds f32[B, kprime],
    slots int32[B, kprime]) ordered by (upper bound desc, slot asc) — every
    backend produces this order bit-identically, which is what lets the
    fused Pallas path be the drop-in production default.

    backend: ``reference | grouped | pallas`` (None -> the process default,
    see repro.kernels.ops.resolve_backend).  ``score_fn`` overrides the
    backend with a custom per-query dense scorer (legacy hook).

    Every backend runs its query sort and row gathers in the named scope
    ``operands``, its scoring pass in ``scan`` and the gate plus the top-k
    in ``topk``: the names a profiled program's device ops carry.
    """
    from repro.kernels import ops as _ops   # deferred: kernels import engine

    ok = state.active if filter_mask is None else (state.active & filter_mask)
    backend = _ops.resolve_backend(backend)
    if score_fn is None and backend == "pallas":
        return _ops.sinnamon_topk_batch(state, spec, q_idx, q_val, kprime,
                                        budget=budget, ok=ok)
    fn = score_fn if score_fn is not None else (
        score_grouped if backend == "grouped" else score)
    s = jax.vmap(lambda i, v: fn(state, spec, i, v, budget))(q_idx, q_val)
    with jax.named_scope("topk"):
        s = jnp.where(ok[None, :], s, -jnp.inf)
        vals, slots = jax.lax.top_k(s, kprime)
        return vals, slots.astype(jnp.int32)


def search(state: SinnamonState, spec: EngineSpec, q_idx: Array, q_val: Array,
           k: int, kprime: int, budget: Optional[int] = None,
           filter_mask: Optional[Array] = None,
           score_fn=None, backend: Optional[str] = None):
    """Algorithms 6+7: candidate generation → sparse exact rerank → top-k.

    filter_mask: optional bool[C] for constrained search (paper §4.2.4, Eq. 3).
    score_fn: override the scoring backend with a custom dense scorer.
    backend: ``reference | grouped | pallas`` candidate backend (see
    :func:`topk_candidates`).  The rerank gathers only the k' candidate CSR
    rows (no dense R^n query), identical across backends.
    Returns (packed ids uint32[k, 2], exact_scores f32[k], slots int32[k]).
    """
    cand_scores, cand_slots = topk_candidates(
        state, spec, q_idx[None], q_val[None], kprime, budget, filter_mask,
        score_fn=score_fn, backend=backend)
    cand_scores, cand_slots = cand_scores[0], cand_slots[0]
    exact = vecstore.exact_scores_sparse(state.store, cand_slots, q_idx, q_val)
    exact = jnp.where(jnp.isneginf(cand_scores), -jnp.inf, exact)
    top_scores, pos = jax.lax.top_k(exact, k)
    slots = cand_slots[pos]
    return state.ids[slots], top_scores, slots


def rerank_topk(state, cand_scores, cand_slots, q_idx, q_val, k):
    """Algorithm 7 back half: sparse exact rerank of [B, k'] candidates.

    Gathers only the candidate CSR rows (no dense R^n query), masks slots
    whose upper bound was gated to -inf, and returns the exact top-k:
    (packed ids uint32[B, k, 2], scores f32[B, k], slots int32[B, k]),
    all in the named scope ``rerank``.
    """
    with jax.named_scope("rerank"):
        exact = jax.vmap(
            lambda s_, i, v: vecstore.exact_scores_sparse(state.store, s_,
                                                          i, v)
        )(cand_slots, q_idx, q_val)
        exact = jnp.where(jnp.isneginf(cand_scores), -jnp.inf, exact)
        top_scores, pos = jax.lax.top_k(exact, k)
        slots = jnp.take_along_axis(cand_slots, pos, axis=-1)
        return state.ids[slots], top_scores, slots


def rerank_topk_rows(state, cand_scores, cand_slots, rows_idx, rows_val,
                     q_idx, q_val, k):
    """:func:`rerank_topk` with the candidate CSR rows passed in directly.

    The tiered path: ``TieredVecStore.gather_rows`` supplies
    ``rows_idx``/``rows_val`` as flat ``[B*k', P]`` (or ``[B, k', P]``)
    arrays and the exact scores go through the same
    ``vecstore.exact_scores_rows`` primitive the resident rerank uses, so
    both paths produce bit-identical (ids, scores, slots).
    """
    B, kp = cand_slots.shape
    Pw = rows_idx.shape[-1]
    with jax.named_scope("rerank"):
        ri = rows_idx.reshape(B, kp, Pw)
        rv = rows_val.reshape(B, kp, Pw)
        exact = jax.vmap(vecstore.exact_scores_rows)(ri, rv, q_idx, q_val)
        exact = jnp.where(jnp.isneginf(cand_scores), -jnp.inf, exact)
        top_scores, pos = jax.lax.top_k(exact, k)
        slots = jnp.take_along_axis(cand_slots, pos, axis=-1)
        return state.ids[slots], top_scores, slots


def rerank_single_rows(state, cand_scores, cand_slots, rows_idx, rows_val,
                       q_idx, q_val, k):
    """:func:`search`'s single-query rerank tail with the rows passed in.

    The unbatched rerank sums in a different (shape-dependent) order than
    the vmapped one, so the tiered single-query path must mirror
    :func:`search` exactly — not go through the batched rerank — to stay
    bit-identical to the resident ``SinnamonIndex.search``.
    """
    exact = vecstore.exact_scores_rows(rows_idx, rows_val, q_idx, q_val)
    exact = jnp.where(jnp.isneginf(cand_scores), -jnp.inf, exact)
    top_scores, pos = jax.lax.top_k(exact, k)
    slots = cand_slots[pos]
    return state.ids[slots], top_scores, slots


def search_batch(state, spec, q_idx, q_val, k, kprime, budget=None,
                 filter_mask=None, score_fn=None,
                 backend: Optional[str] = None):
    """Batched search [B, Lq] -> ([B, k] ids/scores/slots), ONE dispatch.

    Candidate generation is batch-native (the fused kernel's grid covers the
    whole batch); only the k'-row sparse rerank is vmapped.
    """
    cand_scores, cand_slots = topk_candidates(
        state, spec, q_idx, q_val, kprime, budget, filter_mask,
        score_fn=score_fn, backend=backend)
    return rerank_topk(state, cand_scores, cand_slots, q_idx, q_val, k)


def search_batch_sketch(state, spec, q_idx, q_val, k, budget=None,
                        backend: Optional[str] = None):
    """Sketch-only batched search: Algorithm 6 with NO exact rerank.

    Answers straight from the top-k sketch upper bounds — the cheapest
    answer the index can produce (the paper's lite regime taken to its
    limit: scores are Theorem 5.1 upper bounds, not inner products, and
    ranking quality is whatever the sketch alone provides).  This is the
    serving brownout lever: under overload the front door trades rerank
    cost for availability and stamps results ``degraded``.
    Returns (packed ids uint32[B, k, 2], upper_bounds f32[B, k],
    slots int32[B, k]).
    """
    ub, slots = topk_candidates(state, spec, q_idx, q_val, k, budget,
                                None, backend=backend)
    return state.ids[slots], ub, slots


# ---------------------------------------------------------------------------
# Host wrapper: slot allocation, id mapping, growth
# ---------------------------------------------------------------------------

class _WritePathMetrics:
    """Write-path metric handles, lazily bound and revalidated against the
    current process-global registry (so `obs.metrics.set_registry` in tests
    takes effect on indexes created earlier).  Shared by `SinnamonIndex`
    and `ShardedSinnamonIndex`."""

    __slots__ = ("_registry", "_ops", "_docs", "_batch")
    _OPS = ("insert", "insert_many", "delete", "delete_many", "grow", "compact")

    def __init__(self):
        self._registry = None

    def _bind(self):
        reg = obs_metrics.get_registry()
        if reg is not self._registry:
            self._ops = {
                op: (reg.counter("repro_engine_ops_total",
                                 "Engine mutations applied.", labels={"op": op}),
                     reg.histogram(
                         "repro_engine_update_ms",
                         "Host wall time of one mutation, scatter dispatch "
                         "included (async device work not synced).",
                         labels={"op": op}))
                for op in self._OPS}
            self._docs = {
                d: reg.counter("repro_engine_docs_total",
                               "Documents written/removed.", labels={"op": d})
                for d in ("insert", "delete")}
            self._batch = reg.histogram(
                "repro_engine_update_batch_docs",
                "Documents per mutation call.",
                buckets=obs_metrics.DEFAULT_COUNT_BUCKETS)
            self._registry = reg

    def record(self, op: str, t0_s: float, ndocs: int = 0) -> None:
        self._bind()
        count, hist = self._ops[op]
        count.inc()
        hist.observe((time.perf_counter() - t0_s) * 1e3)
        if ndocs:
            self._batch.observe(ndocs)
            self._docs["delete" if op.startswith("delete") else "insert"].inc(ndocs)


class SinnamonIndex:
    """Streaming host-facing index (paper §4's full system, single device).

    Owns the host-side bookkeeping — slot free list, external-id ↔ slot map,
    capacity growth — while every heavy operation stays a jitted pure
    function of :class:`SinnamonState`.  Mutations: :meth:`insert` /
    :meth:`insert_many` (Algorithm 5 sketching + bit-index update),
    :meth:`delete` (§4.3 bit-clear with slot recycling).  Retrieval:
    :meth:`search` / :meth:`search_many` (Algorithm 6 budgeted upper-bound
    candidates + Algorithm 7 exact rerank, through the pluggable scoring
    backend).  Maintenance: :meth:`compact` / :meth:`slot_drift` for churn
    residue, :meth:`memory_bytes` for the §6.1.2 accounting that the
    ``repro.eval`` harness and auto-tuner report.
    """

    def __init__(self, spec: EngineSpec):
        self.spec = spec
        self.default_backend: Optional[str] = None  # repro.api facade sets this
        self.state = self._init_state()
        self._free = list(range(spec.capacity - 1, -1, -1))  # pop() -> slot 0 first
        self._id2slot: dict[int, int] = {}
        self._insert = jax.jit(insert, static_argnums=(1,))
        self._insert_batch = jax.jit(insert_batch, static_argnums=(1,))
        self._delete = jax.jit(delete, static_argnums=(1,))
        self._search = jax.jit(
            search, static_argnums=(1, 4, 5, 6),
            static_argnames=("score_fn", "backend"))
        self._search_many = jax.jit(
            search_batch, static_argnums=(1, 4, 5, 6),
            static_argnames=("score_fn", "backend"))
        self._search_many_sketch = jax.jit(
            search_batch_sketch, static_argnums=(1, 4, 5),
            static_argnames=("backend",))
        self._compact = jax.jit(compact_state, static_argnums=(1,))
        self._slot_drift = jax.jit(slot_drift, static_argnums=(1,))
        self._obs = _WritePathMetrics()

    def _init_state(self) -> SinnamonState:
        """Fresh device state; the tiered subclass swaps in a placeholder
        store here."""
        return init(self.spec)

    # -- streaming updates ---------------------------------------------------
    def insert(self, ext_id: int, idx, val) -> None:
        t0 = time.perf_counter()
        ext_id = int(ext_id)
        if ext_id in self._id2slot:
            self.delete(ext_id)
        if not self._free:
            self.grow(self.spec.capacity * 2)
        slot = self._free.pop()
        idx, val = pad_sparse(idx, val, self.spec.max_nnz)
        self.state = self._insert(self.state, self.spec, slot,
                                  jnp.asarray(pack_ids64(ext_id)), idx, val)
        self._id2slot[ext_id] = slot
        self._obs.record("insert", t0, 1)

    def insert_many(self, ext_ids, idx_batch, val_batch) -> None:
        t0 = time.perf_counter()
        ext_ids = [int(e) for e in ext_ids]
        if len(set(ext_ids)) != len(ext_ids):
            # Sequential overwrite semantics (same as the sharded index):
            # only the LAST occurrence of a duplicated id survives.
            last = {e: pos for pos, e in enumerate(ext_ids)}
            keep = sorted(last.values())
            ext_ids = [ext_ids[p] for p in keep]
            idx_batch = np.asarray(idx_batch)[keep]
            val_batch = np.asarray(val_batch)[keep]
        for e in ext_ids:
            if e in self._id2slot:      # overwrite: drop the stale copy
                self.delete(e)
        bn = len(ext_ids)
        while len(self._free) < bn:
            self.grow(self.spec.capacity * 2)
        slots = np.array([self._free.pop() for _ in range(bn)], np.int32)
        self.state = self._insert_batch(
            self.state, self.spec, jnp.asarray(slots),
            jnp.asarray(pack_ids64(ext_ids)),
            jnp.asarray(idx_batch), jnp.asarray(val_batch))
        for eid, slot in zip(ext_ids, slots):
            self._id2slot[int(eid)] = int(slot)
        self._obs.record("insert_many", t0, bn)

    def delete(self, ext_id: int) -> None:
        t0 = time.perf_counter()
        slot = self._id2slot.pop(ext_id)
        self.state = self._delete(self.state, self.spec, slot)
        self._free.append(slot)
        self._obs.record("delete", t0, 1)

    # -- retrieval -------------------------------------------------------------
    def search(self, q_idx, q_val, k: int, kprime: Optional[int] = None,
               budget: Optional[int] = None, filter_mask=None, score_fn=None,
               backend: Optional[str] = None):
        kprime = kprime if kprime is not None else max(5 * k, k)
        kprime = min(kprime, self.spec.capacity)
        k = min(k, kprime)
        ids, scores, _ = self._search(
            self.state, self.spec, jnp.asarray(q_idx), jnp.asarray(q_val),
            k, kprime, budget, filter_mask, score_fn=score_fn,
            backend=self._backend(backend))
        return unpack_ids64(np.asarray(ids)), np.asarray(scores)

    def search_many(self, q_idx, q_val, k: int, kprime: Optional[int] = None,
                    budget: Optional[int] = None, filter_mask=None,
                    score_fn=None, backend: Optional[str] = None):
        """Batched search: q_idx/q_val are [B, Lq]; one jit dispatch total.

        Records ``launch`` (operands to the device, the jitted call) and
        ``fetch`` (answers to the host) into the thread's active trace
        context (`repro.obs.trace.stage`)."""
        kprime = kprime if kprime is not None else max(5 * k, k)
        kprime = min(kprime, self.spec.capacity)
        k = min(k, kprime)
        with obs_trace.stage("launch"):
            ids, scores, _ = self._search_many(
                self.state, self.spec, jnp.asarray(q_idx),
                jnp.asarray(q_val), k, kprime, budget, filter_mask,
                score_fn=score_fn, backend=self._backend(backend))
        with obs_trace.stage("fetch"):
            return unpack_ids64(np.asarray(ids)), np.asarray(scores)

    def search_many_sketch(self, q_idx, q_val, k: int,
                           budget: Optional[int] = None,
                           backend: Optional[str] = None):
        """Batched sketch-only search (no exact rerank): the degraded
        serving path.  Scores are sketch UPPER BOUNDS, not inner products
        — see :func:`search_batch_sketch`."""
        k = min(k, self.spec.capacity)
        with obs_trace.stage("launch"):
            ids, ub, _ = self._search_many_sketch(
                self.state, self.spec, jnp.asarray(q_idx),
                jnp.asarray(q_val), k, budget,
                backend=self._backend(backend))
        with obs_trace.stage("fetch"):
            return unpack_ids64(np.asarray(ids)), np.asarray(ub)

    def _backend(self, backend) -> str:
        """Resolve the backend OUTSIDE jit so the default binds at call
        time (not at trace time) and jit caches key on the concrete choice.
        Per-call choice > the index default (``repro.api`` sets it from
        ``IndexConfig.backend``) > the process env default."""
        from repro.kernels import ops as _ops
        if backend is None:
            backend = self.default_backend
        return _ops.resolve_backend(backend)

    # -- capacity management ----------------------------------------------------
    def grow(self, new_capacity: int) -> None:
        """Reallocate to a larger capacity, preserving slot numbering."""
        t0 = time.perf_counter()
        spec = self.spec
        if new_capacity <= spec.capacity or new_capacity % 32 != 0:
            raise ValueError("new capacity must be a larger multiple of 32")
        new_spec = dataclasses.replace(spec, capacity=new_capacity)
        self.state = grow_state(self.state, spec, new_spec)
        self.spec = new_spec
        self._free = (list(range(new_capacity - 1, spec.capacity - 1, -1))
                      + self._free)
        self._obs.record("grow", t0)

    # -- maintenance -----------------------------------------------------------
    def compact(self) -> int:
        """Rebuild all dirty sketch columns from the VecStore.

        Restores the Theorem 5.1 upper-bound tightness lost to §4.3
        delete-then-recycle churn.  Returns the number of columns rebuilt.
        """
        t0 = time.perf_counter()
        n_dirty = int(jnp.sum(self.state.dirty))
        if n_dirty:
            self.state = self._compact(self.state, self.spec)
        self._obs.record("compact", t0)
        return n_dirty

    def slot_drift(self) -> np.ndarray:
        """Per-slot sketch overestimate vs. a fresh sketch (f32[C])."""
        return np.asarray(self._slot_drift(self.state, self.spec))

    @property
    def size(self) -> int:
        return len(self._id2slot)

    def __contains__(self, ext_id) -> bool:
        """True iff ``ext_id`` is currently live in the index."""
        return int(ext_id) in self._id2slot

    def doc_ids(self) -> list:
        """Sorted external ids of every live document."""
        return sorted(self._id2slot)

    def memory_bytes(self) -> dict:
        """Index-size accounting (paper §6.1.2): sketch vs inverted index vs raw."""
        st = self.state
        out = {
            "sketch": st.u.size * st.u.dtype.itemsize
                      + (0 if st.l is None else st.l.size * st.l.dtype.itemsize),
            "inverted_index": st.bits.size * st.bits.dtype.itemsize,
            "storage": st.store.indices.size * st.store.indices.dtype.itemsize
                       + st.store.values.size * st.store.values.dtype.itemsize,
        }
        out["index_total"] = out["sketch"] + out["inverted_index"]
        return out


class TieredSinnamonIndex(SinnamonIndex):
    """SinnamonIndex whose raw VecStore is hot/cold tiered.

    The sketch (and bit index, active, ids, dirty) stays fully
    device-resident; ``state.store`` is a zero-row placeholder and the raw
    CSR rows live in a :class:`repro.storage.tiered.TieredVecStore` — host
    RAM backing behind a bounded device-side chunk cache — so the corpus can
    outgrow the device budget.  Search runs as two dispatches: sketch-only
    candidate generation, then a host sync of the ``[B, k']`` candidate
    slots drives chunk promotion (candidate-driven prefetch) before the
    rows-based exact rerank.  Every rerank flows through the same
    ``exact_scores_rows`` primitive as the resident baseline, so results
    are bit-identical (tests/test_tiered_store.py enforces this, churn and
    all).  Maintenance (compact / slot_drift) reads dirty rows from the
    host backing in fixed-size blocks; ``slot_drift`` reports 0 for clean
    slots (the resident path also reports value-dtype quantization noise
    there — tiering only ever evaluates the dirty set).
    """

    _MAINT_BLOCK = 256           # dirty-slot rows per maintenance dispatch

    def __init__(self, spec: EngineSpec, *, tier_chunk_slots: int = 256,
                 device_budget_bytes: Optional[int] = None,
                 cache_chunks: Optional[int] = None):
        from repro.storage import tiered as tiered_mod
        self.tiered = tiered_mod.TieredVecStore(
            spec.capacity, spec.max_nnz, value_dtype=spec.value_dtype,
            chunk_slots=tier_chunk_slots,
            device_budget_bytes=device_budget_bytes,
            cache_chunks=cache_chunks)
        super().__init__(spec)
        self._cand = jax.jit(topk_candidates, static_argnums=(1, 4, 5),
                             static_argnames=("score_fn", "backend"))
        self._rerank_rows = jax.jit(rerank_topk_rows, static_argnums=(7,))
        self._rerank1 = jax.jit(rerank_single_rows, static_argnums=(7,))
        self._delete_rows = jax.jit(delete_batch_rows, static_argnums=(1,))
        self._compact_rows = jax.jit(compact_slots_rows, static_argnums=(1,))
        self._drift_rows = jax.jit(slot_drift_rows, static_argnums=(1,))

    def _init_state(self) -> SinnamonState:
        return init(self.spec, store_rows=0)

    def _placeholder_store(self) -> vecstore.VecStore:
        return vecstore.empty(0, self.spec.max_nnz,
                              dtype=jnp.dtype(self.spec.value_dtype))

    # -- streaming updates ---------------------------------------------------
    def insert(self, ext_id: int, idx, val) -> None:
        i, v = pad_sparse(idx, val, self.spec.max_nnz)
        self.insert_many([ext_id], np.asarray(i)[None], np.asarray(v)[None])

    def insert_many(self, ext_ids, idx_batch, val_batch) -> None:
        t0 = time.perf_counter()
        ext_ids = [int(e) for e in ext_ids]
        if len(set(ext_ids)) != len(ext_ids):
            # Sequential overwrite semantics (same as the resident index):
            # only the LAST occurrence of a duplicated id survives.
            last = {e: pos for pos, e in enumerate(ext_ids)}
            keep = sorted(last.values())
            ext_ids = [ext_ids[p] for p in keep]
            idx_batch = np.asarray(idx_batch)[keep]
            val_batch = np.asarray(val_batch)[keep]
        for e in ext_ids:
            if e in self._id2slot:      # overwrite: drop the stale copy
                self.delete(e)
        bn = len(ext_ids)
        while len(self._free) < bn:
            self.grow(self.spec.capacity * 2)
        slots = np.array([self._free.pop() for _ in range(bn)], np.int32)
        idx_np = _pad_rows(np.asarray(idx_batch, np.int32),
                           self.spec.max_nnz, -1)
        val_np = _pad_rows(np.asarray(val_batch, np.float32),
                           self.spec.max_nnz, 0)
        # Host backing first (write-through), chunks pinned until the
        # device-side sketch/bit update for this in-flight batch is issued.
        chunks = self.tiered.write_rows(slots, idx_np, val_np, pin=True)
        try:
            self.state = self._insert_batch(
                self.state, self.spec, jnp.asarray(slots),
                jnp.asarray(pack_ids64(ext_ids)),
                jnp.asarray(idx_np), jnp.asarray(val_np))
        finally:
            self.tiered.unpin(chunks)
        for eid, slot in zip(ext_ids, slots):
            self._id2slot[int(eid)] = int(slot)
        self._obs.record("insert_many", t0, bn)

    def delete(self, ext_id: int) -> None:
        t0 = time.perf_counter()
        slot = self._id2slot.pop(int(ext_id))
        row = self.tiered.read_indices(np.array([slot]))
        self.state = self._delete_rows(
            self.state, self.spec, jnp.asarray(np.array([slot], np.int32)),
            jnp.asarray(row), jnp.ones((1,), jnp.bool_))
        self.tiered.erase_rows(np.array([slot]))
        self._free.append(slot)
        self._obs.record("delete", t0, 1)

    # -- retrieval -----------------------------------------------------------
    def search(self, q_idx, q_val, k: int, kprime: Optional[int] = None,
               budget: Optional[int] = None, filter_mask=None, score_fn=None,
               backend: Optional[str] = None):
        kprime = kprime if kprime is not None else max(5 * k, k)
        kprime = min(kprime, self.spec.capacity)
        k = min(k, kprime)
        qi, qv = jnp.asarray(q_idx), jnp.asarray(q_val)
        ub, slots = self._cand(self.state, self.spec, qi[None], qv[None],
                               kprime, budget, filter_mask, score_fn=score_fn,
                               backend=self._backend(backend))
        ub, slots = ub[0], slots[0]
        ridx, rval = self.tiered.gather_rows(np.asarray(slots))
        ids, scores, _ = self._rerank1(self.state, ub, slots, ridx, rval,
                                       qi, qv, k)
        return unpack_ids64(np.asarray(ids)), np.asarray(scores)

    def search_many(self, q_idx, q_val, k: int, kprime: Optional[int] = None,
                    budget: Optional[int] = None, filter_mask=None,
                    score_fn=None, backend: Optional[str] = None):
        """Two dispatches: sketch-scan candidates, then rows-based rerank
        fed by the chunk cache (the ``[B, k']`` slot sync between them is
        what drives promotion).  Each dispatch records its ``launch`` and
        ``fetch`` stages, and the chunk-cache gather a ``promote`` stage."""
        kprime = kprime if kprime is not None else max(5 * k, k)
        kprime = min(kprime, self.spec.capacity)
        k = min(k, kprime)
        with obs_trace.stage("launch"):
            qi, qv = jnp.asarray(q_idx), jnp.asarray(q_val)
            ub, slots = self._cand(self.state, self.spec, qi, qv, kprime,
                                   budget, filter_mask, score_fn=score_fn,
                                   backend=self._backend(backend))
        with obs_trace.stage("fetch"):
            slots_np = np.asarray(slots).reshape(-1)
        with obs_trace.stage("promote"):
            ridx, rval = self.tiered.gather_rows(slots_np)
        with obs_trace.stage("launch"):
            ids, scores, _ = self._rerank_rows(self.state, ub, slots, ridx,
                                               rval, qi, qv, k)
        with obs_trace.stage("fetch"):
            return unpack_ids64(np.asarray(ids)), np.asarray(scores)

    # -- capacity / maintenance ----------------------------------------------
    def grow(self, new_capacity: int) -> None:
        super().grow(new_capacity)          # grow_state keeps the placeholder
        self.tiered.grow(new_capacity)

    def _maint_blocks(self):
        """Yield (slots[B], mask[B], n_real) fixed-size blocks of dirty slots."""
        dirty = np.flatnonzero(np.asarray(self.state.dirty))
        B = self._MAINT_BLOCK
        for i in range(0, dirty.size, B):
            blk = dirty[i:i + B]
            slots = np.zeros((B,), np.int32)
            mask = np.zeros((B,), bool)
            slots[:blk.size] = blk
            mask[:blk.size] = True
            yield slots, mask, blk.size

    def compact(self) -> int:
        t0 = time.perf_counter()
        total = 0
        for slots, mask, n in self._maint_blocks():
            ridx, rval = self.tiered.read_rows(slots)
            self.state = self._compact_rows(
                self.state, self.spec, jnp.asarray(slots), jnp.asarray(ridx),
                jnp.asarray(rval), jnp.asarray(mask))
            total += n
        self._obs.record("compact", t0)
        return total

    def slot_drift(self) -> np.ndarray:
        out = np.zeros((self.spec.capacity,), np.float32)
        for slots, mask, n in self._maint_blocks():
            ridx, rval = self.tiered.read_rows(slots)
            d = np.asarray(self._drift_rows(self.state, self.spec,
                                            jnp.asarray(slots),
                                            jnp.asarray(ridx),
                                            jnp.asarray(rval)))
            out[slots[:n]] = d[:n]
        return out

    def memory_bytes(self) -> dict:
        out = super().memory_bytes()
        out["storage"] = self.tiered.device_bytes()       # device-resident
        out["storage_host"] = self.tiered.host_bytes()    # cold backing
        return out

    # -- persistence hooks (repro.persist.snapshot) --------------------------
    def logical_state(self) -> SinnamonState:
        """The state with the FULL raw store materialized (host arrays) —
        what snapshots serialize, so tiered and resident snapshots are one
        interchangeable format."""
        idx, val = self.tiered.to_arrays()
        return self.state._replace(
            store=vecstore.VecStore(indices=idx, values=val))

    def adopt_logical_state(self, state: SinnamonState) -> None:
        """Install a restored logical state: raw rows go to the host
        backing (tiering state resets to access-free defaults), everything
        else to device with the placeholder store."""
        self.tiered.load_rows(np.asarray(state.store.indices),
                              np.asarray(state.store.values))
        self.state = jax.tree.map(
            jnp.asarray, state._replace(store=self._placeholder_store()))


def _pad_rows(arr: np.ndarray, width: int, fill) -> np.ndarray:
    """Pad [B, L] update rows to the fixed CSR width [B, width]."""
    if arr.shape[1] > width:
        raise ValueError(f"document nnz {arr.shape[1]} > max_nnz {width}")
    if arr.shape[1] == width:
        return arr
    out = np.full((arr.shape[0], width), fill, arr.dtype)
    out[:, :arr.shape[1]] = arr
    return out


def pad_sparse(idx, val, width: int):
    """Pad/truncate a sparse (idx, val) pair to fixed width (pad idx = -1)."""
    idx = np.asarray(idx, np.int32)[:width]
    val = np.asarray(val, np.float32)[:width]
    out_i = np.full((width,), -1, np.int32)
    out_v = np.zeros((width,), np.float32)
    out_i[: idx.size] = idx
    out_v[: val.size] = val
    return jnp.asarray(out_i), jnp.asarray(out_v)
