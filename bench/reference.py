"""The plain reference: the Sinnamon search semantics written out directly.

It imports nothing of the program and takes nothing the program made.  From
the configuration and the bench's own corpus it recomputes, for each query:

* the coordinate mappings π_o: [n] -> [m] (the published recipe: a Philox
  stream keyed by the index seed, ``integers(0, m, (h, n))``);
* each document's sketch: U[r] = max, L[r] = min of the document's values
  whose coordinate maps to r, rounded to the sketch cell type up (U) or
  down (L) to the nearest representable value (no round trip through a
  narrow type, which a compiler allowed excess precision may drop);
* the upper bound of each document: the sum over the coordinates the
  document shares with the query of q_j·min_o U[π_o(j)] (q_j > 0) or
  q_j·max_o L[π_o(j)] (q_j < 0); an index without a lower sketch
  (positive-only) takes 0 for q_j < 0;
* on each shard (documents are routed to shards by the multiplicative hash
  of their id), the k' documents with the largest upper bound, ties to the
  lower id;
* the exact inner product of each candidate, with the document's values
  rounded to the raw-store type, and the k best of all shards' candidates.

The arithmetic runs on the device in float32, in blocks of documents, with
all the sampled queries at once; the last merge runs on the host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import ml_dtypes  # noqa: F401  (registers bfloat16 and float8 with numpy)
import numpy as np

SUB_BLOCK = 2048        # documents per step inside one chunk program


def mappings(seed: int, n: int, m: int, h: int) -> np.ndarray:
    """int32[h, n]: coordinate -> sketch row, per mapping."""
    gen = np.random.Generator(np.random.Philox(key=seed))
    return gen.integers(0, m, size=(h, n), dtype=np.int32)


def shard_of(doc_ids: np.ndarray, shards: int) -> np.ndarray:
    """Owning shard of each document id (Knuth multiplicative hash)."""
    ids = np.asarray(doc_ids, np.uint64)
    return ((ids * np.uint64(2654435761)) & np.uint64(0xFFFFFFFF)) \
        % np.uint64(shards)


def grid(dtype) -> np.ndarray:
    """Every finite value of a narrow float type, ascending, as float32."""
    dt = np.dtype(dtype)
    bits = np.arange(1 << (8 * dt.itemsize), dtype=np.uint64).astype(
        {1: np.uint8, 2: np.uint16}[dt.itemsize])
    vals = bits.view(dt).astype(np.float32)
    return np.unique(vals[np.isfinite(vals)])


def round_directed(x, values, up: bool):
    """Round float32 ``x`` to the nearest entry of the ascending grid
    ``values`` at or above (``up``) or at or below it.  ``values=None``
    means bfloat16, whose values are the float32 values with the low 16
    bits clear: rounding is a truncation of the bit pattern (toward zero)
    or one step past it (away from zero)."""
    if values is None:
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        trunc = bits & jnp.uint32(0xFFFF0000)
        away = (trunc != bits) & ((x > 0) if up else (x < 0))
        out = jnp.where(away, trunc + jnp.uint32(0x10000), trunc)
        return jax.lax.bitcast_convert_type(out, jnp.float32)
    if up:
        pos = jnp.searchsorted(values, x, side="left")
    else:
        pos = jnp.searchsorted(values, x, side="right") - 1
    return values[jnp.clip(pos, 0, values.shape[0] - 1)]


def round_store(x, store_dtype: str):
    """float32 ``x`` rounded to the nearest value of the raw-store type,
    kept in float32, without a round trip through the narrow type (which a
    compiler that allows excess precision may remove): ``reduce_precision``
    for bfloat16, the nearest entry of the value grid for other types."""
    x = x.astype(jnp.float32)
    dt = np.dtype(store_dtype)
    if dt == np.float32:
        return x
    if dt == np.dtype("bfloat16"):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    values = jnp.asarray(grid(dt))
    hi = jnp.clip(jnp.searchsorted(values, x), 0, values.shape[0] - 1)
    lo = jnp.clip(hi - 1, 0, values.shape[0] - 1)
    a, b = values[lo], values[hi]
    return jnp.where(jnp.abs(x - a) <= jnp.abs(b - x), a, b)


def _cell_grid(cell_dtype: str):
    if np.dtype(cell_dtype) in (np.float32, np.dtype("bfloat16")):
        return None
    return jnp.asarray(grid(cell_dtype))


def _sketch_side(val, rows, valid, cell_dtype: str, grid_vals, upper: bool):
    """Per-(document, slot) decoded bound, float32 ``[cb, P]``: for the
    coordinate in slot p, over its h rows, the least (upper side) or the
    greatest (lower side) sketch cell, where a cell holds the max (min) of
    the document's values whose coordinate maps to that row, rounded up
    (down) to the cell type.  Written as a comparison of every slot's rows
    with every other slot's, within each document (no scatter)."""
    h = rows.shape[0]
    fill = -jnp.inf if upper else jnp.inf
    v = jnp.where(valid, val, fill)[:, None, :]             # [cb, 1, P]
    out = None
    for o in range(h):
        hits = jnp.zeros(v.shape[:1] + (rows.shape[2],) * 2, jnp.bool_)
        for o2 in range(h):
            hits |= rows[o][:, :, None] == rows[o2][:, None, :]
        pick = jnp.where(hits, v, fill)                      # [cb, P, P]
        cell = jnp.max(pick, axis=2) if upper else jnp.min(pick, axis=2)
        cell = jnp.where(jnp.isinf(cell), 0.0, cell)
        if cell_dtype != "float32":
            cell = round_directed(cell, grid_vals, up=upper)
        out = cell if out is None else (jnp.minimum(out, cell) if upper
                                        else jnp.maximum(out, cell))
    return out


@functools.partial(jax.jit, static_argnames=("signed", "cell_dtype",
                                             "store_dtype"))
def _block_scores(idx, val, maps, q_table, *, signed: bool, cell_dtype: str,
                  store_dtype: str):
    """(upper bounds, exact scores), each f32[B, cb], of one document block.
    ``q_table`` is f32[n + 1, B]: the dense queries, row n all zero."""
    n = maps.shape[1]
    grid_vals = _cell_grid(cell_dtype)
    valid = idx >= 0
    safe = jnp.where(valid, idx, 0)
    rows = maps[:, safe]                                   # [h, cb, P]
    ub_cell = _sketch_side(val, rows, valid, cell_dtype, grid_vals,
                           upper=True)
    lb_cell = (_sketch_side(val, rows, valid, cell_dtype, grid_vals,
                            upper=False)
               if signed else jnp.zeros_like(ub_cell))
    g = q_table[jnp.where(valid, idx, n)]                  # [cb, P, B]
    ub = jnp.sum(jnp.where(g > 0, g * ub_cell[..., None],
                           g * lb_cell[..., None]), axis=1)
    x = round_store(val, store_dtype)
    exact = jnp.sum(g * x[..., None], axis=1)
    return ub.T, exact.T


@functools.partial(jax.jit, static_argnames=("signed", "cell_dtype",
                                             "store_dtype", "kprime",
                                             "shards"))
def _chunk_candidates(idx, val, doc_shard, maps, q_table, *, signed,
                      cell_dtype, store_dtype, kprime, shards):
    """Per shard, the chunk's k' best upper bounds: (ub, position, exact),
    each [shards, B, k']."""
    cb, P = idx.shape
    sub = min(SUB_BLOCK, cb)
    blocks = (idx.reshape(cb // sub, sub, P), val.reshape(cb // sub, sub, P))

    def one(block):
        return _block_scores(block[0], block[1], maps, q_table,
                             signed=signed, cell_dtype=cell_dtype,
                             store_dtype=store_dtype)

    ub, exact = jax.lax.map(one, blocks)                   # [nb, B, sub]
    B = q_table.shape[1]
    ub = jnp.moveaxis(ub, 0, 1).reshape(B, cb)
    exact = jnp.moveaxis(exact, 0, 1).reshape(B, cb)
    kp = min(kprime, cb)
    outs = []
    for s in range(shards):
        mine = jnp.where((doc_shard == s)[None, :], ub, -jnp.inf)
        v, pos = jax.lax.top_k(mine, kp)
        outs.append((v, pos, jnp.take_along_axis(exact, pos, axis=1)))
    return tuple(jnp.stack(t) for t in zip(*outs))


def dense_queries(q_idx: np.ndarray, q_val: np.ndarray, n: int) -> np.ndarray:
    """f32[n + 1, B]: column b is query b, dense; row n stays zero."""
    table = np.zeros((n + 1, len(q_idx)), np.float32)
    for b, (qi, qv) in enumerate(zip(q_idx, q_val)):
        keep = qi >= 0
        np.add.at(table[:, b], qi[keep], qv[keep])
    return table


def search(chunks, q_idx, q_val, *, n: int, m: int, h: int, map_seed: int,
           signed: bool, cell_dtype: str, store_dtype: str, k: int,
           kprime: int, shards: int):
    """Reference answers for queries ``[S, pad]``: (ids int64[S, k],
    scores f32[S, k], the k'-th largest upper bound of each shard [S, shards]).  ``chunks`` is ``[(first id, idx, val), ...]`` with
    the arrays on their devices."""
    maps_host = mappings(map_seed, n, m, h)
    table = dense_queries(q_idx, q_val, n)
    per_dev = {}
    parts = []
    for lo, idx, val in chunks:
        dev = next(iter(idx.devices()))
        if dev not in per_dev:
            per_dev[dev] = (jax.device_put(maps_host, dev),
                            jax.device_put(table, dev))
        maps, q_table = per_dev[dev]
        cb = idx.shape[0]
        doc_shard = jax.device_put(
            shard_of(np.arange(lo, lo + cb), shards).astype(np.int32), dev)
        ub, pos, exact = _chunk_candidates(
            idx, val, doc_shard, maps, q_table, signed=signed,
            cell_dtype=cell_dtype, store_dtype=store_dtype, kprime=kprime,
            shards=shards)
        parts.append((lo, ub, pos, exact))
    ub = np.concatenate([np.asarray(p[1]) for p in parts], axis=2)
    ids = np.concatenate([np.asarray(p[2]).astype(np.int64) + p[0]
                          for p in parts], axis=2)
    exact = np.concatenate([np.asarray(p[3]) for p in parts], axis=2)
    S = len(q_idx)
    out_ids = np.zeros((S, k), np.int64)
    out_scores = np.zeros((S, k), np.float32)
    kth_ub = np.full((S, shards), np.nan)
    for b in range(S):
        cand_ids, cand_exact = [], []
        for s in range(shards):
            order = np.lexsort((ids[s, b], -ub[s, b]))[:kprime]
            order = order[np.isfinite(ub[s, b, order])]
            if len(order):
                kth_ub[b, s] = ub[s, b, order[-1]]
            cand_ids.append(ids[s, b, order])
            cand_exact.append(exact[s, b, order])
        ci = np.concatenate(cand_ids)
        ce = np.concatenate(cand_exact)
        best = np.argsort(-ce, kind="stable")[:k]
        out_ids[b, :len(best)] = ci[best]
        out_scores[b, :len(best)] = ce[best]
    return out_ids, out_scores, kth_ub


@jax.jit
def _take_rows(arr, at):
    return arr[at]


def doc_rows(chunks, doc_ids) -> dict:
    """{doc id: (idx, val)} host rows of the given documents."""
    doc_ids = np.unique(np.asarray(doc_ids, np.int64))
    out = {}
    for lo, idx, val in chunks:
        cb = idx.shape[0]
        mine = doc_ids[(doc_ids >= lo) & (doc_ids < lo + cb)]
        if not len(mine):
            continue
        # Gather at a power-of-two count of positions, so that runs reuse a
        # few compiled gathers instead of compiling one per count.
        at = np.zeros(1 << max(6, (len(mine) - 1).bit_length()), np.int32)
        at[:len(mine)] = mine - lo
        ri, rv = np.asarray(_take_rows(idx, at)), np.asarray(_take_rows(val, at))
        for j, d in enumerate(mine):
            out[int(d)] = (ri[j], rv[j])
    return out


def exact_score(row, q_idx, q_val, store_dtype: str) -> float:
    """Exact inner product (float64) of one document row with a query, the
    document's values rounded to the raw-store type."""
    idx, val = row
    keep = idx >= 0
    x = val[keep].astype(np.dtype(store_dtype)).astype(np.float64)
    qk = q_idx >= 0
    q = dict(zip(q_idx[qk].tolist(), q_val[qk].astype(np.float64).tolist()))
    return float(sum(q.get(int(j), 0.0) * xv
                     for j, xv in zip(idx[keep].tolist(), x.tolist())))


def upper_bound(row, q_idx, q_val, maps: np.ndarray, m: int, cell_dtype: str,
                signed: bool) -> float:
    """Upper bound (float64 sum) of one document row for one query: the
    same semantics as the device arithmetic, for diagnostics and tests."""
    idx, val = row
    keep = idx >= 0
    idx, val = idx[keep], val[keep].astype(np.float32)
    up = np.full(m, -np.inf, np.float32)
    lo = np.full(m, np.inf, np.float32)
    for o in range(maps.shape[0]):
        np.maximum.at(up, maps[o, idx], val)
        np.minimum.at(lo, maps[o, idx], val)
    up = np.where(np.isinf(up), 0.0, up).astype(np.float32)
    lo = np.where(np.isinf(lo), 0.0, lo).astype(np.float32)
    if cell_dtype != "float32":
        g = grid(cell_dtype)
        up = g[np.clip(np.searchsorted(g, up, side="left"), 0, len(g) - 1)]
        lo = g[np.clip(np.searchsorted(g, lo, side="right") - 1, 0,
                       len(g) - 1)]
    q = {int(j): float(v) for j, v in zip(q_idx, q_val) if j >= 0}
    total = 0.0
    for j in idx.tolist():
        if j in q:
            rows = maps[:, j]
            ub = float(np.min(up[rows]))
            lb = float(np.max(lo[rows])) if signed else 0.0
            total += q[j] * (ub if q[j] > 0 else lb)
    return total
