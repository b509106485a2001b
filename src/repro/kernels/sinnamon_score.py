"""Pallas TPU kernel for Sinnamon scoring (paper Algorithm 6).

This is the paper's hot spot: for each query coordinate, read h sketch rows,
take the elementwise min (max for the lower sketch), mask by the bit-packed
inverted index, scale by q[j] and accumulate.

TPU schedule (the tile-resident formulation): the grid is ``(B, T)`` over
queries and document tiles of ``TC`` slots.  The sketch block of one tile is
resident in VMEM while every budgeted query coordinate streams over it, so
each sketch tile is fetched from HBM once per query (the faithful
coordinate-at-a-time order would fetch ``h`` rows per coordinate).

Layout (what Mosaic lowers):

* a tile of ``TC = S * 128`` slots is an ``[S, 128]`` block, slot
  ``128 * i + j`` at sublane ``i``, lane ``j``.  The sketch is viewed as
  ``[R, C / 128, 128]``, so the sketch row of a coordinate is a read at a
  dynamic index of the leading (untiled) axis of the ``(R, S, 128)`` block;
* the sketch-row ids and the query values are scalar-prefetched into SMEM;
* membership words are re-packed outside the kernel into lane-major bit
  planes (:func:`lane_major_words`): lane ``j`` of a tile's plane holds, in
  bit ``i``, the bit of slot ``128 * i + j``.  The kernel unpacks a plane
  with one shift by the sublane iota, so ``S <= 32`` and the default tile
  ``TC = 4096`` fills the 32 bits of every plane word.

VMEM footprint per grid step: two ``(R, S, 128)`` sketch buffers
(``2 * R * TC * cell bytes``: 1 MiB at R=64 in bf16), the ``[L, 128]``
plane block, and the ``[S, 128]`` gate and score blocks.

Entry points:

* :func:`tile_scores` — the kernel: gated upper-bound scores ``f32[B, C]``.
* :func:`sinnamon_score` — dense scores from separate ``u``/``l`` sketches.
* :func:`sinnamon_score_topk` — the serving program: kernel scores, then
  one ``lax.top_k`` over all slots in XLA (:func:`topk_candidates`; Mosaic
  has no top-k lowering), so the gated ``[B, C]`` score matrix is written
  to HBM once per batch (64 MiB at B=16, C=2^20).

The operands are ONE-SIDED: Algorithm 6 needs ``u``-cells only where
``q[j] > 0`` and ``l``-cells only where ``q[j] < 0``, so the caller stacks
``[U; L]`` into one ``[2m, C]`` matrix and pre-offsets each coordinate's
sketch rows by ``+m`` for non-positive coordinates — half the gather and
reduce work of the reference decode, which reads both sides.

Quantized sketch cells (``EngineSpec.dtype`` = f32 | bf16 | f8) are upcast
to f32 after the row read, so the HBM-resident sketch and the VMEM block
stay at the narrow storage width.

:func:`scores_xla` is the kernel's per-slot program in plain XLA, for
backends without a compiled Pallas lowering (CPU serving); it writes the
same scores and feeds the same top-k.  Interpret-mode ``pallas_call`` is the
kernel-validation path: tests assert kernel == twin == dense oracle on the
same operands.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE_C = 4096          # [32, 128] slots: one plane word per lane
LANES = 128
_WORD = 32


def _check_tile(C: int, tile_c: int) -> None:
    if tile_c % LANES or not LANES <= tile_c <= _WORD * LANES:
        raise ValueError(f"tile_c={tile_c} must be a multiple of {LANES} "
                         f"in [{LANES}, {_WORD * LANES}]")
    if C % tile_c != 0:
        raise ValueError(f"C={C} must be a multiple of tile_c={tile_c}")


def lane_major_words(qbits: jax.Array, tile_c: int) -> jax.Array:
    """Slot-major membership words -> the kernel's lane-major bit planes.

    ``qbits`` uint32[B, L, C/32] holds slot ``32 * w + r`` in bit ``r`` of
    word ``w``.  Returns uint32[B, T, L, 128] with T = C / tile_c, where bit
    ``i`` of lane ``j`` in tile ``c`` is the bit of slot
    ``c * tile_c + 128 * i + j``.
    """
    B, L, W = qbits.shape
    S = tile_c // LANES
    T = W * _WORD // tile_c
    # Word 4i + q of a tile covers slots 128i + 32q + r, i.e. lane 32q + r.
    w = qbits.reshape(B, L, T, S, LANES // _WORD)
    bits = (w[..., None] >> jnp.arange(_WORD, dtype=jnp.uint32)) & 1
    shift = jnp.arange(S, dtype=jnp.uint32)[:, None, None]
    planes = jnp.sum(bits << shift, axis=3, dtype=jnp.uint32)   # [B,L,T,4,32]
    return jnp.swapaxes(planes.reshape(B, L, T, LANES), 1, 2)


def _score_kernel(rows_ref, qv_ref, words_ref, gate_ref, sk_ref, out_ref, *,
                  n_coords: int, h: int, one_sided: bool):
    """One (query, tile) grid step: gated upper-bound scores f32[S, 128].

    rows_ref  SMEM int32[B * L * h]  sketch rows (pre-offset when one_sided)
    qv_ref    SMEM f32[B * L]        query values
    words_ref VMEM int32[1, 1, L, 128]   lane-major bit planes of this tile
    gate_ref  VMEM f32[S, 128]       0 keep / -inf excluded
    sk_ref    VMEM [R, S, 128]       [U; L] rows of this tile
    """
    b = pl.program_id(0)
    S = gate_ref.shape[0]
    shifts = jax.lax.broadcasted_iota(jnp.int32, (S, LANES), 0)

    def coord(t, acc):
        base = b * n_coords + t
        v = qv_ref[base]
        pos = v > 0
        x = sk_ref[rows_ref[base * h]].astype(jnp.float32)
        for o in range(1, h):
            y = sk_ref[rows_ref[base * h + o]].astype(jnp.float32)
            if one_sided:
                # positive coords decode U (least upper bound -> min);
                # negative coords decode L (greatest lower bound -> max).
                x = jnp.where(pos, jnp.minimum(x, y), jnp.maximum(x, y))
            else:
                x = jnp.minimum(x, y)
        if not one_sided:
            # positive-only engine: l == 0 exactly, so q<0 contributes q*0.
            x = jnp.where(pos, x, 0.0)
        plane = words_ref[0, 0, pl.ds(t, 1), :]           # [1, 128]
        member = jax.lax.shift_right_logical(plane, shifts) & 1
        return acc + jnp.where(member != 0, v * x, 0.0)

    acc = jax.lax.fori_loop(0, n_coords, coord,
                            jnp.zeros((S, LANES), jnp.float32))
    out_ref[0] = jnp.where(gate_ref[...] == 0.0, acc, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("tile_c", "one_sided",
                                             "interpret"))
def tile_scores(
    qv: jax.Array,               # f32[B, L]
    rows: jax.Array,             # int32[B, L, h]  (pre-offset when one_sided)
    qbits: jax.Array,            # uint32[B, L, W]  (W = C/32)
    gate: jax.Array,             # f32[1, C]: 0 keep / -inf excluded (or pad)
    skmat: jax.Array,            # [R, C]  [U; L] stacked (R = 2m, or m)
    *,
    tile_c: int,
    one_sided: bool,
    interpret: bool,
) -> jax.Array:
    """Gated upper-bound scores f32[B, C] from the Pallas kernel.
    Grid = (B, C / tile_c)."""
    B, Lq = qv.shape
    h = rows.shape[-1]
    R, C = skmat.shape
    _check_tile(C, tile_c)
    S = tile_c // LANES
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, C // tile_c),
        in_specs=[
            pl.BlockSpec((1, 1, Lq, LANES), lambda b, c, *_: (b, c, 0, 0)),
            pl.BlockSpec((S, LANES), lambda b, c, *_: (c, 0)),
            pl.BlockSpec((R, S, LANES), lambda b, c, *_: (0, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, S, LANES), lambda b, c, *_: (b, c, 0)),
    )
    kern = functools.partial(_score_kernel, n_coords=Lq, h=h,
                             one_sided=one_sided)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C // LANES, LANES), jnp.float32),
        interpret=interpret,
    )(rows.reshape(-1), qv.astype(jnp.float32).reshape(-1),
      jax.lax.bitcast_convert_type(lane_major_words(qbits, tile_c),
                                   jnp.int32),
      gate.reshape(C // LANES, LANES),
      skmat.reshape(R, C // LANES, LANES))
    return out.reshape(B, C)


def one_sided_operands(qv: jax.Array, rows: jax.Array, u: jax.Array,
                       l: Optional[jax.Array]) -> tuple:
    """(rows, skmat, one_sided) for the kernel from separate sketches:
    stacks ``[U; L]`` and offsets non-positive coordinates' rows by +m."""
    if l is None:
        return rows, u, False
    rows = jnp.where((qv > 0)[..., None], rows, rows + u.shape[0])
    return rows, jnp.concatenate([u, l], axis=0), True


@functools.partial(jax.jit, static_argnames=("tile_c", "interpret"))
def sinnamon_score(
    qv: jax.Array,               # f32[B, L]
    rows: jax.Array,             # int32[B, L, h]
    qbits: jax.Array,            # uint32[B, L, W]  (W = C/32)
    u: jax.Array,                # [m, C]
    l: Optional[jax.Array] = None,
    *,
    tile_c: int = DEFAULT_TILE_C,
    interpret: bool,
) -> jax.Array:
    """Upper-bound scores f32[B, C] (ungated) through :func:`tile_scores`."""
    rows, skmat, one_sided = one_sided_operands(qv, rows, u, l)
    gate = jnp.zeros((1, skmat.shape[1]), jnp.float32)
    return tile_scores(qv, rows, qbits, gate, skmat, tile_c=tile_c,
                       one_sided=one_sided, interpret=interpret)


def topk_candidates(scores: jax.Array, kp: int) -> tuple:
    """(vals f32[B, kp], slots int32[B, kp]) of gated scores, in
    ``lax.top_k`` order: score desc, ties by slot asc (named scope
    ``topk``)."""
    with jax.named_scope("topk"):
        vals, slots = jax.lax.top_k(scores, kp)
        return vals, slots.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("kp", "tile_c", "one_sided", "interpret"))
def sinnamon_score_topk(
    qv: jax.Array,               # f32[B, L]
    rows: jax.Array,             # int32[B, L, h]  (pre-offset when one_sided)
    qbits: jax.Array,            # uint32[B, L, W]  (W = C/32)
    gate: jax.Array,             # f32[1, C]: 0 keep / -inf excluded (or pad)
    skmat: jax.Array,            # [R, C]  [U; L] stacked (R = 2m, or m)
    *,
    kp: int,
    tile_c: int = DEFAULT_TILE_C,
    one_sided: bool = True,
    interpret: bool,
) -> tuple:
    """The kernel's serving program: :func:`tile_scores`, then
    :func:`topk_candidates` over all slots.  Returns (vals f32[B, kp],
    slots int32[B, kp]).

    Operand preparation (row offsetting, [U; L] stacking, tile padding)
    lives in repro.kernels.ops.sinnamon_candidate_scores.
    """
    if kp > skmat.shape[1]:
        raise ValueError(f"kp={kp} cannot exceed C={skmat.shape[1]}")
    s = tile_scores(qv, rows, qbits, gate, skmat, tile_c=tile_c,
                    one_sided=one_sided, interpret=interpret)
    return topk_candidates(s, kp)


@functools.partial(jax.jit, static_argnames=("one_sided",))
def scores_xla(
    qv: jax.Array,               # f32[B, L]
    rows: jax.Array,             # int32[B, L, h]  (pre-offset when one_sided)
    qbits: jax.Array,            # uint32[B, L, W]  (W = C/32)
    gate: jax.Array,             # f32[1, C]
    skmat: jax.Array,            # [R, C]
    *,
    one_sided: bool = True,
) -> jax.Array:
    """XLA twin of :func:`tile_scores`: same operands, the same gated
    ``f32[B, C]``, bit for bit.

    The kernel's per-slot program over all slots at once: the same
    one-sided decode, the same f32 products, and the same add order — one
    add per coordinate, in coordinate order, from +0.  The coordinate loop
    is unrolled, so XLA fuses the whole sum into one pass over the slots
    without a ``[B, L, C]`` intermediate.  This is how the kernel's math
    runs on backends where Pallas has only its interpreter (CPU serving).
    """
    B, Lq = qv.shape
    h = rows.shape[-1]
    C = skmat.shape[1]
    sk = skmat.astype(jnp.float32)
    shifts = jnp.arange(_WORD, dtype=jnp.uint32)
    acc = jnp.zeros((B, C), jnp.float32)
    for t in range(Lq):
        v = qv[:, t, None]                                  # [B, 1]
        x = sk[rows[:, t, 0]]                               # [B, C]
        for o in range(1, h):
            y = sk[rows[:, t, o]]
            if one_sided:
                # positive coords decode U (min); negative decode L (max).
                x = jnp.where(v > 0, jnp.minimum(x, y), jnp.maximum(x, y))
            else:
                x = jnp.minimum(x, y)
        if not one_sided:
            # positive-only engine: l == 0 exactly, so q<0 contributes q*0.
            x = jnp.where(v > 0, x, 0.0)
        member = ((qbits[:, t, :, None] >> shifts) & 1).reshape(B, C)
        acc = acc + jnp.where(member != 0, v * x, 0.0)
    return jnp.where(gate == 0.0, acc, -jnp.inf)
