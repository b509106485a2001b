"""jit'd public wrappers around the Pallas kernels + the scoring-backend
dispatch point.

Handles operand preparation (query sorting/budgeting, membership-row
gathering, tile padding) and implementation selection: the compiled Pallas
kernel on a TPU, never the interpreter or the twin there; elsewhere the
dense kernel wrappers run in interpret mode (the validation path) while the
fused serving path runs its XLA twin — the kernel's per-slot program without
the per-grid-step interpreter overhead (interpret-mode execution of the fused
kernel remains available via ``use_kernel=True`` and is what the
equivalence tests exercise).

Scoring-backend dispatch
------------------------
Every query hot path (``engine.search``/``search_batch``, both serving
layers, the launcher) routes candidate generation through ONE selector:

* ``pallas``    — the tiled kernel: it writes the gated ``[B, C]`` score
  matrix once and XLA takes one top-k over it (on the CPU the kernel's XLA
  twin writes the same scores).  The production default.
* ``grouped``   — ``engine.score_grouped`` (one fused [L, C] pass) + dense
  ``lax.top_k``.
* ``reference`` — paper-faithful coordinate-at-a-time ``engine.score`` +
  dense ``lax.top_k``; the correctness oracle.

Select per call (``backend=...``), per server (``--score-backend``), or
process-wide via the ``REPRO_SCORE_BACKEND`` environment variable.

The §3.3 *lite* sketch variant (``EngineSpec.sketch_kind="lite"``) rides the
existing one-sided machinery for free: with no ``l`` leaf the fused path
gathers only ``U`` rows and zeroes negative-coordinate contributions —
exactly the Sinnamon+ code path, now reachable on signed collections as a
memory/recall lever.  Quantized cells (bf16/f8) flow through every gather
unchanged and are upcast to f32 inside the tile (see
repro.kernels.sinnamon_score).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import embed_bag as _bag
from repro.kernels import sinnamon_score as _sinn

SCORE_BACKENDS = ("reference", "grouped", "pallas")
SCORE_BACKEND_ENV = "REPRO_SCORE_BACKEND"
DEFAULT_SCORE_BACKEND = "pallas"


def resolve_backend(backend: Optional[str] = None) -> str:
    """Validate an explicit backend choice or fall back to the env default."""
    if backend is None:
        backend = os.environ.get(SCORE_BACKEND_ENV, DEFAULT_SCORE_BACKEND)
    if backend not in SCORE_BACKENDS:
        raise ValueError(f"unknown score backend {backend!r}; "
                         f"expected one of {SCORE_BACKENDS}")
    return backend


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


def pad_axis(x: jax.Array, axis: int, multiple: int, fill=0):
    size = x.shape[axis]
    target = ((size + multiple - 1) // multiple) * multiple
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=fill)


def prepare_query_operands(state, q_idx: jax.Array, q_val: jax.Array,
                           budget: Optional[int] = None, spec=None):
    """Engine state + padded sparse query -> (qv, rows, qbits) kernel operands.

    Sorts coordinates by |q[j]| descending (Algorithm 6 line 2), truncates to
    the anytime budget, gathers the h sketch-row ids and the membership words
    per kept coordinate.  Padded / out-of-budget coordinates get qv = 0.
    """
    L = q_idx.shape[-1] if budget is None else min(budget, q_idx.shape[-1])
    key = jnp.where(q_idx >= 0, jnp.abs(q_val.astype(jnp.float32)), -1.0)
    order = jnp.argsort(-key, axis=-1)[..., :L]
    idx_s = jnp.take_along_axis(q_idx, order, axis=-1)
    val_s = jnp.take_along_axis(q_val, order, axis=-1).astype(jnp.float32)
    valid = idx_s >= 0
    safe = jnp.where(valid, idx_s, 0)
    qv = jnp.where(valid, val_s, 0.0)
    rows = jnp.moveaxis(state.mappings[:, safe], 0, -1)       # [..., L, h]
    from repro.core import engine as _eng
    bit_rows = jnp.maximum(_eng.coord_rows(spec, idx_s), 0) if spec \
        is not None else safe
    qbits = state.bits[bit_rows]                               # [..., L, W]
    qbits = jnp.where(valid[..., None], qbits, jnp.uint32(0))
    return qv, rows, qbits


def sinnamon_score_batch(state, qv, rows, qbits, *, tile_c=None,
                         interpret=None):
    """Kernel-backed Algorithm 6 over a query batch. f32[B, C]."""
    C = state.u.shape[1]
    tile_c = tile_c or _default_tile(C, _sinn.DEFAULT_TILE_C)
    interpret = _interpret() if interpret is None else interpret
    u = pad_axis(state.u, 1, tile_c)
    l = None if state.l is None else pad_axis(state.l, 1, tile_c)
    qbits_p = pad_axis(qbits, -1, tile_c // 32)
    out = _sinn.sinnamon_score(qv, rows, qbits_p, u, l,
                               tile_c=tile_c, interpret=interpret)
    return out[:, :C]


def _default_tile(C: int, full: int) -> int:
    """``full``, or all of a small C rounded up to 256 slots — for the
    kernel, every block is then (8, 128)-aligned or spans the whole padded
    slot axis."""
    return min(full, ((C + 255) // 256) * 256)


def prepare_fused_operands(state, q_idx, q_val, budget=None, spec=None):
    """Query + state -> (qv, rows, qbits, skmat, one_sided) for the fused
    kernel / XLA twin.

    On top of :func:`prepare_query_operands`: stacks ``[U; L]`` into one
    gather matrix and pre-offsets non-positive coordinates' sketch rows by
    +m, so the fused path reads each sketch cell ONE-SIDED — half the decode
    work of the reference scorer.
    """
    with jax.named_scope("operands"):
        qv, rows, qbits = prepare_query_operands(state, q_idx, q_val, budget,
                                                 spec=spec)
        rows, skmat, one_sided = _sinn.one_sided_operands(qv, rows, state.u,
                                                          state.l)
    return qv, rows, qbits, skmat, one_sided


def sinnamon_candidate_scores(state, spec, q_idx, q_val, *, budget=None,
                              ok=None, tile_c=None, use_kernel=None,
                              interpret=None):
    """Sketch-scan stage of the fused path: gated upper-bound scores
    f32[B, C] (``-inf`` where ``ok`` is False).

    Prepares one-sided operands and runs the tile program; the kernel's
    slot axis is padded to a tile multiple (padded slots are gated to -inf
    and sliced off — works at any post-``grow()`` capacity).  The operand
    preparation, the gate and the scan run in the ``operands``, ``topk`` and
    ``scan`` named scopes, which split a profiled search program's device
    time.

    On a TPU this is always the compiled kernel (``use_kernel`` and
    ``interpret`` default to it); elsewhere the XLA twin
    :func:`repro.kernels.sinnamon_score.scores_xla`, unless
    ``use_kernel=True`` asks for the interpreted kernel.
    """
    C = state.u.shape[1]
    use_kernel = on_tpu() if use_kernel is None else use_kernel
    qv, rows, qbits, skmat, one_sided = prepare_fused_operands(
        state, q_idx, q_val, budget, spec=spec)
    with jax.named_scope("topk"):
        keep = jnp.ones((C,), jnp.bool_) if ok is None else ok
        gate = jnp.where(keep, 0.0, -jnp.inf).astype(jnp.float32)[None]
    with jax.named_scope("scan"):
        if not use_kernel:
            return _sinn.scores_xla(qv, rows, qbits, gate, skmat,
                                    one_sided=one_sided)
        tile_c = tile_c or _default_tile(C, _sinn.DEFAULT_TILE_C)
        interpret = _interpret() if interpret is None else interpret
        s = _sinn.tile_scores(
            qv, rows, pad_axis(qbits, -1, tile_c // 32),
            pad_axis(gate, -1, tile_c, fill=-jnp.inf),
            pad_axis(skmat, 1, tile_c),
            tile_c=tile_c, one_sided=one_sided, interpret=interpret)
        return s[:, :C]


def sinnamon_topk_batch(state, spec, q_idx, q_val, kprime, *, budget=None,
                        ok=None, tile_c=None, use_kernel=None,
                        interpret=None):
    """Fused candidate generation: (vals f32[B, kprime], slots int32[B, kprime]).

    The full search front half: :func:`sinnamon_candidate_scores`, then one
    ``lax.top_k`` over all slots.  ``ok``: optional bool[C] keep-mask
    (active & filter); the result is in (upper-bound desc, slot asc) order —
    the order of every other backend.
    """
    C = state.u.shape[1]
    if kprime > C:
        raise ValueError(f"kprime={kprime} > capacity {C}")
    scores = sinnamon_candidate_scores(
        state, spec, q_idx, q_val, budget=budget, ok=ok, tile_c=tile_c,
        use_kernel=use_kernel, interpret=interpret)
    return _sinn.topk_candidates(scores, kprime)


def embed_bag(table, indices, weights=None, *, mode="sum", interpret=None):
    """EmbeddingBag(sum|mean) built on the Pallas gather kernel."""
    interpret = _interpret() if interpret is None else interpret
    B, F = indices.shape
    if weights is None:
        weights = jnp.ones((B, F), jnp.float32)
    if mode == "mean":
        counts = jnp.maximum((indices >= 0).sum(-1, keepdims=True), 1)
        weights = weights / counts
    elif mode != "sum":
        raise ValueError(mode)
    return _bag.embed_bag(table, indices, weights, interpret=interpret)
