"""Explicitly-sharded EquiformerV2 message passing (shard_map).

The GSPMD-automatic path (models/gnn.py) is correct but replicates node
features around the arbitrary-index gather — at ogb_products scale (2.45M
nodes × 49 coef × 128 ch) that is ~62 GB per device.  This module is the
beyond-baseline schedule (EXPERIMENTS.md §Perf, cell equiformer-v2 ×
ogb_products):

  * node tensors: REPLICATED over 'data', channel-sharded over 'model'
    → per-device f is [N, K, C/16] (~240 MB bf16 / 3.8 GB f32 at ogb scale);
  * edges: sharded over 'data'; gathers and scatters are fully shard-local;
  * SO(2) conv: weights row-sharded over 'model', partial matmul + psum;
  * per-shard streaming segment-softmax states merged across 'data' with the
    associative (max, denom, numerator) combine — one pmax + two psums per
    layer instead of per-chunk collectives;
  * the per-degree output mixing (w_out) is folded into the *edge* path
    (linear ops commute with the attention-weighted sum and with rotations),
    so node-level updates never need full-C matmuls;
  * node updates (LN + gating) are computed on each device's node range and
    all-gathered over 'data'.

Numerics match models/gnn.py exactly (tests/test_gnn_sharded.py).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import gnn, sh
from repro.models.gnn import NEG, GNNConfig, GraphBatch, _m_indices, _rbf

Array = jax.Array


def _axis_size(ax):
    return jax.lax.psum(1, ax)


def _axis_linear_index(axes):
    """Linear device index over a tuple of mesh axes (major-to-minor)."""
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


def so2_conv_sharded(fr: Array, lp_so2, cfg: GNNConfig,
                     model_axis) -> Array:
    """SO(2) conv with C sharded: fr [e, K, Cl]; weights row-sharded.

    Weight rows use the (channel-major, degree-minor) layout of
    gnn._flat_cmajor, so this device's contiguous row shard is exactly its
    channel slice × all degrees.  Output is full-C (partial matmuls psum'ed
    over the model axis) in the same flattened layout.
    """
    e, K, Cl = fr.shape
    lm = cfg.l_max
    C = cfg.c

    def mix(flat_local, w_local):
        # flat_local [e, n_rows*Cl]; w_local [(n_rows*Cl), n_rows*C]
        return jax.lax.psum(flat_local @ w_local, model_axis)

    out = jnp.zeros((e, K, C), fr.dtype)
    i0 = jnp.asarray(_m_indices(lm, 0))
    o0 = mix(gnn._flat_cmajor(fr[:, i0, :]), lp_so2["w0"])
    out = out.at[:, i0, :].set(gnn._unflat_cmajor(o0, lm + 1))
    for m in range(1, cfg.m_max + 1):
        ip = jnp.asarray(_m_indices(lm, m))
        im = jnp.asarray(_m_indices(lm, -m))
        nm = lm + 1 - m
        cm = gnn._flat_cmajor(fr[:, ip, :])
        sm = gnn._flat_cmajor(fr[:, im, :])
        cp = mix(cm, lp_so2[f"w{m}r"]) - mix(sm, lp_so2[f"w{m}i"])
        sp = mix(cm, lp_so2[f"w{m}i"]) + mix(sm, lp_so2[f"w{m}r"])
        out = out.at[:, ip, :].set(gnn._unflat_cmajor(cp, nm))
        out = out.at[:, im, :].set(gnn._unflat_cmajor(sp, nm))
    return out


def _per_l_linear_full(x: Array, w: Array, cfg: GNNConfig) -> Array:
    outs = [x[:, sh.l_slice(l), :] @ w[l].astype(x.dtype)
            for l in range(cfg.l_max + 1)]
    return jnp.concatenate(outs, axis=1)


def mp_layer_local(lp, f_slice: Array, src, dst, vec, cfg: GNNConfig,
                   *, data_axis, model_axis: str, N: int) -> Array:
    """Per-device body of one message-passing layer.

    f_slice: [N/nd, K, Cl] — this device's NODE range × channel slice.  The
    layer-boundary representation is doubly sharded so the remat'ed layer
    scan only snapshots N/nd-sized carries; the full node table is a
    per-layer transient (all-gathered here, recomputed in the backward).
    src/dst/vec: this data-shard's edge slice.
    Returns the updated f_slice (same layout).
    """
    K = cfg.k
    C = cfg.c
    H = cfg.n_heads
    Cl = f_slice.shape[-1]
    f_local = jax.lax.all_gather(f_slice, data_axis, axis=0, tiled=True)
    midx = jax.lax.axis_index(model_axis)
    c_lo = midx * Cl
    E_local = src.shape[0]
    chunk = min(cfg.edge_chunk, E_local)
    while E_local % chunk != 0:
        chunk -= 1
    nch = E_local // chunk
    resh = lambda x: x.reshape((nch, chunk) + x.shape[1:])
    xs = (resh(src), resh(dst), resh(vec))

    def edge_math(src_c, dst_c, vec_c):
        valid = src_c >= 0
        s_src = jnp.where(valid, src_c, 0)
        s_dst = jnp.where(valid, dst_c, 0)
        fs = f_local[s_src]                              # [e, K, Cl] local
        blocks = sh.wigner_blocks(cfg.l_max, vec_c)
        fr = sh.apply_blocks(blocks, fs)
        conv = so2_conv_sharded(fr, lp["so2"], cfg, model_axis)
        r = jnp.linalg.norm(vec_c, axis=-1)
        gate = jax.nn.silu(_rbf(r, cfg) @ lp["rad1"]) @ lp["rad2"]
        conv = conv * gnn._per_l_expand(gate, cfg.l_max)[..., None]
        inv = conv[:, 0, :]                              # full-C (post-psum)
        logits = jax.nn.silu(inv @ lp["wa1"]) @ lp["wa2"]
        logits = jnp.where(valid[:, None], logits, NEG)
        return valid, s_dst, blocks, conv, logits

    # ---- pass 1 (no gradients): global per-dst max of attention logits.
    # The max shift cancels between numerator and denominator, so its
    # gradient is exactly zero — a stop_gradient pass is exact and keeps the
    # backward free of per-chunk carry residuals.
    def max_fn(M, inp):
        valid, s_dst, _, _, logits = edge_math(*inp)
        return jnp.maximum(M, jax.ops.segment_max(logits, s_dst,
                                                  num_segments=N)), None

    M0 = jnp.full((N, H), NEG, jnp.float32)
    M, _ = jax.lax.scan(jax.checkpoint(max_fn), M0,
                        jax.lax.stop_gradient(xs))
    # M still carries a tangent via the f_local closure — sever it before
    # the collective (pmax has no differentiation rule; the shift's true
    # gradient is zero anyway).
    M_g = jax.lax.pmax(jax.lax.stop_gradient(M), data_axis)

    # ---- pass 2 (with gradients): accumulate the softmax numerator and
    # denominator.  A plain remat'ed scan would still snapshot its (num, Z)
    # carry every chunk (~4 GB × n_chunks), so the accumulation is a
    # custom_vjp whose backward re-walks the chunks, pulling the (d_num, d_Z)
    # cotangents through a per-chunk jax.vjp and summing into a single
    # [N, K, Cl]-sized d_f accumulator — the flash-attention backward
    # structure.  d_M_g is returned as zeros: M_g is a softmax shift whose
    # true gradient through the num/Z *ratio* is identically zero (and it is
    # produced under stop_gradient anyway).
    lp_edge = {k: lp[k] for k in
               ("so2", "rad1", "rad2", "wa1", "wa2", "w_out")}

    def chunk_contrib(f_loc, lpe, M_shift, c_lo_f, inp):
        c_lo_i = c_lo_f.astype(jnp.int32)
        src_c, dst_c, vec_c = inp
        valid = src_c >= 0
        s_src = jnp.where(valid, src_c, 0)
        s_dst = jnp.where(valid, dst_c, 0)
        fs = f_loc[s_src]
        blocks = sh.wigner_blocks(cfg.l_max, vec_c)
        fr = sh.apply_blocks(blocks, fs)
        conv = so2_conv_sharded(fr, lpe["so2"], cfg, model_axis)
        r = jnp.linalg.norm(vec_c, axis=-1)
        gate = jax.nn.silu(_rbf(r, cfg) @ lpe["rad1"]) @ lpe["rad2"]
        conv = conv * gnn._per_l_expand(gate, cfg.l_max)[..., None]
        logits = jax.nn.silu(conv[:, 0, :] @ lpe["wa1"]) @ lpe["wa2"]
        logits = jnp.where(valid[:, None], logits, NEG)
        mixed = _per_l_linear_full(conv, lpe["w_out"], cfg)
        msg = sh.apply_blocks(blocks, mixed, transpose=True)
        msg = jax.lax.dynamic_slice_in_dim(msg, c_lo_i, Cl, axis=2)
        msg = msg.reshape(-1, K, H, Cl // H)
        p = jnp.where(valid[:, None], jnp.exp(logits - M_shift[s_dst]), 0.0)
        num_c = jax.ops.segment_sum(
            (msg * p[:, None, :, None]).astype(jnp.float32), s_dst,
            num_segments=N)
        Z_c = jax.ops.segment_sum(p, s_dst, num_segments=N)
        return num_c, Z_c

    def _agg_fwd_scan(f_loc, lpe, M_shift, c_lo_f, xs):
        def step(carry, inp):
            num, Z = carry
            nc, zc = chunk_contrib(f_loc, lpe, M_shift, c_lo_f, inp)
            return (num + nc, Z + zc), None

        num0 = jnp.zeros((N, K, H, Cl // H), jnp.float32)
        Z0 = jnp.zeros((N, H), jnp.float32)
        (num, Z), _ = jax.lax.scan(step, (num0, Z0), xs)
        return num, Z

    @jax.custom_vjp
    def aggregate(f_loc, lpe, M_shift, c_lo_f, xs):
        return _agg_fwd_scan(f_loc, lpe, M_shift, c_lo_f, xs)

    def agg_fwd(f_loc, lpe, M_shift, c_lo_f, xs):
        return (_agg_fwd_scan(f_loc, lpe, M_shift, c_lo_f, xs),
                (f_loc, lpe, M_shift, c_lo_f, xs))

    def agg_bwd(res, cots):
        f_loc, lpe, M_shift, c_lo_f, xs_r = res

        def step(carry, inp):
            d_f, d_lpe = carry
            _, vjp_fn = jax.vjp(
                lambda ff, ll: chunk_contrib(ff, ll, M_shift, c_lo_f, inp),
                f_loc, lpe)
            df_c, dl_c = vjp_fn(cots)
            return (d_f + df_c,
                    jax.tree.map(jnp.add, d_lpe, dl_c)), None

        d_f0 = jnp.zeros_like(f_loc)
        d_lp0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                             lpe)
        (d_f, d_lpe), _ = jax.lax.scan(step, (d_f0, d_lp0), xs_r)
        d_xs = jax.tree.map(jnp.zeros_like, xs_r)   # positions are data
        return (d_f, d_lpe, jnp.zeros_like(M_shift), jnp.zeros_like(c_lo_f),
                d_xs)

    aggregate.defvjp(agg_fwd, agg_bwd)

    num, Z = aggregate(f_local, lp_edge, M_g,
                       (c_lo * 1.0).astype(jnp.float32), xs)
    Z_g = jax.lax.psum(Z, data_axis)
    num_g = jax.lax.psum(num, data_axis)
    out = (num_g / jnp.maximum(Z_g, 1e-30)[:, None, :, None]
           ).reshape(N, K, Cl).astype(f_local.dtype)

    f_new = f_local + out          # w_out already applied on the edge path

    # node update on this device's node range only (slice = layer carry)
    didx = _axis_linear_index(data_axis if isinstance(data_axis, tuple)
                              else (data_axis,))
    nd = _axis_size(data_axis)
    Nl = N // nd
    fr_ = jax.lax.dynamic_slice_in_dim(f_new, didx * Nl, Nl, axis=0)

    # equivariant LN: per-degree RMS over (m, FULL C) — partial + psum
    outs = []
    for l in range(cfg.l_max + 1):
        blk = fr_[:, sh.l_slice(l), :]
        ss = jnp.sum(blk.astype(jnp.float32) ** 2, axis=(1, 2))
        ss = jax.lax.psum(ss, model_axis)
        rms = jnp.sqrt(ss / ((2 * l + 1) * C) + 1e-6)
        scale_l = jax.lax.dynamic_slice_in_dim(lp["ln"][l], c_lo, Cl, axis=0)
        outs.append((blk / rms[:, None, None].astype(blk.dtype))
                    * scale_l.astype(blk.dtype))
    fr_ = jnp.concatenate(outs, axis=1)

    # gated nonlinearity: gates need full-C f0 — partial matmul + psum
    f0 = fr_[:, 0, :]
    w_gate = jax.lax.dynamic_slice_in_dim(lp["gate"], c_lo, Cl, axis=0)
    gates_full = jax.lax.psum(f0 @ w_gate, model_axis)   # [Nl, lm*C]
    gates = jax.nn.sigmoid(gates_full).reshape(Nl, cfg.l_max, C)
    gates = jax.lax.dynamic_slice_in_dim(gates, c_lo, Cl, axis=2)
    scal = jax.nn.silu(f0)
    rest = fr_[:, 1:, :] * gnn._per_l_expand_high(gates, cfg.l_max)
    return jnp.concatenate([scal[:, None, :], rest],
                           axis=1).astype(f_slice.dtype)


def forward_sharded(params, g: GraphBatch, cfg: GNNConfig, mesh: Mesh):
    """shard_map forward returning node features [N, K, C] (C sharded)."""
    data_ax = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    model_ax = "model"
    N = g.node_feat.shape[0]

    def body(params, node_feat, src, dst, vec):
        Cl = cfg.c // mesh.shape[model_ax]
        nd = _axis_size(data_ax)
        didx = _axis_linear_index(data_ax)
        midx = jax.lax.axis_index(model_ax)
        Nl = N // nd
        feat_slice = jax.lax.dynamic_slice_in_dim(node_feat, didx * Nl, Nl,
                                                  axis=0)
        emb = feat_slice.astype(jnp.float32) @ params["embed_in"]  # [Nl, C]
        emb = jax.lax.dynamic_slice_in_dim(emb, midx * Cl, Cl, axis=1)
        f = jnp.zeros((Nl, cfg.k, Cl), jnp.dtype(cfg.dtype))
        f = f.at[:, 0, :].set(emb.astype(f.dtype))

        def layer_fn(f, lp):
            return mp_layer_local(lp, f, src[0], dst[0], vec[0], cfg,
                                  data_axis=data_ax, model_axis=model_ax,
                                  N=N), None

        lf = jax.checkpoint(layer_fn) if cfg.remat else layer_fn
        f, _ = jax.lax.scan(lf, f, params["layers"])
        return f[None]

    pspecs = _param_pspecs(cfg)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, P(), P(None, data_ax), P(None, data_ax),
                  P(None, data_ax, None)),
        out_specs=P(None, data_ax if isinstance(data_ax, str) else data_ax,
                    None, model_ax),
        check_vma=False)
    # edges get a leading singleton axis so shard_map splits dim 1 (= edges)
    f = fn(params, g.node_feat, g.edge_src[None], g.edge_dst[None],
           g.edge_vec[None])
    return f[0]


def _param_pspecs(cfg: GNNConfig):
    so2 = {"w0": P(None, "model", None)}
    for m in range(1, cfg.m_max + 1):
        so2[f"w{m}r"] = P(None, "model", None)
        so2[f"w{m}i"] = P(None, "model", None)
    layers = {"so2": so2, "rad1": P(), "rad2": P(), "wa1": P(), "wa2": P(),
              "w_out": P(), "gate": P(), "ln": P()}
    return {"embed_in": P(), "layers": layers, "ro1": P(), "ro2": P(),
            "force_w": P()}


def param_shardings(cfg: GNNConfig, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        _param_pspecs(cfg), is_leaf=lambda x: isinstance(x, P))


def loss_fn_sharded(params, g: GraphBatch, cfg: GNNConfig, mesh: Mesh):
    f = forward_sharded(params, g, cfg, mesh)
    inv = f[:, 0, :].astype(jnp.float32)          # [N, C] (C sharded)
    h = jax.nn.silu(inv @ params["ro1"])
    out = h @ params["ro2"]
    if cfg.task == "energy_force":
        energy = jax.ops.segment_sum(out[:, 0], g.graph_id,
                                     num_segments=g.n_graphs)
        forces = (f[:, 1:4, :].astype(jnp.float32)
                  @ params["force_w"])[..., 0]
        le = jnp.mean((energy - g.labels.astype(jnp.float32)) ** 2)
        lf = jnp.mean((forces - g.forces) ** 2)
        return le + 10.0 * lf, {"energy_mse": le}
    valid = g.labels >= 0
    labels = jnp.where(valid, g.labels, 0)
    lse = jax.nn.logsumexp(out, axis=-1)
    gold = jnp.take_along_axis(out, labels[:, None], axis=-1)[:, 0]
    xent = jnp.sum(jnp.where(valid, lse - gold, 0.0)) / jnp.maximum(
        valid.sum(), 1)
    return xent, {"xent": xent}
