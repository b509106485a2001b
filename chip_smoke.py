"""Chip smoke: the Sinnamon serving path on a TPU at a real corpus size.

    python chip_smoke.py                 # one chip: 2^20 SPLADE-like docs
    python chip_smoke.py --chips 4       # the sharded index on 4 chips
    python chip_smoke.py --rehearse --docs 4096   # CPU dress rehearsal

One process drives every phase through the entry points a user calls:

1. draw a SPLADE-like corpus (``synth.SPLADE_LIKE``: n=30,000, Zipf
   activations, lognormal values) from ``--seed`` with the vectorized draw,
   documents padded to 128 coordinates and queries to 64;
2. ``repro.api.open_index`` (m=64, h=1, positive-only, bf16 sketch cells,
   bf16 raw values, exact bitmap) and ``insert_many`` in batches of 2,048
   documents per chip;
3. serve 64 queries at batch 16 through ``QueryServer.query_many``, and —
   on one chip — again through a ``ServingFrontend`` from 4 client threads;
4. check on the device: the ``pallas`` program holds the compiled kernel
   (``tpu_custom_call``), its ids equal the ``reference`` backend's, every
   returned score equals the exact inner product from the device scan
   ``vecstore.exact_scores_all``, and (sharded) the state sits on distinct
   devices; recall@10 against that scan is printed, not gated.

``--chips 4`` runs only the sharded path: 4 x 2^20 documents over
``jax.devices()[:4]``, with the same per-chip state as the one-chip run.

Lines starting ``obs`` are smoke observations, not benchmarks.  The last
line is one JSON object; ``"ok"`` is true only on a TPU with every check
passed, and the exit code is 0 only then.  Without a TPU the script exits 1
at once, unless ``--rehearse`` runs the phases anyway (``"ok": false``).
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
K, KPRIME = 10, 800
N_QUERIES, QUERY_BATCH, CLIENTS = 64, 16, 4
INSERT_BATCH = 2048         # documents per insert_many, per chip
DOC_PAD, QUERY_PAD = 128, 64
SCAN_CHUNK = 8192          # slots per step of the exact device scan


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: single-device index + front door; 4: the "
                         "sharded index over jax.devices()[:4] only")
    ap.add_argument("--docs", type=int, default=None,
                    help="documents to index (default 2^20 per chip)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases without a TPU (ends ok=false)")
    return ap.parse_args(argv)


class Checks:
    """Collects every failed check, so one run reports all of them."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"check {name}: {'PASS' if ok else 'FAIL'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def obs(msg: str) -> None:
    print(f"obs {msg}", flush=True)


@functools.lru_cache(maxsize=None)
def _scan_fn():
    """jit: exact scores of every local slot for a query batch (the device
    scan ``vecstore.exact_scores_all``, chunked over slots) -> (top-k
    values, top-k local slots, exact scores at the given local slots)."""
    import jax
    import jax.numpy as jnp

    from repro.storage import vecstore

    @functools.partial(jax.jit, static_argnames=("k",))
    def scan(indices, values, active, q_dense, at_slots, *, k):
        C, P = indices.shape
        step = math.gcd(C, SCAN_CHUNK)
        store = vecstore.VecStore(
            indices=indices.reshape(C // step, step, P),
            values=values.reshape(C // step, step, P))

        def scores(st):
            return jax.vmap(lambda q: vecstore.exact_scores_all(st, q))(
                q_dense)                                   # [Bq, step]

        s = jnp.moveaxis(jax.lax.map(scores, store), 0, 1).reshape(-1, C)
        top_v, top_s = jax.lax.top_k(jnp.where(active[None], s, -jnp.inf), k)
        here = at_slots >= 0
        at = jnp.take_along_axis(s, jnp.where(here, at_slots, 0), axis=1)
        return top_v, top_s, jnp.where(here, at, 0.0), here

    return scan


def _local_pieces(state):
    """(device, first global slot, indices, values, active) per shard."""
    def by_device(arr):
        return {sh.device: sh for sh in arr.addressable_shards}

    idx = by_device(state.store.indices)
    val = by_device(state.store.values)
    act = by_device(state.active)
    out = []
    for dev, sh in idx.items():
        start = sh.index[0].start or 0
        out.append((dev, start, sh.data, val[dev].data, act[dev].data))
    return sorted(out, key=lambda p: p[1])


def exact_oracle(index, qi, qv, ret_ids, n: int, k: int):
    """Device exact scan of every slot.  Returns (exact scores at the
    returned ids [Q, K], exact top-k ids [Q, k], found [Q, K])."""
    import jax

    from repro.core import engine as eng

    state = index.state
    ids_of_slot = eng.unpack_ids64(np.asarray(state.ids))
    active = np.asarray(state.active)
    live = np.flatnonzero(active)
    slot_of = np.full(int(ids_of_slot[live].max()) + 1, -1, np.int64)
    slot_of[ids_of_slot[live]] = live
    ret_slots = np.where(ret_ids >= 0, slot_of[np.maximum(ret_ids, 0)], -1)
    pieces = _local_pieces(state)
    scan = _scan_fn()
    Q = len(qi)
    at_all = np.zeros(ret_ids.shape, np.float32)
    found = np.zeros(ret_ids.shape, bool)
    top_ids = np.zeros((Q, k), np.int64)
    for lo in range(0, Q, QUERY_BATCH):
        hi = min(lo + QUERY_BATCH, Q)
        qd = np.zeros((hi - lo, n), np.float32)
        for b in range(lo, hi):
            keep = qi[b] >= 0
            np.add.at(qd[b - lo], qi[b][keep], qv[b][keep])
        outs = []
        for dev, start, d_idx, d_val, d_act in pieces:
            C = d_idx.shape[0]
            rs = ret_slots[lo:hi]
            local = np.where((rs >= start) & (rs < start + C), rs - start, -1)
            outs.append((start, scan(d_idx, d_val, d_act,
                                     jax.device_put(qd, dev),
                                     jax.device_put(local.astype(np.int32),
                                                    dev), k=k)))
        cand_v, cand_s = [], []
        for start, (tv, ts, at, here) in outs:
            cand_v.append(np.asarray(tv))
            cand_s.append(np.asarray(ts).astype(np.int64) + start)
            at_all[lo:hi] += np.asarray(at)
            found[lo:hi] |= np.asarray(here)
        cv = np.concatenate(cand_v, axis=1)
        cs = np.concatenate(cand_s, axis=1)
        order = np.lexsort((cs, -cv), axis=1)[:, :k]      # score desc, slot asc
        top_ids[lo:hi] = ids_of_slot[np.take_along_axis(cs, order, axis=1)]
    return at_all, top_ids, found


def serve_batches(server, qi, qv):
    """query_many over QUERY_BATCH-sized batches -> (ids, scores, seconds)."""
    ids, scores, secs = [], [], []
    for lo in range(0, len(qi), QUERY_BATCH):
        t0 = time.perf_counter()
        res = server.query_many(qi[lo:lo + QUERY_BATCH],
                                qv[lo:lo + QUERY_BATCH])
        secs.append(time.perf_counter() - t0)
        ids.append(np.asarray(res.ids))
        scores.append(np.asarray(res.scores))
    return np.concatenate(ids), np.concatenate(scores), secs


def serve_front_door(server, qi, qv):
    """Every query through a ServingFrontend from CLIENTS threads.
    Returns (ids [Q, K], scores [Q, K], errors)."""
    from repro.serving.frontend import ServingFrontend

    Q = len(qi)
    ids = np.full((Q, K), -1, np.int64)
    scores = np.full((Q, K), np.nan, np.float32)
    errors = []
    with ServingFrontend(server, max_batch=QUERY_BATCH, batch_window_ms=5.0,
                         queue_depth=4 * N_QUERIES,
                         default_deadline_ms=600_000.0) as frontend:
        def client(c):
            for i in range(c, Q, CLIENTS):
                try:
                    res = frontend.submit(qi[i], qv[i]).result(timeout=600)
                    ids[i] = res.ids
                    scores[i] = res.scores
                except Exception as e:                  # noqa: BLE001
                    errors.append(f"query {i}: {e!r}")

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return ids, scores, errors


def run(args, checks: Checks, on_tpu: bool) -> None:
    import jax

    from repro.api import IndexConfig, open_index
    from repro.core import engine as eng
    from repro.data import synth
    from repro.serving import sharded
    from repro.serving.serve import QueryServer

    chips = args.chips
    devices = jax.devices()[:chips]
    docs = args.docs or (1 << 20) * chips
    ds = synth.SPLADE_LIKE
    obs(f"device platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)}")

    t0 = time.perf_counter()
    idx, val = synth.make_corpus_bulk(args.seed, ds, docs, pad=DOC_PAD)
    qi, qv = synth.make_queries_bulk(args.seed, ds, N_QUERIES, pad=QUERY_PAD)
    obs(f"draw docs={docs} seconds={time.perf_counter() - t0:.3f} "
        f"mean_doc_nnz={(idx >= 0).sum(1).mean():.2f} "
        f"mean_query_nnz={(qi >= 0).sum(1).mean():.2f}")

    config = IndexConfig(
        n=ds.n, capacity=docs, m=64, h=1, max_nnz=DOC_PAD,
        positive_only=True, cell_dtype="bf16", store_dtype="bfloat16",
        backend="pallas", seed=args.seed, shards=chips,
        # per-shard rectangle with room for the hash routing's spread, so
        # one insert_many is one dispatch
        update_block=INSERT_BATCH * 9 // 8)
    index = open_index(config)
    t0 = time.perf_counter()
    batch = INSERT_BATCH * chips
    for lo in range(0, docs, batch):
        hi = min(lo + batch, docs)
        index.insert_many(range(lo, hi), idx[lo:hi], val[lo:hi])
        # Inserts do not donate the state; sync so at most two copies live.
        jax.block_until_ready(index.state)
        if lo == 0:
            obs(f"insert first_batch_seconds={time.perf_counter() - t0:.3f}"
                " (includes compile)")
    secs = time.perf_counter() - t0
    obs(f"insert docs={index.size} seconds={secs:.3f} "
        f"docs_per_s={docs / secs:.1f}")
    checks("all documents indexed", index.size == docs,
           f"{index.size} of {docs}")
    if chips > 1:
        devs = {sh.device for x in (index.state.u, index.state.bits,
                                    index.state.store.indices)
                for sh in x.addressable_shards}
        per_dev = {sh.device: sh.data.nbytes
                   for sh in index.state.bits.addressable_shards}
        checks("state spread over distinct devices",
               len(devs) == chips and set(devs) == set(devices)
               and len(set(per_dev.values())) == 1,
               f"{len(devs)} devices, bitmap bytes/device "
               f"{sorted(per_dev.values())}")

    # The search program the pallas backend runs: compiled kernel on a TPU.
    if chips == 1:
        search = jax.jit(eng.search_batch, static_argnums=(1, 4, 5, 6),
                         static_argnames=("score_fn", "backend"))
        text = search.lower(index.state, index.spec, qi[:QUERY_BATCH],
                            qv[:QUERY_BATCH], K, KPRIME, None, None,
                            backend="pallas").as_text()
    else:
        step = sharded.make_search_step(index.mesh, index.spec, k=K,
                                        kprime_local=KPRIME, backend="pallas")
        text = step.lower(index.state, qi[:QUERY_BATCH],
                          qv[:QUERY_BATCH]).as_text()
    if on_tpu:
        checks("pallas search program runs the compiled kernel",
               "tpu_custom_call" in text)
    else:
        obs("not a TPU: the pallas backend runs its XLA twin here")

    server = QueryServer(index, k=K, kprime=KPRIME)
    p_ids, p_scores, p_secs = serve_batches(server, qi, qv)
    obs(f"pallas first_batch_seconds={p_secs[0]:.3f} (includes compile)")
    obs("pallas batch_latency_ms=" + ",".join(f"{s * 1e3:.3f}"
                                              for s in p_secs[1:]))
    ref = QueryServer(index, k=K, kprime=KPRIME, score_backend="reference")
    r_ids, r_scores, r_secs = serve_batches(ref, qi, qv)
    obs(f"reference first_batch_seconds={r_secs[0]:.3f} (includes compile)")
    obs("reference batch_latency_ms=" + ",".join(f"{s * 1e3:.3f}"
                                                 for s in r_secs[1:]))
    checks("pallas ids == reference ids", np.array_equal(p_ids, r_ids),
           f"{int((p_ids != r_ids).sum())} of {p_ids.size} differ")
    checks("pallas scores == reference scores",
           np.array_equal(p_scores, r_scores))

    if chips == 1:
        t0 = time.perf_counter()
        f_ids, f_scores, errors = serve_front_door(server, qi, qv)
        obs(f"front_door clients={CLIENTS} queries={N_QUERIES} "
            f"seconds={time.perf_counter() - t0:.3f}")
        checks("front door answered every request ok", not errors,
               "; ".join(errors[:3]))
        checks("front door answers == query_many answers",
               np.array_equal(f_ids, p_ids)
               and np.array_equal(f_scores, p_scores))

    t0 = time.perf_counter()
    exact, top_ids, found = exact_oracle(index, qi, qv, p_ids, ds.n, K)
    obs(f"exact_scan seconds={time.perf_counter() - t0:.3f}")
    diff = np.abs(exact - p_scores)[found]
    checks("every returned id is a live slot", bool(found.all()))
    checks("returned scores == exact scan scores",
           bool(found.all()) and np.array_equal(exact, p_scores),
           f"max |diff| {diff.max() if diff.size else 0.0:.3g}, "
           f"{int((diff != 0).sum())} of {diff.size} differ")
    recall = np.mean([len(set(p_ids[q]) & set(top_ids[q])) / K
                      for q in range(len(qi))])
    obs(f"recall@{K}={recall:.4f} against the exact scan (not gated)")
    for d in devices:
        stats = d.memory_stats() or {}
        obs(f"memory device={d.id} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')} "
            f"bytes_limit={stats.get('bytes_limit', 'n/a')}")


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.chips}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro import runtime
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    cache = runtime.enable_compile_cache()
    import jax

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); pass --rehearse for a CPU dress "
              f"rehearsal", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    obs(f"compile_cache dir={cache}")
    checks = Checks()
    t0 = time.perf_counter()
    try:
        run(args, checks, on_tpu)
    except Exception as e:                              # noqa: BLE001
        import traceback
        traceback.print_exc()
        checks("phases ran to the end", False, repr(e))
    obs(f"wall seconds={time.perf_counter() - t0:.3f}")
    ok = on_tpu and not checks.failed
    if checks.failed:
        print(f"chip_smoke: failed checks: {checks.failed}", file=sys.stderr)
    print(json.dumps({"ok": ok, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
