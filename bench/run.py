"""Run one benchmark cell once.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up draws the corpus and a query pool on the device from ``--seed``,
builds the index through ``repro.api.open_index`` + ``insert_many`` and
warms every query-width bucket through the front door.  The window then
offers the cell's open-loop schedule to ``ServingFrontend.submit`` for
``--seconds``; each request's latency runs from its due time to its answer.
With ``--trace 1`` a profiler trace of a steady part of the window gives the
per-layer metrics.  After the window the program's state is freed and a
plain reference recomputes a sample of the answers from the same corpus.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` a
``breakdown``, and last ``compared``: each number of the correctness
comparison beside its limit (also the last lines of standard error).

Without a TPU the run fails at once.  ``--rehearse`` runs the same phases on
the CPU at ``--docs`` documents (tests only): it prints no device metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

from bench import catalog  # noqa: E402

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = catalog.ROOT / ".jax_cache"
COLLECTIVES = r"all-gather|all-reduce|all-to-all|collective-permute|" \
              r"reduce-scatter"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", nargs="?", const="all",
                    choices=("all", "cells"),
                    help="answer with the reference computed one precision "
                         "below the configuration's: sketch cells and raw "
                         "values (all) or the sketch cells alone (cells); "
                         "calibration only")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU (tests only; no device metric)")
    ap.add_argument("--docs", type=int, default=None,
                    help="documents (rehearsal only)")
    return ap.parse_args(argv)


def say(msg: str) -> None:
    print(msg, flush=True)


class Collector:
    """Keeps every request context and batch record the front door seals
    (it stands where a flight recorder would)."""

    def __init__(self):
        self.requests = {}          # trace id -> (outcome, queue ms)
        self.batches = []           # batch records (dicts)

    def record(self, ctx):
        self.requests[ctx.trace_id] = (ctx.outcome,
                                       ctx.stage_ms().get("queue"))
        return None

    def record_batch(self, record: dict):
        self.batches.append(record)


def _percentile(values: np.ndarray, p: float) -> float:
    """Nearest-rank percentile."""
    v = np.sort(values)
    return float(v[max(0, math.ceil(p / 100.0 * len(v)) - 1)])


# -- set-up -------------------------------------------------------------------

def draw_data(cell, args, devices):
    """The corpus (kept on the devices), the query pool and the mappings."""
    import jax

    from bench import draw, reference

    cfg, ix = cell.config, cell.config["index"]
    law = draw.Law.from_config(cfg)
    n_docs = args.docs if args.rehearse and args.docs else cfg["n_docs"]
    chunk = min(cfg["insert_batch"], n_docs)
    map_seed = args.seed & 0xFFFFFFFF
    chunks = draw.corpus_chunks(args.seed, law, n_docs, ix["doc_pad"], chunk,
                                devices)
    jax.block_until_ready([c[1:] for c in chunks])
    pool = draw.query_pool(args.seed, law, cell.traffic["query_pool"],
                           ix["query_pad"])
    doc_nnz = sum(int(jax.numpy.sum(c[1] >= 0)) for c in chunks) / n_docs
    say(f"drawn docs={n_docs} nnz_mean={doc_nnz:.3f} "
        f"queries={len(pool[0])} nnz_mean={(pool[0] >= 0).sum(1).mean():.3f} "
        f"nnz_max={int((pool[0] >= 0).sum(1).max())}")
    return types.SimpleNamespace(
        law=law, n_docs=n_docs, chunk=chunk, chunks=chunks, pool=pool,
        map_seed=map_seed,
        maps=reference.mappings(map_seed, law.n, ix["m"], ix["h"]))


def build(cell, args, devices, setup: dict):
    """Corpus, query pool, index, server and warmed front door."""
    import jax

    from repro.api import IndexConfig, open_index
    from repro.obs.metrics import MetricsRegistry
    from repro.serving.frontend import ServingFrontend
    from repro.serving.serve import QueryServer

    tr, ix = cell.traffic, cell.config["index"]
    t = time.perf_counter()
    data = draw_data(cell, args, devices)
    setup["draw_s"] = time.perf_counter() - t
    law, n_docs, chunk, chunks = data.law, data.n_docs, data.chunk, data.chunks

    t = time.perf_counter()
    chips = len(devices)
    config = IndexConfig(
        n=law.n, capacity=n_docs, m=ix["m"], h=ix["h"],
        max_nnz=ix["doc_pad"], positive_only=ix["positive_only"],
        cell_dtype=ix["cell_dtype"], store_dtype=ix["store_dtype"],
        backend=ix["backend"], seed=data.map_seed, shards=chips,
        # per-shard rectangle with room for the hash routing's spread, so
        # one insert_many of a chunk is one dispatch
        update_block=max(32, chunk // chips * 9 // 8))
    index = open_index(config)
    for lo, idx, val in chunks:
        index.insert_many(range(lo, lo + idx.shape[0]), idx, val)
        jax.block_until_ready(index.state)
    if index.size != n_docs:
        raise RuntimeError(f"indexed {index.size} of {n_docs} documents")
    setup["insert_s"] = time.perf_counter() - t

    t = time.perf_counter()
    registry = MetricsRegistry()
    collector = Collector()
    server = QueryServer(index, k=ix["k"], kprime=ix["kprime"],
                         registry=registry)
    frontend = ServingFrontend(
        server, max_batch=tr["max_batch"],
        batch_window_ms=tr["batch_window_ms"], queue_depth=tr["queue_depth"],
        default_deadline_ms=tr["deadline_ms"], query_pad=tr["query_bucket"],
        registry=registry, recorder=collector)
    bucket = tr["query_bucket"]
    top = -(-ix["query_pad"] // bucket) * bucket
    for width in range(bucket, top + 1, bucket):
        qi = (np.arange(width) * (law.n // width)).astype(np.int32)
        frontend.submit(qi, np.ones(width, np.float32),
                        tenant=tr["tenant"]).result(timeout=1200)
    setup["warmup_s"] = time.perf_counter() - t
    return types.SimpleNamespace(
        data=data, pool=data.pool, maps=data.maps, index=index,
        server=server, frontend=frontend, registry=registry,
        collector=collector, capacity_per_chip=config.local_capacity)


# -- the window ---------------------------------------------------------------

class _Tracer(threading.Thread):
    """Starts and stops the profiler around a steady part of the window."""

    def __init__(self, t0: float, seconds: float, traffic: dict, log_dir):
        super().__init__(name="bench-tracer", daemon=True)
        self.start_at = t0 + traffic["trace_start_share"] * seconds
        self.length = min(traffic["trace_seconds"], 0.5 * seconds)
        self.log_dir = log_dir
        self.anchor = None          # (unix ns, perf s) just before start
        self.error = None

    def run(self):
        import jax

        try:
            time.sleep(max(0.0, self.start_at - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self.anchor = (time.time_ns(), time.perf_counter())
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            time.sleep(self.length)
            jax.profiler.stop_trace()
        except Exception as e:                      # noqa: BLE001
            self.error = e


def window(cell, args, sut, rate: float, trace_dir):
    """Offer the schedule; returns per-request records."""
    import jax

    from bench import schedule
    from repro.serving.frontend import Rejected

    tr = cell.traffic
    offsets = schedule.arrivals(args.seed, rate, args.seconds, tr)
    n = len(offsets)
    pool_idx, pool_val = sut.pool
    pool_of = schedule.pool_order(n, len(pool_idx))
    queries = [(pool_idx[p][pool_idx[p] >= 0], pool_val[p][pool_idx[p] >= 0])
               for p in range(len(pool_idx))]
    done = np.full(n, np.nan)
    submitted = np.full(n, np.nan)
    futures = [None] * n
    compiles = []

    def on_event(name, *_args, **_kw):
        # A trace or lowering in the window is a shape that set-up did not
        # warm, even where the compiled program then comes from the cache.
        if name in (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT):
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    hist = sut.registry.histogram("repro_frontend_batch_size")
    batch0 = (hist.sum, hist.count)
    first_batch = len(sut.collector.batches)
    gc_ms = []                  # (generation, ms) of each collection

    def on_gc(phase, info):
        if phase == "start":
            on_gc.t = time.perf_counter()
        else:
            gc_ms.append((info["generation"],
                          (time.perf_counter() - on_gc.t) * 1e3))

    gc.callbacks.append(on_gc)

    def mark(i):
        return lambda _f: done.__setitem__(i, time.perf_counter())

    t0 = time.perf_counter() + 0.05
    tracer = None
    if trace_dir is not None:
        tracer = _Tracer(t0, args.seconds, tr, trace_dir)
        tracer.start()
    for i in range(n):
        due = t0 + offsets[i]
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        qi, qv = queries[pool_of[i]]
        submitted[i] = time.perf_counter()
        try:
            fut = sut.frontend.submit(qi, qv, tenant=tr["tenant"])
        except Rejected:
            continue
        futures[i] = fut
        fut.add_done_callback(mark(i))
    t_end = t0 + args.seconds
    give_up = t_end + tr["answer_wait_s"]
    for fut in futures:
        if fut is None:
            continue
        try:
            fut.result(timeout=max(0.0, give_up - time.perf_counter()))
        except Exception:                           # noqa: BLE001
            pass
    batch1 = (hist.sum, hist.count)
    jax.monitoring.unregister_event_duration_listener(on_event)
    gc.callbacks.remove(on_gc)
    if tracer is not None:
        tracer.join(timeout=120)
        if tracer.error is not None:
            raise RuntimeError(f"the profiler failed: {tracer.error!r}")
    ok = np.array([f is not None and f.done() and f.exception() is None
                   for f in futures])
    results = [f.result() if good else None for f, good in zip(futures, ok)]
    return types.SimpleNamespace(
        n=n, t0=t0, t_end=t_end, due=t0 + offsets, submitted=submitted,
        done=done, ok=ok, results=results, pool_of=pool_of, queries=queries,
        batch_sizes=(batch1[0] - batch0[0], batch1[1] - batch0[1]),
        first_batch=first_batch, compiles_in_window=len(compiles),
        gc_ms=gc_ms, tracer=tracer)


# -- metrics ------------------------------------------------------------------

def stalls(sut, win) -> dict:
    """Where the window's worst latency came from: the generator's largest
    lateness, the slowest device stage of a dispatch of the window, the
    longest gap between the starts of consecutive dispatches, and the
    longest pause of Python's garbage collector."""
    late = (win.submitted - win.due) * 1e3
    batches = sorted(sut.collector.batches[win.first_batch:],
                     key=lambda b: b["ts"])
    device = [st["ms"] for b in batches for st in b["stages"]
              if st["stage"] == "device"]
    starts = [b["ts"] for b in batches]
    lat = np.where(win.ok, (win.done - win.due) * 1e3, np.inf)
    worst = int(np.argmax(lat))
    return {
        "generator_late_max_ms": float(np.nanmax(late)),
        "dispatch_device_max_ms": max(device, default=0.0),
        "dispatch_start_gap_max_ms": 1e3 * max(
            (b - a for a, b in zip(starts, starts[1:])), default=0.0),
        "worst_latency_ms": float(lat[worst]),
        "worst_due_s": float(win.due[worst] - win.t0),
        "gc_max_ms": max((ms for _, ms in win.gc_ms), default=0.0),
        "gc_full_collections": sum(g == 2 for g, _ in win.gc_ms)}


def end_to_end(win, seconds: float, setup_s: float) -> dict:
    lat = np.where(win.ok, (win.done - win.due) * 1e3, np.inf)
    answered = int((win.ok & (win.done <= win.t_end)).sum())
    return {
        "p50_ms": {"value": _percentile(lat, 50), "unit": "ms"},
        "p99_ms": {"value": _percentile(lat, 99), "unit": "ms"},
        "qps": {"value": answered / seconds, "unit": "queries/s"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }


def layer_context(cell, sut, win, trace, peak, capacity: int):
    """What the per-layer readers read."""
    from bench import roofline

    ix = cell.config["index"]
    by_trace_id = {}
    for i, res in enumerate(win.results):
        if res is not None:
            by_trace_id[res.trace_id] = i
    queue_ms = [q for tid, (outcome, q) in sut.collector.requests.items()
                if tid in by_trace_id and outcome == "ok" and q is not None]
    dispatch_bytes = []
    pending = None
    if trace is not None:
        anchor_unix, anchor_perf = win.tracer.anchor
        start_unix = trace["start_unix_ns"]
        lo_s = start_unix / 1e9
        hi_s = lo_s + trace["window_ns"][1] / 1e9
        cell_bytes = np.dtype(ix["cell_dtype"]).itemsize
        for rec in sut.collector.batches:
            if not lo_s <= rec["ts"] <= hi_s:
                continue
            members = [by_trace_id[t] for t in rec.get("trace_ids", ())
                       if t in by_trace_id]
            if not members:
                continue
            qi = [win.queries[win.pool_of[i]][0] for i in members]
            qv = [win.queries[win.pool_of[i]][1] for i in members]
            width = max(len(q) for q in qi)
            pad_i = np.full((len(qi), width), -1, np.int32)
            pad_v = np.zeros((len(qi), width), np.float32)
            for r, (a, b) in enumerate(zip(qi, qv)):
                pad_i[r, :len(a)] = a
                pad_v[r, :len(b)] = b
            dispatch_bytes.append(roofline.scan_bytes(
                pad_i, pad_v, sut.maps, capacity, cell_bytes,
                signed=not ix["positive_only"]))

        def to_trace_ns(perf_s):
            return (anchor_unix + (perf_s - anchor_perf) * 1e9) - start_unix

        sub_ns = to_trace_ns(win.submitted)
        queued = np.full(win.n, np.nan)
        for i, res in enumerate(win.results):
            if res is not None:
                q = sut.collector.requests.get(res.trace_id, (None, None))[1]
                queued[i] = np.nan if q is None else q
        disp_ns = sub_ns + queued * 1e6

        def pending(s, e):
            return bool(np.any((sub_ns < e) & (disp_ns > s)))

    return types.SimpleNamespace(
        trace=trace, chips=cell.chips, max_batch=cell.traffic["max_batch"],
        queue_ms=queue_ms, batch_sizes=win.batch_sizes,
        dispatch_bytes=dispatch_bytes, peak=peak, pending=pending,
        collectives=COLLECTIVES)


def per_layer(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        value = catalog.metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(ctx) -> dict:
    from bench import xtrace

    trace = ctx.trace
    planes = xtrace.device_planes(trace)
    totals = {}
    for p in planes:
        for name, _s, d in xtrace.line_events(p, xtrace.OPS_LINE):
            key = xtrace.op_label(name)
            totals[key] = totals.get(key, 0) + d
    chips = max(1, len(planes))
    ops = sorted(((name, t / chips / 1e9) for name, t in totals.items()),
                 key=lambda x: -x[1])[:10]
    gaps = []
    if planes:
        lo, hi = trace["window_ns"]
        ivs = [(e[1], e[2]) for e in xtrace.line_events(planes[0],
                                                         xtrace.OPS_LINE)]
        gaps = xtrace.label_gaps(trace, xtrace.idle_gaps(ivs, lo, hi),
                                 pending=ctx.pending)
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": gaps}


# -- correctness --------------------------------------------------------------

LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn",
         "float16": "float8_e4m3fn"}


def compare(cell, args, data, used, collect) -> tuple:
    """(correct, compared, reasons): answers against the reference on a
    sample, drawn from the seed, of the pool queries ``used``.
    ``collect(sample, kw)`` returns ``{pool index: [(ids, scores), ...]}``
    with one entry per request compared."""
    from bench import reference

    ix = cell.config["index"]
    rng = np.random.Generator(np.random.Philox(key=args.seed ^ 0xC0FFEE))
    sample = np.sort(rng.choice(used, size=min(cell.traffic["sample_queries"],
                                               len(used)), replace=False))
    pool_idx, pool_val = data.pool
    kw = dict(n=data.law.n, m=ix["m"], h=ix["h"], map_seed=data.map_seed,
              signed=not ix["positive_only"], k=ix["k"], kprime=ix["kprime"],
              shards=cell.chips)
    ref_ids, _, kth_ub = reference.search(
        data.chunks, pool_idx[sample], pool_val[sample],
        cell_dtype=ix["cell_dtype"], store_dtype=ix["store_dtype"], **kw)
    slot = {int(p): j for j, p in enumerate(sample)}
    answers = collect(sample, kw)
    reasons = []
    want = set()
    for p, outs in answers.items():
        for ids, _ in outs:
            want.update(int(d) for d in ids)
        want.update(int(d) for d in ref_ids[slot[p]])
    rows = reference.doc_rows(data.chunks,
                              [d for d in want if 0 <= d < data.n_docs])
    ids_missed, score_gap, compared_requests = 0, 0.0, 0
    notes = []
    for p, outs in answers.items():
        j = slot[p]
        qi, qv = pool_idx[p], pool_val[p]
        exact = {}
        for d in ref_ids[j]:
            exact[int(d)] = reference.exact_score(rows[int(d)], qi, qv,
                                                  ix["store_dtype"])
        scale = max(abs(exact[int(ref_ids[j][0])]), 1e-30)
        for ids, scores in outs:
            compared_requests += 1
            missed = set(int(d) for d in ref_ids[j]) - set(int(d) for d in ids)
            ids_missed += len(missed)
            for d in sorted(missed)[:max(0, 12 - len(notes))]:
                # Enough to tell a tie at rank k from a candidate lost at
                # the k'-th upper bound.
                ub = reference.upper_bound(
                    rows[d], qi, qv, data.maps, ix["m"], ix["cell_dtype"],
                    not ix["positive_only"])
                notes.append(
                    f"missed pool={p} id={d} "
                    f"ref_rank={int(np.nonzero(ref_ids[j] == d)[0][0])} "
                    f"exact={exact[d]!r} answer_kth={float(scores[-1])!r} "
                    f"ub={ub!r} kth_ub={kth_ub[j].tolist()!r}")
            for d, s in zip(ids, scores):
                d = int(d)
                if d not in rows:
                    score_gap = max(score_gap, 1.0)
                    continue
                if d not in exact:
                    exact[d] = reference.exact_score(rows[d], qi, qv,
                                                     ix["store_dtype"])
                score_gap = max(score_gap, abs(float(s) - exact[d]) / scale)
    for note in notes:
        print(f"bench: {note}", file=sys.stderr)
    limits = cell.params["limits"]
    compared = {
        "ids_missed": {"value": ids_missed, "limit": limits["ids_missed"]},
        "score_gap": {"value": score_gap, "limit": limits["score_gap"]},
        "requests_compared": {"value": compared_requests,
                              "limit": limits["requests_compared_min"]},
    }
    if ids_missed > limits["ids_missed"]:
        reasons.append("reference ids missing from the answers")
    if score_gap > limits["score_gap"]:
        reasons.append("returned scores differ from the exact scores")
    if compared_requests < limits["requests_compared_min"]:
        reasons.append("too few answers compared")
    return not reasons, compared, reasons


# -- main ---------------------------------------------------------------------

def window_answers(win):
    """``collect`` for :func:`compare`: the window's own answers."""
    def collect(sample, _kw):
        wanted = set(int(p) for p in sample)
        answers = {}
        for i in range(win.n):
            p = int(win.pool_of[i])
            if p in wanted and win.ok[i]:
                res = win.results[i]
                answers.setdefault(p, []).append(
                    (np.asarray(res.ids), np.asarray(res.scores)))
        return answers
    return collect


def control_answers(cell, data, lower: str):
    """``collect`` for :func:`compare`: the reference itself, computed one
    precision below the configuration's in the sketch cells and, with
    ``lower="all"``, in the raw values too, in the program's place."""
    from bench import reference

    ix = cell.config["index"]
    store = LOWER[ix["store_dtype"]] if lower == "all" else ix["store_dtype"]

    def collect(sample, kw):
        pool_idx, pool_val = data.pool
        ids, scores, _ = reference.search(
            data.chunks, pool_idx[sample], pool_val[sample],
            cell_dtype=LOWER[ix["cell_dtype"]],
            store_dtype=store, **kw)
        return {int(p): [(ids[j], scores[j])] for j, p in enumerate(sample)}
    return collect


def report(result: dict, compared: dict, reasons: list) -> dict:
    for r in reasons:
        print(f"bench: not correct: {r}", file=sys.stderr)
    result["correct"] = not reasons
    result["compared"] = compared
    for name, c in compared.items():
        print(f"compare {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    return result


def device_info(devices) -> dict:
    memory = 0
    for d in devices:
        stats = d.memory_stats() or {}
        memory = max(memory, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory}


def run_control(cell, args, devices) -> dict:
    """The control: no program, the lower-precision reference answers the
    pool queries a window at the cell's rate would use."""
    data = draw_data(cell, args, devices)
    n = int(round(cell.params["rate_qps"] * args.seconds))
    used = np.unique(np.arange(n) % len(data.pool[0]))
    correct, compared, reasons = compare(
        cell, args, data, used, control_answers(cell, data, args.control))
    result = {"correct": False, "attempted": int(len(used)), "failed": 0,
              "metrics": {}, "device": device_info(devices)}
    return report(result, compared, reasons)


def run(cell, args, devices, peak) -> dict:
    import jax

    if args.control:
        return run_control(cell, args, devices)
    setup = {}
    sut = build(cell, args, devices, setup)
    setup_s = time.perf_counter() - T_PROCESS
    say("setup " + " ".join(f"{k}={v:.3f}" for k, v in setup.items())
        + f" total_s={setup_s:.3f}")
    rate = cell.params["rate_qps"]
    trace_dir = None
    if args.trace and not args.rehearse:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    win = window(cell, args, sut, rate, trace_dir)
    late = (win.submitted - win.due) * 1e3
    late = late[np.isfinite(late)]
    say(f"generator requests={win.n} rate_qps={rate} lateness_ms "
        f"p50={_percentile(late, 50):.4f} p99={_percentile(late, 99):.4f} "
        f"max={late.max():.4f}")
    say(f"window traces_or_compiles={win.compiles_in_window} "
        f"answered_ok={int(win.ok.sum())} of {win.n}")
    say("stalls " + " ".join(f"{k}={v:.3f}"
                             for k, v in stalls(sut, win).items()))
    device = device_info(devices)
    trace = None
    if trace_dir is not None:
        from bench import xtrace
        trace = xtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = layer_context(cell, sut, win, trace, peak, sut.capacity_per_chip)
    result = {"correct": False, "attempted": int(win.n),
              "failed": int((~win.ok).sum())}
    if args.rehearse:
        metrics = {}
        for name in ("frontend.queue_ms", "frontend.batch_fill"):
            value = catalog.metric_reader(name)(ctx)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": "host"}
    elif args.trace:
        from bench import xtrace
        metrics = per_layer(cell, ctx)
        busy = xtrace.busy_ns(trace)
        device["busy_s"] = float(np.mean(busy)) / 1e9 if busy else 0.0
        device["window_s"] = trace["window_ns"][1] / 1e9
        result["breakdown"] = breakdown(ctx)
    else:
        metrics = end_to_end(win, args.seconds, setup_s)
    result["metrics"] = metrics
    result["device"] = device

    sut.frontend.close()
    data = sut.data
    del sut, ctx
    gc.collect()
    t = time.perf_counter()
    correct, compared, reasons = compare(cell, args, data,
                                         np.unique(win.pool_of),
                                         window_answers(win))
    say(f"reference seconds={time.perf_counter() - t:.3f}")
    if not win.ok.all():
        reasons.insert(0, f"{int((~win.ok).sum())} of {win.n} requests got "
                          f"no answer")
    jax.clear_caches()
    return report(result, compared, reasons)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = catalog.load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={cell.chips}")
    sys.path.insert(0, str(catalog.ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the program (src/repro) is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    import jax

    if not args.rehearse:
        if CACHE_ENV not in os.environ:
            jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    peak = None
    if not args.rehearse:
        if devices[0].platform != "tpu":
            print(f"bench: JAX found no TPU (platform "
                  f"{devices[0].platform!r}); this benchmark runs only on "
                  f"the chip", file=sys.stderr)
            return 1
        try:
            peak = catalog.peaks(devices[0].device_kind)
        except KeyError as e:
            print(f"bench: {e}", file=sys.stderr)
            return 1
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    result = run(cell, args, devices[:cell.chips], peak)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
