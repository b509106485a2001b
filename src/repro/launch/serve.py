"""Serving launcher for the retrieval engine: build (or recover) an index,
then serve batched queries with the anytime budget.

    PYTHONPATH=src python -m repro.launch.serve --docs 10000 --queries 64 \
        [--budget 16] [--kprime 800] [--index-buckets 2048] [--shards 4] \
        [--sketch-kind full|lite] [--value-dtype f32|bf16|f8] \
        [--auto-tune --tune-memory-mb 8 --recall-floor 0.9] \
        [--score-backend pallas|grouped|reference] \
        [--wal runs/wal --snapshot-dir runs/snap --snapshot-every 5000 \
         --compact-threshold 0.5]

``--shards N`` (N > 1) serves through the mesh-sharded streaming index
(corpus sharded over 'model'), using the batched `query_many` path: on an
accelerator over the first N chips; on the CPU platform over N forced host
devices.  The default is the single-device index.

``--docs`` at or above ``BULK_DRAW_DOCS`` draws the corpus with the
vectorized ``synth.make_corpus_bulk`` (same law, different numbers); the
recall oracle is the batched exact scan ``repro.eval.recall.exact_topk_ids``.

``--sketch-kind lite`` serves the §3.3 upper-bound-only half sketch and
``--value-dtype`` picks the quantized sketch-cell storage — the paper's
memory/accuracy levers (see docs/levers.md and ``repro.eval``).
``--auto-tune`` ignores ``--m/--sketch-kind/--value-dtype`` and instead
grid-searches those levers on a corpus sample (``repro.eval.tune``) for the
cheapest configuration that fits ``--tune-memory-mb`` of index memory at
``--docs`` scale while holding ``--recall-floor`` on the sample.

``--wal DIR`` makes the index durable: every insert/delete is logged to the
write-ahead log before it is applied, and on startup the launcher *recovers*
(latest snapshot from ``--snapshot-dir`` + WAL tail replay) instead of
re-indexing — so a second run with the same dirs skips the build entirely.
``--snapshot-every N`` snapshots after every N logged ops;
``--compact-threshold X`` rebuilds recycled sketch columns whenever the max
per-slot overestimate exceeds X (see repro.persist).

Observability (see docs/observability.md):

* ``--metrics-port P`` serves the process-global metrics registry over
  HTTP: ``/metrics`` (Prometheus text), ``/metrics.json`` (structured
  snapshot), ``/healthz`` (liveness), ``/readyz`` (readiness: 503 until
  the index is built/recovered), plus the ``/debug/*`` surfaces below.
* ``--event-log FILE`` appends one JSON line per query / maintenance op
  (each query batch with its trace stages: ``device``, ``launch``,
  ``fetch``); ``--event-log-max-bytes B`` rotates the file at B bytes
  keeping ``--event-log-keep`` segments.
* ``--recorder-capacity N`` sizes the tail-sampled flight recorder ring
  (``/debug/requests``, ``/debug/trace/<id>``, ``/debug/batches``);
  ``--record-sample R`` head-samples fast OK requests at rate R (errors,
  rejections, deadline misses, and the slowest decile are always kept).
* ``--slo-latency-ms`` / ``--slo-target`` / ``--slo-availability`` declare
  the serving SLOs; a background monitor publishes ``repro_slo_*``
  burn-rate gauges over fast/slow windows (``--slo-fast-window-s`` /
  ``--slo-slow-window-s``), serves ``/debug/slo``, and WARNs to the event
  log on sustained burn.
* ``--profile-dir DIR`` captures a ``jax.profiler`` trace of the query
  loop for kernel-level inspection, and mounts ``/debug/profile?seconds=N``
  for on-demand traces while serving.
* ``--hold-seconds S`` keeps the process (and the metrics endpoint) alive
  after the query loop — for scrape-based smoke tests and demos.

Serving front door (see docs/serving.md):

* ``--serve-port P`` boots the async HTTP/JSON front door
  (``repro.serving.frontend``) on this port after the build/recovery —
  ``POST /v1/query`` plus the standard ``/metrics`` family on the same
  port — and holds for ``--hold-seconds``.
* ``--max-batch B`` / ``--batch-window-ms W`` — dynamic batching: coalesce
  queries for up to W ms or until B are waiting, then issue ONE fused
  ``query_many`` dispatch.
* ``--queue-depth D`` — bounded admission queue; requests beyond D are
  rejected with 429 + Retry-After (explicit backpressure).
* ``--deadline-ms T`` — default per-request deadline; queries whose budget
  elapses while queued are dropped and counted, not served late.

Robustness (see docs/robustness.md):

* ``--failpoints SPEC`` arms the deterministic fault-injection registry
  (``site=mode[:arg][:prob]``, comma-separated — e.g.
  ``wal.fsync=error:0.02,device.dispatch=stall:250ms``) for chaos drills;
  ``--failpoint-seed`` fixes the injection schedule.  Equivalent to the
  ``REPRO_FAILPOINTS`` / ``REPRO_FAILPOINT_SEED`` environment variables.
* ``--degrade`` enables the front door's graceful-degradation ladder:
  driven by SLO fast-burn and queue depth, L1 shrinks the rerank budget,
  L2 serves sketch-only upper-bound scores (``degraded: true`` in the
  response), L3 sheds the lowest-priority tenants with 429.  Thresholds
  via ``--degrade-enter-burn`` / ``--degrade-exit-burn`` /
  ``--degrade-enter-queue-frac`` / ``--degrade-exit-queue-frac`` /
  ``--degrade-dwell-ticks`` (hysteresis).
* ``--watchdog-timeout-s S`` fails in-flight front-door queries with 504
  when one fused dispatch is stuck on the device longer than S seconds.

Index construction goes through the ``repro.api`` facade: the flags here
are argparse spellings of :class:`repro.api.IndexConfig` (and the ``--wal``
family of :class:`repro.api.DurabilityConfig`), and the launcher calls
``open_index`` exactly like library code should.
"""

from __future__ import annotations

import argparse
import os

#: --docs at or above this draw the corpus with the vectorized bulk draw.
BULK_DRAW_DOCS = 1 << 16


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=10_000)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--kprime", type=int, default=800)
    ap.add_argument("--budget", type=int, default=None)
    ap.add_argument("--m", type=int, default=60)
    ap.add_argument("--h", type=int, default=1)
    ap.add_argument("--index-buckets", type=int, default=None)
    ap.add_argument("--sketch-kind", default="full",
                    choices=["full", "lite"],
                    help="lite = upper-bound-only half sketch (§3.3): "
                         "halves sketch memory; on signed collections "
                         "recall degrades (measure with repro.eval)")
    ap.add_argument("--value-dtype", default="bf16",
                    choices=["f32", "bf16", "f8"],
                    help="sketch cell storage dtype (quantized cells are "
                         "directed-rounded and dequantized in-kernel)")
    ap.add_argument("--auto-tune", action="store_true",
                    help="pick m/sketch-kind/value-dtype with the "
                         "repro.eval.tune grid search instead of the flags")
    ap.add_argument("--tune-memory-mb", type=float, default=8.0, metavar="MB",
                    help="auto-tune: index memory budget (sketch + inverted "
                         "index) at --docs scale")
    ap.add_argument("--recall-floor", type=float, default=0.9, metavar="R",
                    help="auto-tune: minimum recall@k on the tuning sample")
    ap.add_argument("--score-backend", default=None,
                    choices=["reference", "grouped", "pallas"],
                    help="scoring backend for the query hot path "
                         "(default: REPRO_SCORE_BACKEND env or 'pallas', "
                         "the fused tiled-top-k kernel)")
    ap.add_argument("--shards", type=int, default=1,
                    help=">1: sharded streaming index over N devices: the "
                         "first N chips on an accelerator; on the CPU "
                         "platform N forced host devices")
    ap.add_argument("--device-budget-mb", type=float, default=None,
                    metavar="MB",
                    help="per-device byte budget for raw vector rows; "
                         "enables the hot/cold tiered store (sketches stay "
                         "resident, rows page between a device chunk cache "
                         "and host RAM — docs/tiering.md); results are "
                         "bit-identical to the resident index")
    ap.add_argument("--tier-chunk-slots", type=int, default=256, metavar="S",
                    help="tiered store paging granularity in slots per chunk")
    ap.add_argument("--query-batch", type=int, default=16)
    ap.add_argument("--dataset", default="splade_like")
    ap.add_argument("--wal", default=None, metavar="DIR",
                    help="write-ahead-log dir; enables the durable index")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="snapshot dir (recovery base + periodic snapshots)")
    ap.add_argument("--snapshot-every", type=int, default=None, metavar="N",
                    help="snapshot after every N logged ops")
    ap.add_argument("--compact-threshold", type=float, default=None,
                    metavar="X", help="compact when max sketch drift > X")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="P",
                    help="serve /metrics (Prometheus text) + /metrics.json "
                         "+ /healthz on this port (0 = OS-assigned)")
    ap.add_argument("--event-log", default=None, metavar="FILE",
                    help="append one JSON line per query/maintenance op")
    ap.add_argument("--event-log-max-bytes", type=int, default=None,
                    metavar="B", help="rotate the event log at B bytes "
                                      "(default: never)")
    ap.add_argument("--event-log-keep", type=int, default=3, metavar="N",
                    help="rotated event-log segments to keep")
    ap.add_argument("--recorder-capacity", type=int, default=512,
                    metavar="N", help="flight-recorder ring size "
                                      "(0 disables the recorder)")
    ap.add_argument("--record-sample", type=float, default=0.05, metavar="R",
                    help="head-sampling rate for fast OK requests "
                         "(failures and the slow tail are always kept)")
    ap.add_argument("--slo-latency-ms", type=float, default=100.0,
                    metavar="MS", help="latency SLO bound")
    ap.add_argument("--slo-target", type=float, default=0.99, metavar="F",
                    help="fraction of requests that must meet the latency "
                         "bound")
    ap.add_argument("--slo-availability", type=float, default=0.999,
                    metavar="F", help="fraction of requests that must not "
                                      "be rejected/expired/errored")
    ap.add_argument("--slo-fast-window-s", type=float, default=300.0,
                    metavar="S", help="fast burn-rate window")
    ap.add_argument("--slo-slow-window-s", type=float, default=3600.0,
                    metavar="S", help="slow burn-rate window")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the query loop "
                         "(the launcher fails if the trace cannot start)")
    ap.add_argument("--hold-seconds", type=float, default=0.0, metavar="S",
                    help="keep the process (and metrics endpoint) alive "
                         "this long after the query loop")
    ap.add_argument("--serve-port", type=int, default=None, metavar="P",
                    help="boot the HTTP/JSON front door (POST /v1/query + "
                         "/metrics family) on this port (0 = OS-assigned) "
                         "and hold for --hold-seconds")
    ap.add_argument("--max-batch", type=int, default=16, metavar="B",
                    help="front door: max queries coalesced into one fused "
                         "dispatch")
    ap.add_argument("--batch-window-ms", type=float, default=2.0,
                    metavar="W", help="front door: max coalesce wait after "
                                      "the first queued query")
    ap.add_argument("--queue-depth", type=int, default=128, metavar="D",
                    help="front door: bounded admission queue; beyond this "
                         "requests get 429 + Retry-After")
    ap.add_argument("--deadline-ms", type=float, default=1000.0, metavar="T",
                    help="front door: default per-request deadline; "
                         "requests expiring in-queue are dropped + counted")
    ap.add_argument("--failpoints", default=None, metavar="SPEC",
                    help="arm fault-injection failpoints: comma-separated "
                         "site=mode[:arg][:prob] (docs/robustness.md); "
                         "equivalent to REPRO_FAILPOINTS")
    ap.add_argument("--failpoint-seed", type=int, default=0, metavar="N",
                    help="seed for the failpoint injection schedule")
    ap.add_argument("--degrade", action="store_true",
                    help="front door: enable the graceful-degradation "
                         "ladder (L1 shrink rerank, L2 sketch-only, "
                         "L3 shed lowest-priority tenants)")
    ap.add_argument("--degrade-enter-burn", type=float, default=4.0,
                    metavar="X", help="ladder: escalate when SLO fast-burn "
                                      ">= X")
    ap.add_argument("--degrade-exit-burn", type=float, default=1.0,
                    metavar="X", help="ladder: calm requires fast-burn <= X")
    ap.add_argument("--degrade-enter-queue-frac", type=float, default=0.75,
                    metavar="F", help="ladder: escalate when queue fill "
                                      "fraction >= F")
    ap.add_argument("--degrade-exit-queue-frac", type=float, default=0.25,
                    metavar="F", help="ladder: calm requires queue fill "
                                      "fraction <= F")
    ap.add_argument("--degrade-dwell-ticks", type=int, default=4,
                    metavar="N", help="ladder: consecutive calm ticks "
                                      "before de-escalating one level")
    ap.add_argument("--watchdog-timeout-s", type=float, default=None,
                    metavar="S", help="front door: fail in-flight queries "
                                      "with 504 when a fused dispatch is "
                                      "stuck longer than S seconds")
    args = ap.parse_args(argv)
    if args.wal is None and (args.snapshot_dir is not None
                             or args.snapshot_every is not None
                             or args.compact_threshold is not None):
        ap.error("--snapshot-dir/--snapshot-every/--compact-threshold "
                 "require --wal (durability is WAL-based)")
    if args.snapshot_every is not None and args.snapshot_dir is None:
        ap.error("--snapshot-every requires --snapshot-dir "
                 "(periodic snapshots need somewhere to go)")
    if (args.device_budget_mb is not None and args.wal is not None
            and args.shards > 1):
        ap.error("--device-budget-mb with both --wal and --shards > 1 is "
                 "not supported yet; drop one of the three")
    if args.auto_tune and args.wal is not None:
        ap.error("--auto-tune is incompatible with --wal: durable runs pin "
                 "their spec to the WAL dir; tune first, then launch with "
                 "the chosen flags")
    return args


def _check_launch_params(args) -> None:
    """Pin the corpus/spec flags of a durable run to its WAL directory."""
    import json
    import sys

    params = {"dataset": args.dataset, "docs": args.docs,
              "draw": "bulk" if args.docs >= BULK_DRAW_DOCS else "loop",
              "m": args.m,
              "h": args.h, "index_buckets": args.index_buckets,
              "sketch_kind": args.sketch_kind,
              "value_dtype": args.value_dtype,
              "shards": args.shards}
    os.makedirs(args.wal, exist_ok=True)
    pfile = os.path.join(args.wal, "launch_params.json")
    if os.path.exists(pfile):
        with open(pfile) as f:
            prev = json.load(f)
        changed = {k: (prev.get(k), v) for k, v in params.items()
                   if prev.get(k) != v and k != "shards"}
        if changed:
            sys.exit(f"refusing to recover from {args.wal}: "
                     f"{', '.join(f'--{k} was {a!r}, now {b!r}' for k, (a, b) in changed.items())} "
                     f"— the synthetic corpus/spec would no longer match the "
                     f"indexed vectors; rerun with the original flags or "
                     f"fresh --wal/--snapshot-dir directories")
        if prev != params:       # only the (elastic) shard count changed
            with open(pfile, "w") as f:
                json.dump(params, f)
    else:
        with open(pfile, "w") as f:
            json.dump(params, f)


def main():
    args = parse_args()
    if args.shards > 1:
        # CPU shards are forced host devices (the flag affects only the CPU
        # platform).  Must happen before jax initialises its backends;
        # append so any user-provided XLA_FLAGS survive.
        flag = f"--xla_force_host_platform_device_count={args.shards}"
        prev = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in prev:
            os.environ["XLA_FLAGS"] = f"{prev} {flag}".strip()

    import jax
    import numpy as np

    from repro.api import DurabilityConfig, IndexConfig, open_index
    from repro.data import synth
    from repro.eval.recall import exact_topk_ids
    from repro.obs import (
        EventLog,
        FlightRecorder,
        MetricsServer,
        ReadyState,
        SLOMonitor,
        SLOSpec,
        set_event_log,
        set_recorder,
    )
    from repro.obs.instrument import install_recorder_gauges
    from repro.runtime import enable_compile_cache
    from repro.serving.serve import QueryServer

    enable_compile_cache()
    if args.failpoints:
        from repro.fault import FailpointRegistry, set_failpoints
        set_failpoints(FailpointRegistry(seed=args.failpoint_seed)
                       .configure(args.failpoints))
        print(f"failpoints armed: {args.failpoints} "
              f"(seed={args.failpoint_seed})")

    obs_on = args.metrics_port is not None or args.serve_port is not None
    if args.event_log:
        set_event_log(EventLog(args.event_log,
                               max_bytes=args.event_log_max_bytes,
                               keep=args.event_log_keep))
        print(f"event log: {args.event_log}"
              + (f" (rotate at {args.event_log_max_bytes} B, "
                 f"keep {args.event_log_keep})"
                 if args.event_log_max_bytes else ""))
    recorder = slo_monitor = None
    ready = ReadyState()
    ready.mark("engine", False, "index build/recovery in progress")
    if obs_on and args.recorder_capacity > 0:
        recorder = FlightRecorder(capacity=args.recorder_capacity,
                                  sample_rate=args.record_sample)
        set_recorder(recorder)
        install_recorder_gauges(recorder)
    if obs_on:
        slo_monitor = SLOMonitor(
            SLOSpec(latency_ms=args.slo_latency_ms,
                    latency_target=args.slo_target,
                    availability_target=args.slo_availability),
            fast_window_s=args.slo_fast_window_s,
            slow_window_s=args.slo_slow_window_s)
    metrics_server = None
    if args.metrics_port is not None:
        metrics_server = MetricsServer(
            port=args.metrics_port, ready=ready, recorder=recorder,
            slo=slo_monitor, profile_dir=args.profile_dir).start()
        print(f"metrics: {metrics_server.url}/metrics "
              f"(json: /metrics.json, liveness: /healthz, "
              f"readiness: /readyz, debug: /debug/requests /debug/slo)")

    ds = synth.DATASETS[args.dataset]
    draw = (synth.make_corpus_bulk if args.docs >= BULK_DRAW_DOCS
            else synth.make_corpus)
    idx, val = draw(0, ds, args.docs, pad=256)
    qi, qv = synth.make_queries(1, ds, args.queries, pad=96)
    cap = ((args.docs + 31) // 32) * 32
    sketch_kind, cell_dtype = args.sketch_kind, args.value_dtype
    if args.auto_tune:
        from repro.eval import tune as tunelib
        result = tunelib.tune(
            idx, val, qi, qv, ds.n,
            memory_budget_bytes=args.tune_memory_mb * 2 ** 20,
            recall_floor=args.recall_floor, k=args.k,
            target_docs=args.docs, sample_docs=min(args.docs, 2048),
            sample_queries=min(args.queries, 32),
            ms=tuple(sorted({32, args.m, 96})),
            cell_dtypes=("bf16", "f8"),
            kprimes=(args.kprime,), budgets=(args.budget,),
            h=args.h, index_buckets=args.index_buckets)
        pt = result.point
        sketch_kind, cell_dtype, args.m = (pt["sketch_kind"],
                                           pt["cell_dtype"], pt["m"])
        print(f"auto-tune: m={pt['m']} sketch_kind={sketch_kind} "
              f"value_dtype={cell_dtype} -> predicted index "
              f"{pt['predicted_index_bytes'] / 2**20:.2f} MiB @ {args.docs} "
              f"docs, sample recall@{args.k}={pt['recall_at_k']:.3f} "
              f"({'meets constraints' if result.feasible else 'NO feasible point — best-recall fallback'})")
    if args.wal:
        # Recovery serves the PREVIOUS run's vectors, while the corpus and
        # the recall ground truth are regenerated from the flags — and
        # synth.make_corpus is not prefix-stable across --docs.  Refuse to
        # mix durable state with a differently-drawn corpus (or a spec the
        # snapshot would silently override).
        _check_launch_params(args)
    durability = None
    if args.wal:
        durability = DurabilityConfig(
            wal_dir=args.wal, snapshot_dir=args.snapshot_dir,
            snapshot_every=args.snapshot_every,
            compact_threshold=args.compact_threshold)
    config = IndexConfig(
        n=ds.n, capacity=cap, m=args.m, h=args.h, max_nnz=256,
        positive_only=ds.nonneg, index_buckets=args.index_buckets,
        sketch_kind=sketch_kind, cell_dtype=cell_dtype,
        backend=args.score_backend, shards=args.shards,
        durability=durability,
        device_budget_mb=args.device_budget_mb,
        tier_chunk_slots=args.tier_chunk_slots)
    index = open_index(config)
    recovered = index.size
    if recovered:
        print(f"recovered {recovered} docs from snapshot + WAL tail")
    todo = [d for d in range(args.docs)
            if args.wal is None or d not in index]
    for lo in range(0, len(todo), 2048):
        chunk = todo[lo:lo + 2048]
        index.insert_many(chunk, idx[chunk], val[chunk])
        # Inserts do not donate the state: sync so that at most two copies
        # of a multi-GB state are alive at once.
        jax.block_until_ready(index.state)
    n_shards = args.shards if args.shards > 1 else 1
    print(f"indexed {index.size} docs over {n_shards} shard(s)")
    if args.wal and args.snapshot_dir:
        index.snapshot()
        print(f"snapshot written to {args.snapshot_dir}")

    server = QueryServer(index, k=args.k, kprime=args.kprime,
                         budget=args.budget,
                         score_backend=args.score_backend)
    ready.mark("engine", True)      # built/recovered: ready to serve
    if slo_monitor is not None:
        slo_monitor.start()
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    served = []
    for lo in range(0, args.queries, args.query_batch):
        hi = min(lo + args.query_batch, args.queries)
        ids, _ = server.query_many(qi[lo:hi], qv[lo:hi])
        served.extend(ids)
    if args.profile_dir:
        jax.profiler.stop_trace()
        print(f"profiler trace written to {args.profile_dir}")
    truth = exact_topk_ids(idx, val, qi, qv, ds.n, args.k)
    recalls = [len(set(ids.tolist()) & set(t.tolist())) / args.k
               for ids, t in zip(served, truth)]
    lat = server.latency_percentiles()
    print(f"recall@{args.k}={np.mean(recalls):.3f}  "
          f"p50={lat['p50']:.1f}ms p90={lat['p90']:.1f}ms "
          f"p99={lat['p99']:.1f}ms", flush=True)
    frontend = front_door = None
    if args.serve_port is not None:
        from repro.fault import DegradeConfig
        from repro.serving.frontend import FrontendServer, ServingFrontend
        degrade_cfg = DegradeConfig(
            enabled=args.degrade,
            enter_burn=args.degrade_enter_burn,
            exit_burn=args.degrade_exit_burn,
            enter_queue_frac=args.degrade_enter_queue_frac,
            exit_queue_frac=args.degrade_exit_queue_frac,
            dwell_ticks=args.degrade_dwell_ticks) if args.degrade else None
        frontend = ServingFrontend(
            server, max_batch=args.max_batch,
            batch_window_ms=args.batch_window_ms,
            queue_depth=args.queue_depth,
            default_deadline_ms=args.deadline_ms,
            slo=slo_monitor, degrade=degrade_cfg,
            watchdog_timeout_s=args.watchdog_timeout_s)
        front_door = FrontendServer(
            frontend, port=args.serve_port, slo=slo_monitor,
            profile_dir=args.profile_dir)
        front_door.ready.add_check("engine",
                                   lambda: ready()[1]["engine"]["ok"])
        front_door.start()
        print(f"front door: POST {front_door.url}/v1/query "
              f"(max_batch={args.max_batch}, "
              f"window={args.batch_window_ms:g}ms, "
              f"queue_depth={args.queue_depth}, "
              f"deadline={args.deadline_ms:g}ms); "
              f"metrics + /debug also on {front_door.url}", flush=True)
    if args.hold_seconds > 0:
        import time
        print(f"holding for {args.hold_seconds:.0f}s "
              f"(front door and metrics stay up); Ctrl-C to exit",
              flush=True)
        try:
            time.sleep(args.hold_seconds)
        except KeyboardInterrupt:
            pass
    if front_door is not None:
        front_door.stop()
    if frontend is not None:
        frontend.close()
    if slo_monitor is not None:
        slo_monitor.stop()
    set_recorder(None)
    log = set_event_log(None)
    if log is not None:
        log.close()
    if metrics_server is not None:
        metrics_server.stop()


if __name__ == "__main__":
    main()
