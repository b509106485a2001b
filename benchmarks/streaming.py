"""Sharded streaming benchmarks: insert throughput and query latency vs
shard count.

A chip belongs to one process, so where JAX runs on an accelerator the
benchmark runs in this process over the real devices (shard counts above the
device count are skipped).  On the CPU it runs in a subprocess so the forced
host-device count never leaks into the parent's jax runtime (same pattern as
tests/test_distributed.py); rows come back over stdout as
``ROW,name,value,derived`` lines.
"""

from __future__ import annotations

import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SUBPROC = r'''
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={max_shards}"
sys.path[:0] = ["src", "."]
from benchmarks.streaming import bench_rows
for name, value, derived in bench_rows({docs}, {queries}, {batch},
                                       {shard_counts}):
    print(f"ROW,{{name}},{{value}},{{derived}}")
'''


def bench_rows(docs, queries, batch, shard_counts):
    """Insert throughput and query p50/p99 per shard count, in-process."""
    import time

    import jax

    from repro.core.engine import EngineSpec
    from repro.data import synth
    from repro.distributed import mesh as meshlib
    from repro.serving.serve import QueryServer
    from repro.serving.sharded import ShardedSinnamonIndex

    ds = synth.SparseDatasetSpec("stream", n=2000, psi_doc=40, psi_query=16)
    idx, val = synth.make_corpus(0, ds, docs, pad=64)
    qi, qv = synth.make_queries(1, ds, queries, pad=32)
    rows = []
    for shards in shard_counts:
        mesh = meshlib.make_mesh((1, shards), ("data", "model"))
        cap_local = (((docs + shards - 1) // shards + 31) // 32) * 32
        spec = EngineSpec(n=ds.n, m=20, capacity=cap_local, max_nnz=64, h=1)
        index = ShardedSinnamonIndex(spec, mesh)
        bs = 256
        t0 = time.perf_counter()
        for lo in range(0, docs, bs):
            hi = min(lo + bs, docs)
            index.insert_many(list(range(lo, hi)), idx[lo:hi], val[lo:hi])
        jax.block_until_ready(index.state.u)
        tput = docs / (time.perf_counter() - t0)
        server = QueryServer(index, k=10, kprime=50)
        server.query_many(qi[:batch], qv[:batch])        # compile warmup
        server.reset_stats()
        for lo in range(0, queries, batch):
            server.query_many(qi[lo:lo + batch], qv[lo:lo + batch])
        lat = server.latency_percentiles()
        rows += [(f"streaming/shards{shards}/insert_tput", f"{tput:.1f}",
                  "docs/s"),
                 (f"streaming/shards{shards}/query_p50_ms",
                  f"{lat['p50']:.2f}", ""),
                 (f"streaming/shards{shards}/query_p99_ms",
                  f"{lat['p99']:.2f}", "")]
    return rows


def _run(max_shards, shard_counts, docs, queries, batch, timeout):
    import jax

    if jax.default_backend() != "cpu":
        n_dev = len(jax.devices())
        return bench_rows(docs, queries, batch,
                          [s for s in shard_counts if s <= n_dev])
    code = SUBPROC.format(max_shards=max_shards, shard_counts=shard_counts,
                          docs=docs, queries=queries, batch=batch)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, cwd=_ROOT)
    if out.returncode != 0:
        raise RuntimeError(f"streaming subprocess failed:\n{out.stderr[-2000:]}")
    rows = []
    for line in out.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, value, derived = line.split(",", 3)
            rows.append((name, value, derived))
    if not rows:
        raise RuntimeError(f"no rows from streaming subprocess:\n{out.stdout}")
    return rows


def streaming_smoke():
    """CI-sized: 2 shards, small corpus — exercises the full sharded
    insert → batched-serve path in under a couple of minutes on CPU."""
    return _run(max_shards=2, shard_counts=[2], docs=512, queries=16,
                batch=8, timeout=600)


def streaming_sharded():
    """Insert throughput and query p50/p99 vs shard count (1, 2, 4)."""
    return _run(max_shards=4, shard_counts=[1, 2, 4], docs=4096, queries=32,
                batch=16, timeout=1800)


ALL = [streaming_smoke, streaming_sharded]
