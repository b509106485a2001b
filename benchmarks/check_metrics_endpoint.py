"""CI check: launch the demo server with --metrics-port and validate /metrics.

Boots ``repro.launch.serve`` as a subprocess with a metrics endpoint and
an event log, then:

1. polls ``/metrics`` until the query counter and latency histogram
   families appear (i.e. the server actually served queries),
2. parses the full Prometheus exposition with
   ``repro.obs.metrics.parse_exposition`` (malformed lines raise),
3. asserts the required metric families from the ISSUE acceptance list are
   present (query latency, WAL-independent engine health, byte gauges),
4. fetches ``/metrics.json`` and checks it is valid JSON with the same
   metric names,
5. checks the event log contains parseable ``query`` events carrying
   their batch's trace stages (``device`` with ``launch`` and ``fetch``),
6. hits the ISSUE 8 surfaces on the same port — ``/readyz`` (must be 200
   with per-check detail once the engine is built), ``/debug/requests``
   (flight-recorder ring + stats schema), and ``/debug/slo`` (declared
   objectives + per-window burn rates) — validating each JSON schema.

Exit 0 on success; raises (non-zero) on any failure.  Run as
``python benchmarks/check_metrics_endpoint.py`` from the repo root.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_ROOT, os.path.join(_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# Families the endpoint must expose once a query has been served.
REQUIRED = (
    "repro_query_latency_ms_count",
    "repro_queries_total",
    "repro_engine_live_docs",
    "repro_engine_bytes",
    "repro_engine_ops_total",
)
_READY_MARKERS = ("repro_queries_total", "repro_query_latency_ms_count")
_TIMEOUT_S = 240.0


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _fetch(url: str) -> str:
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.read().decode("utf-8")


def main() -> None:
    from repro.obs.metrics import parse_exposition

    port = _free_port()
    event_log = os.path.join(tempfile.mkdtemp(prefix="obs_check_"),
                             "events.jsonl")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), env.get("PYTHONPATH", "")])
    cmd = [sys.executable, "-m", "repro.launch.serve",
           "--docs", "512", "--queries", "16", "--query-batch", "8",
           "--kprime", "64", "--metrics-port", str(port),
           "--event-log", event_log,
           "--hold-seconds", "600"]
    print(f"+ {' '.join(cmd)}")
    proc = subprocess.Popen(cmd, env=env, cwd=_ROOT,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        base = f"http://127.0.0.1:{port}"
        deadline = time.time() + _TIMEOUT_S
        text = ""
        while time.time() < deadline:
            if proc.poll() is not None:
                out = proc.stdout.read() if proc.stdout else ""
                raise RuntimeError(
                    f"server exited early (rc={proc.returncode}):\n{out}")
            try:
                text = _fetch(base + "/metrics")
            except OSError:
                time.sleep(0.5)
                continue
            if all(m in text for m in _READY_MARKERS):
                break
            time.sleep(0.5)
        else:
            raise RuntimeError(
                f"timed out after {_TIMEOUT_S}s waiting for "
                f"{_READY_MARKERS} in /metrics; last scrape:\n{text[:2000]}")

        flat = parse_exposition(text)   # raises on malformed exposition
        names = {name for name, _ in flat}
        missing = [m for m in REQUIRED if m not in names]
        if missing:
            raise RuntimeError(f"missing metric families: {missing}")
        print(f"/metrics OK: {len(flat)} series")

        doc = json.loads(_fetch(base + "/metrics.json"))
        missing = [m for m in ("repro_query_latency_ms",
                               "repro_engine_live_docs") if m not in doc]
        if missing:
            raise RuntimeError(f"/metrics.json missing: {missing}")
        if doc["repro_query_latency_ms"]["type"] != "histogram":
            raise RuntimeError("repro_query_latency_ms is not a histogram")
        print(f"/metrics.json OK: {len(doc)} metric names")

        with open(event_log) as f:
            events = [json.loads(line) for line in f if line.strip()]
        traced = [e for e in events if e["event"] == "query"
                  and {"device", "launch", "fetch"}
                  <= {s["stage"] for s in e.get("stages") or ()}]
        if not traced:
            raise RuntimeError(f"no query events with device/launch/fetch "
                               f"stages in {event_log}; saw "
                               f"{[e['event'] for e in events][:20]}")
        print(f"event log OK: {len(events)} events, {len(traced)} with "
              f"stages={[s['stage'] for s in traced[0]['stages']]}")

        ready = json.loads(_fetch(base + "/readyz"))
        if ready.get("ready") is not True:
            raise RuntimeError(f"/readyz not ready after build: {ready}")
        engine = ready.get("checks", {}).get("engine")
        if not (isinstance(engine, dict) and engine.get("ok") is True):
            raise RuntimeError(f"/readyz missing engine check: {ready}")
        print(f"/readyz OK: checks={sorted(ready['checks'])}")

        dbg = json.loads(_fetch(base + "/debug/requests?limit=10"))
        for key in ("requests", "count", "recorder"):
            if key not in dbg:
                raise RuntimeError(f"/debug/requests missing {key!r}: "
                                   f"{sorted(dbg)}")
        stats = dbg["recorder"]
        if stats.get("seen", 0) < 1 or "capacity" not in stats:
            raise RuntimeError(f"/debug/requests recorder stats wrong: "
                               f"{stats}")
        for rec in dbg["requests"]:
            for key in ("trace_id", "outcome", "stages", "retained"):
                if key not in rec:
                    raise RuntimeError(
                        f"/debug/requests record missing {key!r}: {rec}")
        print(f"/debug/requests OK: {dbg['count']} retained of "
              f"{stats['seen']} seen")

        slo = json.loads(_fetch(base + "/debug/slo"))
        for key in ("objectives", "windows", "slos"):
            if key not in slo:
                raise RuntimeError(f"/debug/slo missing {key!r}: "
                                   f"{sorted(slo)}")
        for name in ("latency", "availability"):
            wins = slo["slos"][name]["windows"]
            for w in ("fast", "slow"):
                for key in ("burn_rate", "compliance", "good", "total"):
                    if key not in wins[w]:
                        raise RuntimeError(
                            f"/debug/slo {name}/{w} missing {key!r}: "
                            f"{wins[w]}")
        print(f"/debug/slo OK: objectives={slo['objectives']}")
        print("check_metrics_endpoint: PASS")
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()


if __name__ == "__main__":
    main()
