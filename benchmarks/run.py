# One function per paper table. Prints ``name,value,derived`` CSV; with
# ``--json PATH`` also writes a machine-readable {name: {value, derived}}
# map so CI can archive the perf trajectory as BENCH_<n>.json artifacts.
# Exits non-zero if any table function errors, so CI smoke jobs fail loudly.
import argparse
import datetime
import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_ROOT, os.path.join(_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("only", nargs="?", default=None,
                    help="run only benchmark functions matching this substring")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as JSON (BENCH_<n>.json)")
    return ap.parse_args(argv)


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "-C", _ROOT, "rev-parse", "HEAD"], capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
    except Exception:                               # noqa: BLE001
        return "unknown"


def main() -> None:
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (chaos, obs_overhead, paper, persist, query_path,
                            recall, serving, streaming, tiering)

    args = parse_args()
    fns = [fn for fn in paper.ALL + streaming.ALL + persist.ALL
           + query_path.ALL + recall.ALL + obs_overhead.ALL + serving.ALL
           + chaos.ALL + tiering.ALL
           if not args.only or args.only in fn.__name__]
    if not fns:
        print(f"no benchmark matches {args.only!r}", file=sys.stderr)
        sys.exit(2)
    failed = False
    results = {}
    print("name,value,derived")
    for fn in fns:
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:                      # noqa: BLE001
            print(f"{fn.__name__},ERROR,{type(e).__name__}: {e}")
            results[fn.__name__] = {"value": "ERROR",
                                    "derived": f"{type(e).__name__}: {e}"}
            failed = True
            continue
        for name, value, derived in rows:
            print(f"{name},{value},{derived}")
            results[name] = {"value": value, "derived": derived}
        print(f"# {fn.__name__} took {time.time() - t0:.1f}s",
              file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"bench_schema_version": 2, "git_sha": _git_sha(),
                       "generated_utc": datetime.datetime.now(
                           datetime.timezone.utc).isoformat(
                               timespec="seconds"),
                       "rows": results, "failed": failed}, f, indent=1)
        print(f"# wrote {args.json}", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
