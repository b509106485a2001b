"""Calibration runs on the chip (not part of a benchmark run).

    python3 -m bench.calibrate --workload <cell> --seed <n> sweep \
        --rates 60,80,100 --seconds 15
    python3 -m bench.calibrate --workload <cell> --seed <n> trace \
        --seconds 10 --out chiprun_out/trace.json.gz

``sweep`` builds the cell once and offers the open-loop schedule at each
rate in turn: for each it prints the requests offered, those answered
within the window, the backlog (offered but not answered) when the window
closes, and the latencies of the window's first and second halves.  The
knee is the highest rate whose completions keep up and whose backlog does
not grow.  ``trace`` writes one normalised profiler trace of a window.
"""

from __future__ import annotations

import argparse
import os
import gzip
import json
import shutil
import sys
import tempfile
import types

import numpy as np

from bench import catalog, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    sub = ap.add_subparsers(dest="mode", required=True)
    sw = sub.add_parser("sweep")
    sw.add_argument("--rates", required=True)
    sw.add_argument("--seconds", type=float, required=True)
    tr = sub.add_parser("trace")
    tr.add_argument("--seconds", type=float, required=True)
    tr.add_argument("--rate", type=float, default=None)
    tr.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    cell = catalog.load_cell(args.workload)
    sys.path.insert(0, str(catalog.ROOT / "src"))
    import jax

    if run.CACHE_ENV not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: needs the chip", file=sys.stderr)
        return 1
    devices = devices[:cell.chips]
    setup = {}
    rargs = types.SimpleNamespace(seed=args.seed, seconds=args.seconds,
                                  rehearse=False, docs=None, trace=0)
    sut = run.build(cell, rargs, devices, setup)
    print("setup " + " ".join(f"{k}={v:.3f}" for k, v in setup.items()),
          flush=True)
    if args.mode == "sweep":
        for rate in [float(r) for r in args.rates.split(",")]:
            win = run.window(cell, rargs, sut, rate, None)
            lat = np.where(win.ok, (win.done - win.due) * 1e3, np.inf)
            in_win = win.ok & (win.done <= win.t_end)
            backlog = int((win.submitted <= win.t_end).sum() - in_win.sum())
            half = win.due < win.t0 + args.seconds / 2
            print(json.dumps({
                "rate_qps": rate, "offered": int(win.n),
                "answered_in_window": int(in_win.sum()),
                "completions_per_s": float(in_win.sum() / args.seconds),
                "backlog_at_close": backlog,
                "p50_ms": run._percentile(lat, 50),
                "p99_ms": run._percentile(lat, 99),
                "p50_first_half_ms": run._percentile(lat[half], 50),
                "p50_second_half_ms": run._percentile(lat[~half], 50),
                "batch_fill": (win.batch_sizes[0] / max(1, win.batch_sizes[1])
                               / cell.traffic["max_batch"]),
                "compiles_in_window": win.compiles_in_window,
                **run.stalls(sut, win)}), flush=True)
    else:
        from bench import xtrace
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        rate = args.rate or cell.params["rate_qps"]
        win = run.window(cell, rargs, sut, rate, log_dir)
        trace = xtrace.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        with gzip.open(args.out, "wt") as f:
            json.dump(trace, f)
        totals = xtrace.op_totals(trace)
        mods = xtrace.op_totals(trace, xtrace.MODULES_LINE)
        print("planes", [p["name"] for p in trace["planes"]])
        for p in trace["planes"]:
            print("plane", p["name"], [(ln["name"], len(ln["events"]))
                                       for ln in p["lines"]])
        for name, (t, c) in sorted(mods.items(), key=lambda x: -x[1][0])[:10]:
            print(f"module {t / 1e6:.3f} ms x{c} {name}")
        for name, (t, c) in sorted(totals.items(), key=lambda x: -x[1][0])[:25]:
            print(f"op {t / 1e6:.3f} ms x{c} {name}")
        print("answered", int(win.ok.sum()), "of", win.n)
    sut.frontend.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
