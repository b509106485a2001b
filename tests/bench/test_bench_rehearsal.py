"""``bench.run`` off the chip: it refuses to run without a TPU or without
the program, and its CPU rehearsal (``--rehearse``) drives every phase of a
cell at a tiny size, compares with the reference, and prints no device
metric.  The fp8 control, put in the program's place, comes out not
correct."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
TINY = {"splade_f32.open": 4096, "g100_f32.open": 4096}


def bench_run(args, cwd=ROOT, code=None, timeout=600):
    """Run ``bench.run`` in a fresh process on the CPU (optionally after
    ``code``, which may break the program underneath)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    prelude = ("import sys, numpy as np\n"
               f"sys.path[:0] = [{str(cwd)!r}, {str(cwd / 'src')!r}]\n")
    main = f"from bench import run\nsys.exit(run.main({list(args)!r}))\n"
    return subprocess.run([sys.executable, "-c", prelude + (code or "") + main],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def result_of(out):
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert lines, out.stdout[-2000:] + out.stderr[-3000:]
    return json.loads(lines[-1])


def rehearse(cell, seed, seconds="1.5", extra=()):
    return ["--workload", cell, "--seed", str(seed), "--seconds", seconds,
            "--rehearse", "--docs", str(TINY[cell]), *extra]


def test_without_tpu_fails_and_prints_no_result():
    out = bench_run(["--workload", "splade_f32.open", "--seed", "1",
                     "--seconds", "1"])
    assert out.returncode == 1
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert "no TPU" in out.stderr


def test_alone_fails_and_prints_no_result(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", alone)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, alone / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = bench_run(["--workload", "splade_f32.open", "--seed", "1",
                     "--seconds", "1"], cwd=alone)
    assert out.returncode != 0
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_rehearsal_is_correct_and_prints_no_device_metric(cell):
    out = bench_run(rehearse(cell, 2**31 + 17))
    assert out.returncode == 0, out.stderr[-3000:]
    res = result_of(out)
    assert res["correct"] is True, out.stderr[-2000:]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "compared"
    assert res["compared"]["ids_missed"]["value"] == 0
    assert not set(res["metrics"]) & {
        "p50_ms", "p99_ms", "qps", "setup_s", "device.idle_share",
        "search.device_ms", "scan.kernel_ms", "scan_roofline",
        "shard.collective_ms"}
    assert "busy_s" not in res["device"]
    assert out.stderr.rstrip().splitlines()[-1].startswith("compare ")


def test_control_is_not_correct():
    out = bench_run(rehearse("splade_f32.open", 5, extra=("--control",)))
    assert out.returncode == 0, out.stderr[-3000:]
    res = result_of(out)
    assert res["correct"] is False
    c = res["compared"]
    assert (c["ids_missed"]["value"] > c["ids_missed"]["limit"]
            or c["score_gap"]["value"] > c["score_gap"]["limit"])
