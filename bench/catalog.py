"""Find everything a cell needs by name.

* ``BENCHMARK.json`` at the root of the checkout: cells and metrics;
* ``bench/configs/<config>.json``: one deployment (sizes, index settings);
* ``bench/traffic/<traffic>.json``: one traffic mix (parameters only);
* ``bench/cells/<workload>.json``: the cell's fixed rate and the limits of
  its correctness comparison;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, with a
  ``read(ctx)`` that returns the number or None;
* ``bench/peaks.json``: the device peaks, keyed by ``device_kind``.

Adding a configuration, a mix, a cell or a metric adds files and entries;
no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    params: dict            # bench/cells/<name>.json: rate_qps, limits
    end_to_end: tuple       # metric entries of BENCHMARK.json
    per_layer: tuple


def benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``workload`` with its configuration, mix and parameters."""
    spec = benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    params = _load_json(bench_dir / "cells" / f"{workload}.json")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        params=params,
        end_to_end=tuple(m for m in spec["end_to_end"]
                         if _applies(m, workload)),
        per_layer=tuple(m for m in spec["per_layer"]
                        if _applies(m, workload)))


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    mod_name = "bench_metric_" + "".join(
        ch if ch.isalnum() else "_" for ch in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """The peaks of one device kind; an unknown kind is an error."""
    table = _load_json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
