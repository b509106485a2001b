"""Configurations, mixes, cells and metric readers are found by name, and a
new one is picked up without editing any file that exists."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import catalog  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads(cell):
    c = catalog.load_cell(cell)
    assert c.chips in (1, 4)
    assert c.config["chips"] == c.chips
    assert c.params["rate_qps"] > 0
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "p50_ms"}
    assert c.per_layer, "every cell reports a per-layer metric"


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(catalog.metric_reader(metric))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        catalog.peaks("TPU v99")
    assert catalog.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_new_entries_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "splade_msmarco.json").read_text())
    cfg["name"] = "bm25_msmarco"
    cfg["dataset"]["psi_query"] = 6
    (bench / "configs" / "bm25_msmarco.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "open_poisson.json").read_text())
    mix["phases"] = [[0.25, 4.0], [0.75, 0.0]]
    (bench / "traffic" / "burst.json").write_text(json.dumps(mix))
    (bench / "cells" / "bm25.burst.json").write_text(json.dumps(
        {"rate_qps": 12.5, "limits": {"ids_missed": 0, "score_gap": 1e-4,
                                      "requests_compared_min": 8}}))
    (bench / "metrics" / "frontend.answers.py").write_text(
        "def read(ctx):\n    return len(ctx.queue_ms)\n")
    spec["configs"].append({"name": "bm25_msmarco", "source": "x",
                            "file": "bench/configs/bm25_msmarco.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "bm25.burst", "config": "bm25_msmarco",
                              "traffic": "burst", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "frontend.answers", "unit": "1",
                              "better": "higher", "source": "program_counter",
                              "layer": "front door", "moves": "p99_ms",
                              "workloads": ["bm25.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = catalog.load_cell("bm25.burst", root=root, bench_dir=bench)
    assert cell.config["dataset"]["psi_query"] == 6
    assert cell.traffic["phases"][0] == [0.25, 4.0]
    assert cell.params["rate_qps"] == 12.5
    assert [m["name"] for m in cell.per_layer] == ["frontend.answers"]
    read = catalog.metric_reader("frontend.answers", bench_dir=bench)
    assert read(type("Ctx", (), {"queue_ms": [1.0, 2.0]})) == 2
    after = {p.relative_to(root): p.read_bytes()
             for p in (root / "bench").rglob("*") if p.is_file()
             and p.relative_to(root) in before}
    assert after == before, "no existing file under bench/ was edited"


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        catalog.load_cell("no.such.cell")
