"""Every cell resolves to its files by name, and a new cell needs only new
files and new entries in BENCHMARK.json."""
from __future__ import annotations

import hashlib
import json
import shutil

import pytest

from chip_bench_tiny import BENCH, ROOT, harness, run, tiny_cell

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = harness.load_cell(name)
    assert cell.config["graph"]["family"] in harness.generators.FAMILIES
    assert cell.traffic["loop"] == "closed"
    assert cell.end_to_end and cell.per_layer
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.reader(BENCH, m["name"])), m["name"]


def test_every_per_layer_metric_moves_an_end_to_end_metric():
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def _digest(tree):
    return {p.relative_to(tree): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(tree.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_from_new_files_only(tmp_path):
    """A configuration, a traffic mix and a metric reader, added as files:
    the harness runs the new cell without an edit to any file it already
    had."""
    bench_copy = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench_copy,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _digest(bench_copy)
    (bench_copy / "configs" / "urand7.json").write_text(json.dumps({
        "graph": {"family": "urand", "scale": 7, "edge_factor": 16,
                  "seed": 3},
        "engine": {"kappa": 32, "switching": "on"}}))
    (bench_copy / "traffic" / "reach-pairs.json").write_text(json.dumps({
        "loop": "closed", "clients": 8,
        "kinds": {"distance": 0.5, "reach": 0.5},
        "sources": {"dist": "uniform"}, "targets": {"dist": "uniform"},
        "warmup_queries": 8}))
    (bench_copy / "metrics" / "answered.py").write_text(
        "def read(rec):\n"
        "    return sum(q['in_window'] for q in rec['queries'])\n")
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": "urand7", "source": "test",
                             "file": "benchmarks/chip/configs/urand7.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "urand7.reach-pairs",
                               "config": "urand7",
                               "traffic": "reach-pairs",
                               "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "answered", "unit": "queries",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["urand7.reach-pairs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out, _ = run(harness.load_cell("urand7.reach-pairs", tmp_path))
    assert out["correct"], out["checks"]
    assert out["metrics"]["answered"]["value"] > 0
    assert set(out["metrics"]) == {"queries_per_s", "latency_p50_s",
                                   "latency_p95_s", "setup_s", "answered"}
    after = _digest(bench_copy)
    assert {k: v for k, v in after.items() if k in before} == before


def test_tiny_cells_are_correct_and_report_their_metrics():
    for name in CELLS:
        cell = tiny_cell(name, scale=8)
        out, lines = run(cell)
        assert out["correct"], (name, out["checks"], lines)
        assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert list(out)[-1] == "checks"
        assert lines[-1].startswith("check ")
