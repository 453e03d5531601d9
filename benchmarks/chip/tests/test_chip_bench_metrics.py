"""The end-to-end readers take every query sent in the window, and the
warm-up fails loudly where the engine's internals it drives are gone."""
from __future__ import annotations

import json
import types

import pytest

from chip_bench_tiny import BENCH, ROOT, harness

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


def _rec(queries, seconds=10.0):
    return {"queries": queries, "window": {"seconds": seconds}}


def _q(latency, in_window, wait=0.5):
    return {"kind": "distance", "latency_s": latency, "queue_wait_s": wait,
            "in_window": in_window}


def test_latency_counts_queries_answered_after_the_close():
    # 19 answered in the window at 1 s, one sent in it and answered in the
    # drain at 30 s: the tail is that query's, the rate counts only the 19
    rec = _rec([_q(1.0, True)] * 19 + [_q(30.0, False)])
    p95 = harness.reader(BENCH, "latency_p95_s")(rec)
    assert p95 > 1.0
    assert harness.reader(BENCH, "latency_p50_s")(rec) == 1.0
    assert harness.reader(BENCH, "queries_per_s")(rec) == pytest.approx(1.9)


def test_queue_wait_mean_over_the_sent_queries():
    rec = _rec([_q(1.0, True, 0.2), _q(5.0, False, 1.0), _q(None, False, None)])
    assert harness.reader(BENCH, "queue_wait_s_mean")(rec) == pytest.approx(0.6)


@pytest.mark.parametrize("config", [c["name"] for c in BENCHMARK["configs"]])
def test_every_configuration_fixes_its_graph(config):
    cfg = json.loads((ROOT / {c["name"]: c for c in BENCHMARK["configs"]}
                      [config]["file"]).read_text())
    assert isinstance(cfg["graph"]["seed"], int)


def test_warm_levels_raises_without_the_engine_internals():
    eng = types.SimpleNamespace(cache=types.SimpleNamespace(
        peek=lambda graph: types.SimpleNamespace()))
    with pytest.raises(AttributeError):
        harness._warm_levels(eng, "g")
