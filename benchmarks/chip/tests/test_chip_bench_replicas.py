"""The four-replica cell and the readers of the replica router's counters.

The cell runs tiny on four virtual CPU devices in a subprocess (this
process keeps its single-device view); the readers run on synthetic
window records, and read nothing where the engine lacks the counters (the
parent of the change that added them) or nothing ran."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from chip_bench_tiny import BENCH, ROOT, harness

CELL = "urand18-4rep.closeness-backlog"
NEW = ("ms_per_round", "replicas_per_round", "replica_level_balance")

TINY_4 = textwrap.dedent(f"""
    import json, sys
    sys.path[:0] = [{str(BENCH / "tests")!r}, {str(BENCH)!r},
                    {str(ROOT / "src")!r}]
    import jax
    from chip_bench_tiny import run, tiny_cell
    out = {{"devices": jax.device_count()}}
    for traced in (False, True):
        # the warm-up's 256 clients open every replica's lanes
        res, lines = run(tiny_cell({CELL!r}, scale=8, warmup=256),
                         traced=traced)
        out[str(traced)] = {{"correct": res["correct"],
                             "checks": res["checks"],
                             "metrics": res["metrics"],
                             "tail": lines[-6:]}}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def tiny_runs():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", TINY_4], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_tiny_four_replica_cell_is_correct(tiny_runs):
    assert tiny_runs["devices"] == 4
    run = tiny_runs["False"]
    assert run["correct"], run
    assert run["checks"]["answers_checked"]["value"] > 0
    assert set(run["metrics"]) == {"queries_per_s", "latency_p50_s",
                                   "latency_p95_s", "setup_s"}


def test_traced_tiny_four_replica_cell_reports_the_router(tiny_runs):
    run = tiny_runs["True"]
    assert run["correct"], run
    m = run["metrics"]
    assert set(NEW) <= set(m), run["tail"]
    assert m["ms_per_round"]["value"] > 0
    assert 1.0 <= m["replicas_per_round"]["value"] <= 4.0
    assert 0.0 < m["replica_level_balance"]["value"] <= 100.0


def _read(metric, rec):
    return harness.reader(BENCH, metric)(rec)


def _rec(start, end, elapsed_s=10.0):
    return {"stats": {"start": start, "end": end},
            "window": {"seconds": elapsed_s, "elapsed_s": elapsed_s}}


def _window():
    start = {"levels": 100, "rounds": 30, "levels:replica0": 30,
             "levels:replica1": 25, "levels:replica2": 25,
             "levels:replica3": 20}
    end = {"levels": 500, "rounds": 130, "levels:replica0": 130,
           "levels:replica1": 125, "levels:replica2": 125,
           "levels:replica3": 100}
    return start, end


def test_router_readers_by_hand():
    rec = _rec(*_window())
    # 400 levels in 100 rounds over 10 s; replica levels 100/100/100/80
    assert _read("ms_per_round", rec) == pytest.approx(100.0)
    assert _read("replicas_per_round", rec) == pytest.approx(4.0)
    assert _read("replica_level_balance", rec) == pytest.approx(80.0)


@pytest.mark.parametrize("metric", NEW)
def test_router_readers_read_nothing_without_the_counters(metric):
    """An engine without the counters (the parent), a window without a
    round, and a replica that ran no level give no reading."""
    start, end = _window()
    bare = _rec({"levels": 10}, {"levels": 40})
    assert _read(metric, bare) is None
    assert _read(metric, _rec(start, dict(start))) is None
    if metric == "replica_level_balance":
        idle = dict(end, **{"levels:replica3": start["levels:replica3"]})
        assert _read(metric, _rec(start, idle)) is None
        lone = _rec({"levels": 1, "levels:replica0": 1},
                    {"levels": 9, "levels:replica0": 9})
        assert _read(metric, lone) is None
