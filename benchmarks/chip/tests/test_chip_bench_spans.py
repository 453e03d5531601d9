"""The readers of the engine's host spans and serving counters, and the
byte model of a queued level: window deltas of ``stats``, nothing where
there is nothing to divide or the engine has no such keys."""
from __future__ import annotations

import pytest

from chip_bench_tiny import BENCH, PEAKS, harness, run, tiny_cell

import roofline_queued
import trace as trace_mod

SHAPES = {"n_ext": 1 << 18, "num_vss_pad": 77_568, "tau": 128, "sigma": 8,
          "num_sets": 32_768, "num_sets_ext": 32_769}
NEW = ("step_host_ms_per_level", "sync_wait_ms_per_level",
       "dispatches_per_level", "queued_bucket_fill", "queued_level_roofline")


def _read(metric, rec):
    return harness.reader(BENCH, metric)(rec)


def _stats(**kw):
    base = {"levels": 0, "levels_queued": 0, "levels_dense": 0,
            "host_syncs": 0}
    base.update(kw)
    return base


def _rec(start, end, modules=None):
    red = trace_mod.Reduced(busy_s=1.0, devices=1, ops={},
                            modules=modules or {}, idle_gaps={}, span_s={})
    return {"stats": {"start": start, "end": end}, "trace": red,
            "artifact": {"shapes": SHAPES, "kappa": 32}, "peaks": PEAKS}


def _window():
    start = _stats(**{
        "levels": 10, "levels_queued": 8, "dispatches": 40,
        "queued_vss": 1000, "queued_rows": 2048,
        "host_s:serve.step": 0.010, "host_s:serve.tick": 0.020,
        "host_s:serve.admit": 0.005, "host_s:serve.sync.new_lane": 0.100,
        "host_s:serve.sync.active_mask": 0.050})
    end = _stats(**{
        "levels": 30, "levels_queued": 18, "dispatches": 100,
        "queued_vss": 21_000, "queued_rows": 34_816,
        "host_s:serve.step": 0.030, "host_s:serve.tick": 0.120,
        "host_s:serve.admit": 0.045, "host_s:serve.sync.new_lane": 0.900,
        "host_s:serve.sync.active_mask": 0.250})
    return start, end


def test_host_and_sync_ms_per_level():
    rec = _rec(*_window())
    # (0.02 + 0.10 + 0.04) s of own code and (0.8 + 0.2) s of read-backs
    # over 20 levels
    assert _read("step_host_ms_per_level", rec) == pytest.approx(8.0)
    assert _read("sync_wait_ms_per_level", rec) == pytest.approx(50.0)


def test_dispatches_per_level_and_bucket_fill():
    rec = _rec(*_window())
    assert _read("dispatches_per_level", rec) == pytest.approx(3.0)
    assert _read("queued_bucket_fill", rec) == pytest.approx(
        100.0 * 20_000 / 32_768)


def test_queued_level_bytes_by_hand():
    # per active VSS, kappa = 32 (one word): masks 128, row ids 512, v2r 4,
    # one frontier tile 8 * 4, visited words read and written 2 * 128 * 4
    assert roofline_queued.queued_level_bytes(SHAPES, 32, 1) == 1700
    assert roofline_queued.queued_level_bytes(SHAPES, 64, 1000) == (
        1000 * (128 + 512 + 4 + 64 + 2 * 128 * 8))
    assert roofline_queued.queued_level_int8_ops(SHAPES, 32, 1) == (
        2 * 32 * 8 * 128)


def test_queued_level_roofline():
    modules = {"jit__level_queued": [10, 0.5], "jit__level": [20, 9.0]}
    rec = _rec(*_window(), modules=modules)
    # 20,000 active VSSs over 10 queued levels, 0.05 s of device time each;
    # bytes bound: 2,000 x 1,700 B at 819 GB/s
    least = 2000 * 1700 / PEAKS["hbm_bytes_per_s"]
    assert _read("queued_level_roofline", rec) == pytest.approx(
        100.0 * least / 0.05)


def test_nothing_to_divide():
    start, end = _window()
    no_levels = _rec(start, dict(start))
    for metric in NEW:
        assert _read(metric, no_levels) is None, metric
    # dense levels only: no queued rows, no queued-level device time
    dense_only = dict(start, levels=start["levels"] + 5,
                      levels_dense=start["levels_dense"] + 5)
    rec = _rec(start, dense_only, modules={"jit__level": [5, 1.0]})
    assert _read("queued_bucket_fill", rec) is None
    assert _read("queued_level_roofline", rec) is None
    # queued levels ran but the trace holds no queued-level program
    assert _read("queued_level_roofline", _rec(start, end)) is None


def test_an_engine_without_the_keys_reads_nothing():
    """An engine that has no such spans or counters (the parent of the
    change that added them) gives no reading, and no error."""
    rec = _rec(_stats(levels=10, levels_queued=10),
               _stats(levels=30, levels_queued=30),
               modules={"jit__level_queued": [20, 1.0]})
    for metric in NEW:
        assert _read(metric, rec) is None, metric


def test_traced_tiny_cell_reports_the_span_metrics():
    """A traced tiny run on the CPU: the readers of the engine's own
    spans and counters find them (the CPU has no device plane, so the
    device-trace readers read nothing)."""
    out, lines = run(tiny_cell("rgg18.routing-backlog", scale=8),
                     traced=True)
    assert out["correct"], (out["checks"], lines)
    m = out["metrics"]
    for metric in NEW[:4]:
        assert m[metric]["value"] > 0, metric
    assert m["queued_bucket_fill"]["value"] <= 100.0
    assert "queued_level_roofline" not in m
