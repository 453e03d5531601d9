"""Shared by the benchmark's tests: the harness on the CPU at tiny sizes.

The tests run the harness without its look for a chip (``run_cell`` is
called directly with the CPU's devices), on the cells' own files with the
graph's scale and the warm-up cut down.
"""
from __future__ import annotations

import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for _p in (str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402

PEAKS = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
SEED = 2**31 + 11


def tiny_cell(name: str, scale: int, warmup: int = 16,
              root: pathlib.Path = ROOT) -> "harness.Cell":
    cell = harness.load_cell(name, root)
    cell.config["graph"]["scale"] = scale
    cell.traffic["warmup_queries"] = warmup
    return cell


def run(cell, *, seed: int = SEED, seconds: float = 0.5,
        traced: bool = False, drain_s: float = 5.0, warmup_s: float = 60.0):
    """``(result line, stderr lines)`` of one tiny run on the CPU."""
    import jax

    lines: list[str] = []
    out = harness.run_cell(cell, seed, seconds, traced,
                           devices=jax.devices(), peaks=PEAKS,
                           t_start=time.perf_counter(), drain_s=drain_s,
                           warmup_s=warmup_s, log=lines.append)
    return out, lines
