#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, its traffic
mix and its metrics are named in ``BENCHMARK.json`` (see ``harness.py``).
The graph and the queries are made from ``--seed``; set-up builds the
graph's artifact and answers a warm-up of the mix, then the window
measures for ``--seconds``; every answer due in the window is then
compared with the plain reference.  ``--trace 1`` records a profiler
trace of the window and reports the per-layer metrics instead of the
end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` too when
traced) and, last, ``checks``: each compared number beside its limit,
which are also the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, the run prints no result and exits 1.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# the persistent compilation cache lives at a fixed path in the checkout
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    pass


def log(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def chips_for(chips: int):
    """The first ``chips`` TPU devices, or ``NoChip``."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"jax.devices()[0] is {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise NoChip(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    try:
        import harness

        cell = harness.load_cell(args.workload)
        devices = chips_for(cell.chips)
        peaks = peaks_for(devices[0].device_kind)
        import jax

        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), devices=devices,
                               peaks=peaks, t_start=T_START, log=log)
    except (NoChip, ImportError, KeyError, FileNotFoundError) as e:
        log(f"run.py: {type(e).__name__}: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
