#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, at a cell's size.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 1,2,3 --queries 400

It makes the cell's graph and, for each seed, as many of the window's
queries as a run answers, puts the control (the plain reference cut one
level short, ``reference.traverse(stop_early=True)``) in the program's
place, and prints the comparison's numbers.  Every seed has to come out
not correct.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import compare  # noqa: E402
import generators  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    all_fail = True
    n, src, dst = generators.make(cell.config["graph"])
    g = reference.Csr(n, src, dst)
    for seed in (int(s) for s in args.seeds.split(",")):
        stream = traffic.Stream(cell.traffic, n, harness.sub_seed(seed, 2))
        queries = [stream.next() for _ in range(args.queries)]
        got = compare.reference_answers(g, queries, control=True)
        v = compare.compare(g, queries, got, missing=0)
        all_fail &= not v.correct
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": v.correct, "checks": v.limits(),
                          "first_wrong": v.first_wrong}), flush=True)
    return 0 if all_fail else 1


if __name__ == "__main__":
    sys.exit(main())
