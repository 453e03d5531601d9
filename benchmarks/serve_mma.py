"""Binary-MMA pull layout vs the packed selective-OR pull on dense
levels (DESIGN.md §13.5).

Dense serve levels have two kernel formulations: the packed layout's
selective-OR pull (``kernels/pull_ms_packed.py``, one grid pass walking
every VSS block, its marks ORed into their rows by the slot-table
gather of ``kernels/gather_or.py``) and the blocked bit-matrix product
(``kernels/pull_mma_ms_packed.py``), which unpacks the VSS bit-tiles to
int8 planes once at tile prep and turns each dense sweep into MXU-shaped
``(block, tau, sigma) x (block, sigma, kappa)`` batched matmuls.  On CPU
the comparison runs each layout's XLA reference twin (``use_pallas=False``
— Pallas interpret wall-times are meaningless, see benchmarks/common.py),
which is the bit-identical formulation the TPU kernels implement: the
fused gather pays a serialized selective-OR per VSS row, the MMA path one
batched int8 contraction — the same work-shape gap §13 predicts on the
MXU.

This module serves kappa-sized request bursts over scale-free (kron) and
uniform (urand) graphs at kappa ∈ {32, 64}, switching off (every level
dense — the regime under comparison), through three engine layouts:
``packed`` (selective-OR baseline), ``mma`` (the new layout), and
``byteplane`` (the AND-OR base substrate, context for the §13.4 probe
verdict).  Every result of every configuration is checked bit-identical
to the CPU oracle before its row prints.

Acceptance bar (PR 6, full size only): the MMA layout beats the fused
gather layout in levels/sec at every kappa on at least one graph family.

    PYTHONPATH=src python -m benchmarks.serve_mma [--tiny] [--json PATH]

``--tiny`` shrinks the graphs/kappas/requests for the CI smoke step; the
smoke keeps every oracle check but not the throughput bar (sub-ms tiny
timings are jitter-dominated on shared CI runners).  ``--json PATH``
dumps the rows for the CI perf-trajectory artifact
(``BENCH_serve_mma.json``).
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.core import ref_bfs
from repro.data import graphs

from benchmarks import common

KAPPAS = (32, 64)
FAMILIES = ("kron", "urand")
LAYOUTS = ("packed", "mma", "byteplane")
REPEATS = 3


def _submit_bursts(srcs, kappa):
    """One kappa-burst per drain so every configuration serves identical
    lane generations (same shape as benchmarks/serve_fused.py)."""
    def submit(eng):
        results = {}
        for i in range(0, len(srcs), kappa):
            for s in srcs[i : i + kappa]:
                eng.submit("g", int(s))
            results.update(eng.run())
        return results
    return submit


def bench_family(fam, g, srcs, oracle, kappa) -> dict:
    from repro.serve.bfs_engine import BfsEngine

    def make_engine(kw):
        eng = BfsEngine(kappa=kappa, use_pallas=False, switching="off",
                        reorder="natural", **kw)
        eng.register_graph("g", g)
        return eng

    configs = [(f"{fam}_k{kappa}_{layout}", {"layout": layout})
               for layout in LAYOUTS]
    drain = lambda eng: common.serve_drain(eng, _submit_bursts(srcs, kappa))
    best = common.interleaved_best(configs, make_engine, drain, REPEATS)
    rows = {}
    for label, (_eng, (secs, results, stats)) in best.items():
        for r in results.values():
            assert (r.levels == oracle[r.source]).all(), \
                f"{label}: result diverged from oracle at source {r.source}"
        rows[label] = {
            "label": label, "family": fam, "kappa": kappa,
            "layout": label.rsplit("_", 1)[1], "seconds": secs,
            "stats": stats, "levels_per_s": stats["levels"] / secs}
    return rows


def main(argv=()):
    # argv defaults to () — benchmarks.run calls main() with the harness's
    # own flags still in sys.argv; only the __main__ path forwards them
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: small graphs, one kappa, few requests")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump rows as JSON (CI perf-trajectory artifact)")
    args = ap.parse_args(list(argv))

    scale = 6 if args.tiny else 10
    kappas = (32,) if args.tiny else KAPPAS
    families = ("kron",) if args.tiny else FAMILIES
    bursts = 1 if args.tiny else 2

    rows = {}
    for fam in families:
        g = graphs.make(fam, scale=scale, seed=0)
        rng = np.random.default_rng(0)
        for kappa in kappas:
            srcs = rng.integers(0, g.n, bursts * kappa)
            oracle = {int(s): ref_bfs.bfs_levels(g, int(s))
                      for s in set(map(int, srcs))}
            rows.update(bench_family(fam, g, srcs, oracle, kappa))

    for fam in families:
        for kappa in kappas:
            base = rows[f"{fam}_k{kappa}_packed"]
            for layout in LAYOUTS:
                row = rows[f"{fam}_k{kappa}_{layout}"]
                print(common.csv_row(
                    row["label"], row["seconds"] / len(srcs) * 1e6,
                    f"levels_per_s={row['levels_per_s']:.0f} "
                    f"speedup_vs_packed="
                    f"{row['levels_per_s'] / base['levels_per_s']:.2f}x "
                    f"dense={row['stats']['levels_dense']}"))

    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"scale": scale, "kappas": list(kappas),
                       "families": list(families), "tiny": args.tiny,
                       "rows": list(rows.values())}, fh, indent=2)
        print(f"# wrote {args.json}")

    # acceptance (full size only).  --tiny is a *smoke*: sub-ms timings are
    # jitter-dominated on shared CI runners, so the tiny run keeps the
    # oracle checks (the correctness invariant) but not the throughput bar.
    if args.tiny:
        return
    for kappa in kappas:
        wins = [fam for fam in families
                if rows[f"{fam}_k{kappa}_mma"]["levels_per_s"]
                > rows[f"{fam}_k{kappa}_packed"]["levels_per_s"]]
        if not wins:
            raise AssertionError(
                f"kappa={kappa}: the MMA layout beat the fused gather "
                f"layout on no graph family — §13's dense-level win "
                f"did not materialize")
        print(f"# kappa={kappa}: mma beats fused gather on "
              f"{','.join(wins)}")


if __name__ == "__main__":
    import sys

    main(sys.argv[1:])
