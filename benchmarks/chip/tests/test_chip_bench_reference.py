"""The plain reference against an independent oracle, and its control."""
from __future__ import annotations

import numpy as np
import pytest

from chip_bench_tiny import harness

import compare
import generators
import reference
import traffic

SPECS = {"urand": {"family": "urand", "scale": 9, "edge_factor": 16},
         "rgg": {"family": "rgg", "scale": 10, "radius_coeff": 0.55}}


def _plain_bfs(n, src, dst, s):
    """One source, one level at a time, with Python sets."""
    adj = [[] for _ in range(n)]
    for a, b in zip(src.tolist(), dst.tolist()):
        adj[a].append(b)
    depth = np.full(n, -1, np.int32)
    depth[s] = 0
    frontier, d = [s], 0
    while frontier:
        d += 1
        nxt = {b for a in frontier for b in adj[a] if depth[b] < 0}
        for b in nxt:
            depth[b] = d
        frontier = list(nxt)
    return depth


@pytest.mark.parametrize("family", sorted(SPECS))
def test_reference_matches_plain_bfs(family):
    n, src, dst = generators.make({**SPECS[family], "seed": 99})
    g = reference.Csr(n, src, dst)
    rng = np.random.default_rng(5)
    sources = rng.integers(0, n, 70)          # more than one 64-bit word
    targets = rng.integers(0, n, 70)
    t = reference.traverse(g, sources, targets, keep=range(70))
    for i, s in enumerate(sources):
        want = _plain_bfs(n, src, dst, int(s))
        assert (t.rows[i] == want).all()
        reached = want[want >= 0]
        assert t.far[i] == reached.sum() and t.reach[i] == reached.size
        assert t.target_depth[i] == want[targets[i]]


@pytest.mark.parametrize("family", sorted(SPECS))
def test_control_fails_the_comparison(family):
    """The control (the reference cut one level short) in the program's
    place is not correct on three seeds."""
    mix = harness.load_cell("urand18.mixed-backlog").traffic
    for seed in (1, 2, 3):
        n, src, dst = generators.make({**SPECS[family], "seed": seed})
        g = reference.Csr(n, src, dst)
        stream = traffic.Stream(mix, n, seed)
        queries = [stream.next() for _ in range(100)]
        got = compare.reference_answers(g, queries, control=True)
        verdict = compare.compare(g, queries, got, missing=0)
        assert not verdict.correct and verdict.wrong > 0, seed
        same = compare.compare(g, queries,
                               compare.reference_answers(g, queries), 0)
        assert same.correct and same.wrong == 0
