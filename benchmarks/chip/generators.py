"""Graph generators of the benchmark's configurations, made from a seed.

Each generator returns ``(n, src, dst)``: the vertex count and the
directed edge list as int32 arrays, symmetric (both directions of every
undirected edge), without self loops or duplicate edges.  They are kept
here, apart from the program's own generators, so that a change to the
program cannot change the graphs it is measured on.

A configuration file names its generator under ``graph.family`` and the
graph's fixed seed under ``graph.seed``; the other keys of ``graph`` are
the generator's keyword arguments.
"""
from __future__ import annotations

import numpy as np


def _symmetric_simple(n: int, u: np.ndarray, v: np.ndarray):
    """Both directions of every edge, self loops and duplicates removed."""
    keep = u != v
    u, v = u[keep], v[keep]
    key = np.unique(np.concatenate([u * n + v, v * n + u]))
    return n, (key // n).astype(np.int32), (key % n).astype(np.int32)


def urand(seed: int, *, scale: int, edge_factor: int):
    """GAP Benchmark Suite ``urand`` (Beamer et al., arXiv:1508.03619):
    ``edge_factor * 2**scale`` undirected edges whose endpoints are drawn
    uniformly over ``2**scale`` vertices, then symmetrised and squished
    (self loops and duplicates dropped), as GAP's ``-u -k`` generator does."""
    n = 1 << scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    ends = rng.integers(0, n, size=(2, m), dtype=np.int64)
    return _symmetric_simple(n, ends[0], ends[1])


def rgg(seed: int, *, scale: int, radius_coeff: float):
    """DIMACS-10 random geometric graph ``rgg_n_2_<scale>``: ``2**scale``
    points uniform in the unit square, an edge between every pair closer
    than ``radius_coeff * sqrt(ln n / n)`` (0.55 in DIMACS-10).

    Vectorised cell binning: cells of side >= r, so every neighbour of a
    point lies in its own cell or one of the eight around it; each point
    is compared with the points of its own cell and of four neighbouring
    cells (the other four see the pair from the far side)."""
    n = 1 << scale
    r = radius_coeff * np.sqrt(np.log(n) / n)
    rng = np.random.default_rng(seed)
    pts = rng.random((n, 2))
    ncell = max(1, int(1.0 / r))
    cxy = np.minimum((pts * ncell).astype(np.int64), ncell - 1)
    cell = cxy[:, 0] * ncell + cxy[:, 1]
    order = np.argsort(cell, kind="stable")
    cell_sorted = cell[order]
    bounds = np.searchsorted(cell_sorted, np.arange(ncell * ncell + 1))
    us, vs = [], []
    for dx, dy in ((0, 0), (1, -1), (1, 0), (1, 1), (0, 1)):
        nx, ny = cxy[:, 0] + dx, cxy[:, 1] + dy
        ok = (nx >= 0) & (nx < ncell) & (ny >= 0) & (ny < ncell)
        me = np.flatnonzero(ok)
        other = nx[me] * ncell + ny[me]
        lo, hi = bounds[other], bounds[other + 1]
        cnt = hi - lo
        a = np.repeat(me, cnt)
        offs = np.arange(int(cnt.sum())) - np.repeat(np.cumsum(cnt) - cnt, cnt)
        b = order[np.repeat(lo, cnt) + offs]
        d2 = ((pts[a] - pts[b]) ** 2).sum(axis=1)
        close = d2 <= r * r
        if dx == 0 and dy == 0:
            close &= a < b
        us.append(a[close])
        vs.append(b[close])
    return _symmetric_simple(n, np.concatenate(us), np.concatenate(vs))


FAMILIES = {"urand": urand, "rgg": rgg}


def make(graph_spec: dict):
    """``(n, src, dst)`` for a configuration's ``graph`` block."""
    params = dict(graph_spec)
    family, seed = params.pop("family"), int(params.pop("seed"))
    if family not in FAMILIES:
        raise ValueError(f"no generator for graph family {family!r}; "
                         f"known: {sorted(FAMILIES)}")
    return FAMILIES[family](seed, **params)
