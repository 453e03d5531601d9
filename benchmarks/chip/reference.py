"""The plain reference: breadth-first search in numpy, and the answers of
the query kinds the cells serve, computed from it.

It imports nothing of the program.  Sources are traversed 64 at a time,
one bit of a uint64 word per source, level by level: a level expands the
frontier's out-edges (push) while they are few, and otherwise has every
vertex pull its in-neighbours' frontier words; both give the same sets.

``traverse(..., stop_early=True)`` is the control: the reference with one
guarantee of the configurations broken, exact answers.  It leaves the
last level that discovers anything undiscovered, and the comparison must
reject what it gives.
"""
from __future__ import annotations

import dataclasses

import numpy as np

WORD = 64
_BITS = np.uint64(1) << np.arange(WORD, dtype=np.uint64)


class Csr:
    """Out- and in-adjacency of a directed edge list."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray):
        self.n = int(n)
        self.m = int(src.size)
        self.out_ptr, self.out_col = _csr(self.n, src, dst)
        self.in_ptr, self.in_col = _csr(self.n, dst, src)
        self.out_deg = np.diff(self.out_ptr)
        self.in_rows = np.flatnonzero(np.diff(self.in_ptr))


def _csr(n, rows, cols):
    order = np.argsort(rows, kind="stable")
    ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=ptr[1:])
    return ptr, np.ascontiguousarray(cols[order]).astype(np.int64)


def _expand(ptr, col, verts):
    """Concatenated neighbour lists of ``verts`` and each entry's owner."""
    starts = ptr[verts]
    cnt = ptr[verts + 1] - starts
    total = int(cnt.sum())
    owner = np.repeat(np.arange(verts.size), cnt)
    offs = np.arange(total) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    return col[starts[owner] + offs], owner


def _next_words(g: Csr, frontier: np.ndarray) -> np.ndarray:
    active = np.flatnonzero(frontier)
    nxt = np.zeros(g.n, np.uint64)
    if int(g.out_deg[active].sum()) * 2 < g.m:
        nbr, owner = _expand(g.out_ptr, g.out_col, active)
        np.bitwise_or.at(nxt, nbr, frontier[active][owner])
        return nxt
    if g.m:
        nxt[g.in_rows] = np.bitwise_or.reduceat(frontier[g.in_col],
                                                g.in_ptr[g.in_rows])
    return nxt


@dataclasses.dataclass
class Traversal:
    """What the reference found from each of ``k`` sources: the sum of
    depths and the count of reached vertices, the depth of each source's
    target (-1 where unreached or no target), and the full depth rows
    (-1 where unreached) of the sources asked for."""

    far: np.ndarray
    reach: np.ndarray
    target_depth: np.ndarray
    rows: dict


def traverse(g: Csr, sources, targets=None, keep=(), *,
             stop_early: bool = False) -> Traversal:
    """BFS from every source; ``targets`` (-1 for none) and ``keep`` (the
    indices whose depth rows are kept) are per source.  ``stop_early``
    leaves the last level that discovers anything undiscovered."""
    sources = np.asarray(sources, np.int64)
    k_all = sources.size
    targets = (np.full(k_all, -1, np.int64) if targets is None
               else np.asarray(targets, np.int64))
    out = Traversal(far=np.zeros(k_all, np.int64),
                    reach=np.ones(k_all, np.int64),
                    target_depth=np.where(targets == sources, 0, -1),
                    rows={})
    keep = set(int(i) for i in keep)
    for i in keep:
        out.rows[i] = np.full(g.n, -1, np.int32)
        out.rows[i][sources[i]] = 0
    for b0 in range(0, k_all, WORD):
        batch = sources[b0:b0 + WORD]
        k = batch.size
        tgt = targets[b0:b0 + k]
        watch = np.flatnonzero(tgt >= 0)
        kept = [j for j in range(k) if b0 + j in keep]
        frontier = np.zeros(g.n, np.uint64)
        np.bitwise_or.at(frontier, batch, _BITS[:k])
        visited = frontier.copy()
        depth = 0
        while frontier.any():
            depth += 1
            new = _next_words(g, frontier) & ~visited
            if stop_early:
                # keep only the lanes that discover more at the next level
                new &= np.bitwise_or.reduce(_next_words(g, new)
                                            & ~(visited | new))
            visited |= new
            idx = np.flatnonzero(new)
            bits = np.unpackbits(new[idx].view(np.uint8).reshape(-1, 8),
                                 axis=1, bitorder="little")[:, :k]
            cnt = bits.sum(axis=0, dtype=np.int64)
            out.reach[b0:b0 + k] += cnt
            out.far[b0:b0 + k] += depth * cnt
            if watch.size:
                hit = (new[tgt[watch]] & _BITS[watch]) != 0
                td = out.target_depth[b0:b0 + k]
                td[watch[hit & (td[watch] < 0)]] = depth
            for j in kept:
                out.rows[b0 + j][idx[bits[:, j] != 0]] = depth
            frontier = new
    return out


def answer(kind: str, t: Traversal, i: int, n: int) -> dict:
    """The reference's answer to the ``i``-th query of a traversal."""
    if kind == "bfs":
        return {"levels": t.rows[i]}
    far, reach = int(t.far[i]), int(t.reach[i])
    if kind == "closeness":
        return {"far": far, "reach": reach,
                "closeness": float((n - 1) / far) if far > 0 else 0.0}
    if kind == "distance":
        d = int(t.target_depth[i])
        return {"distance": None if d < 0 else d}
    if kind == "reach":
        return {"reach": reach}
    raise ValueError(f"the reference has no answer for kind {kind!r}")
