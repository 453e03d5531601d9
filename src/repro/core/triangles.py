"""Triangle counting over the (popc, AND) semiring (paper §6.3).

The paper identifies triangle counting as TC-suitable: the transmitted
information is a single bit per (neighbour, neighbour) pair, and the count
is a popcount —

    triangles = (1/6) * sum_{(u,v) in E} popc(row_u & row_v)

for undirected graphs (each triangle counted once per ordered edge per
corner).  Rows are the packed bit-adjacency (n x n/32 uint32); the
intersection popcount runs at full VPU width with
``jax.lax.population_count`` — the same packed-word machinery as the BVSS
pull kernels.  Memory is O(n^2/8) bits, so this module targets the
container-scale graphs of the benchmark suite; a production variant would
tile rows through the BVSS structure (noted in DESIGN.md §9).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph


def packed_adjacency(g: Graph) -> np.ndarray:
    """Symmetrized packed bit-adjacency (n, ceil(n/32)) uint32."""
    gs = g.symmetrized()
    words = (g.n + 31) // 32
    rows = np.zeros((g.n, words), np.uint32)
    np.bitwise_or.at(rows, (gs.src, gs.dst // 32),
                     np.uint32(1) << (gs.dst % 32).astype(np.uint32))
    return rows


@jax.jit
def _count_edge_intersections(rows: jax.Array, src: jax.Array,
                              dst: jax.Array) -> jax.Array:
    a = rows[src]          # (m, words)
    b = rows[dst]
    return jax.lax.population_count(a & b).astype(jnp.int32).sum()


def triangle_count(g: Graph, batch: int = 1 << 14) -> int:
    """Exact triangle count via packed AND+popcount over edges."""
    rows = jnp.asarray(packed_adjacency(g))
    gs = g.symmetrized()
    src = np.asarray(gs.src)
    dst = np.asarray(gs.dst)
    total = 0
    for off in range(0, len(src), batch):
        s = jnp.asarray(src[off : off + batch])
        d = jnp.asarray(dst[off : off + batch])
        total += int(_count_edge_intersections(rows, s, d))
    # each triangle is counted at both endpoints of each of its 3 edges
    assert total % 6 == 0, "symmetrized graph must 6-count triangles"
    return total // 6


def triangle_count_ref(g: Graph) -> int:
    """Oracle: dense boolean matrix trace formula (small graphs only)."""
    a = np.zeros((g.n, g.n), dtype=bool)
    gs = g.symmetrized()
    a[gs.src, gs.dst] = True
    a2 = (a.astype(np.int64) @ a.astype(np.int64))
    return int((a2 * a).sum() // 6)


# ---------------------------------------------------------------------------
# Per-vertex triangle counts (the serve engine's `tpv` kind, DESIGN.md §15.1)
# ---------------------------------------------------------------------------


@jax.jit
def _edge_intersection_counts(rows: jax.Array, src: jax.Array,
                              dst: jax.Array) -> jax.Array:
    """Per-edge |N(src) ∩ N(dst)| — the batched form of
    :func:`_count_edge_intersections` without the final reduction."""
    a = rows[src]
    b = rows[dst]
    return jax.lax.population_count(a & b).astype(jnp.int32).sum(-1)


def triangles_per_vertex(g: Graph, batch: int = 1 << 14) -> np.ndarray:
    """(n,) int64 triangle incidences per vertex via batched AND+popcount:
    summing |N(v) ∩ N(u)| over v's neighbours u counts each triangle at v
    twice (once per incident edge), so the per-vertex total halves."""
    rows = jnp.asarray(packed_adjacency(g))
    gs = g.symmetrized()
    src = np.asarray(gs.src)
    dst = np.asarray(gs.dst)
    per_edge = np.empty(len(src), np.int64)
    for off in range(0, len(src), batch):
        s = jnp.asarray(src[off : off + batch])
        d = jnp.asarray(dst[off : off + batch])
        per_edge[off : off + batch] = np.asarray(
            _edge_intersection_counts(rows, s, d))
    per_v = np.bincount(src, weights=per_edge, minlength=g.n).astype(np.int64)
    assert (per_v % 2 == 0).all(), "symmetrized graph must 2-count per vertex"
    return per_v // 2


def triangles_per_vertex_ref(g: Graph) -> np.ndarray:
    """Oracle: dense boolean matrix formula, per-vertex row of the trace."""
    a = np.zeros((g.n, g.n), dtype=bool)
    gs = g.symmetrized()
    a[gs.src, gs.dst] = True
    a2 = a.astype(np.int64) @ a.astype(np.int64)
    return (a2 * a).sum(axis=1) // 2


def triangles_of_vertex_ref(sym_csr, v: int) -> int:
    """Oracle for one vertex: row ``v`` of :func:`triangles_per_vertex_ref`,
    ``sum_{u in N(v)} |N(v) & N(u)| // 2``, from the symmetrized CSR
    (``g.symmetrized().csr``: rows deduplicated, self-loops kept).  Host
    memory O(n + m), so it checks graphs whose dense n x n matrix does not
    fit."""
    ptrs, cols = sym_csr
    nv = cols[ptrs[v]:ptrs[v + 1]]
    total = sum(np.intersect1d(nv, cols[ptrs[u]:ptrs[u + 1]],
                               assume_unique=True).size for u in nv)
    return total // 2


class TpvState:
    """Per-graph device state for on-demand single-vertex triangle queries
    (the serve engine's ``tpv`` graph state, DESIGN.md §15.2): the packed
    adjacency with a zero row appended at index n (the gather pad — padded
    neighbour slots intersect nothing), plus the symmetrized CSR."""

    __slots__ = ("n", "rows_ext", "ptrs", "cols")

    def __init__(self, g: Graph):
        self.n = g.n
        rows = packed_adjacency(g)
        self.rows_ext = jnp.asarray(
            np.vstack([rows, np.zeros((1, rows.shape[1]), np.uint32)]))
        self.ptrs, self.cols = g.symmetrized().csr


@jax.jit
def _vertex_triangles(rows_ext: jax.Array, v: jax.Array,
                      nbrs: jax.Array) -> jax.Array:
    inter = rows_ext[nbrs] & rows_ext[v][None, :]
    return jax.lax.population_count(inter).astype(jnp.int32).sum()


def triangles_of_vertex(state: TpvState, v: int) -> int:
    """One vertex's triangle count from a :class:`TpvState`: gather the
    neighbour rows (padded to the next power of two with the zero row, so
    jit retraces are bounded by log2(max degree)) and AND against row v."""
    lo, hi = int(state.ptrs[v]), int(state.ptrs[v + 1])
    deg = hi - lo
    if deg == 0:
        return 0
    cap = 1 << (deg - 1).bit_length()
    nbrs = np.full(cap, state.n, np.int64)
    nbrs[:deg] = state.cols[lo:hi]
    total = int(_vertex_triangles(state.rows_ext, jnp.asarray(v),
                                  jnp.asarray(nbrs)))
    assert total % 2 == 0
    return total // 2
