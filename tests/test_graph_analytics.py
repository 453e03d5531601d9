"""Property-based differential tests for the graph-analytics family
(DESIGN.md §15): seeded random graphs — directed scale-free, stars,
rings, disconnected unions, isolated-vertex-heavy, prime-sized n —
comparing the packed implementations (``core/components.py``,
``core/mis.py``, ``core/triangles.py``) against slow pure-numpy
references, plus an engine-in-the-loop differential that serves the same
queries through the full ticket/session path.

Scaled by ``REPRO_PARITY_CASES`` like tests/test_kernel_parity.py; the
graph generator draws ``n`` from a fixed pool so jit retraces stay
bounded (one trace per distinct (n, words) shape)."""
import os

import numpy as np
import pytest

from repro.core import components, mis, ref_bfs, triangles
from repro.core.graph import from_edges
from repro.data import graphs
from repro.serve import workloads
from repro.serve.bfs_engine import BfsEngine

from hypothesis_shim import given_seeds

CASES = int(os.environ.get("REPRO_PARITY_CASES", "200"))

# n pool bounds distinct jit shapes; 211 is prime (misaligned word tail),
# unions below compose to in-pool sizes only
N_POOL = [16, 32, 48, 64, 96, 128, 211]
_UNIONS = [(16, 16, 16), (16, 32, 0), (32, 32, 0), (64, 32, 0),
           (64, 64, 0)]


def random_graph(seed: int):
    """One of six structurally distinct families, seed-deterministic."""
    rng = np.random.default_rng(seed)
    pick = int(rng.integers(0, 6))
    if pick == 0:    # directed scale-free (cc takes the union-find path)
        return graphs.rmat(int(rng.integers(4, 7)), edge_factor=8,
                           seed=seed)
    if pick == 1:    # hub-and-spoke: extreme degree skew
        return graphs.star(int(N_POOL[rng.integers(0, 4)]))
    if pick == 2:    # cycle: maximal diameter
        return graphs.ring(int(N_POOL[rng.integers(0, 4)]))
    if pick == 3:    # disconnected union of two graphs + isolated tail
        n1, n2, iso = _UNIONS[int(rng.integers(0, len(_UNIONS)))]
        g1 = graphs.rmat(int(np.log2(n1)), edge_factor=4, seed=seed)
        g2 = graphs.ring(n2)
        return from_edges(
            np.concatenate([g1.src, g2.src + n1]),
            np.concatenate([g1.dst, g2.dst + n1]), n=n1 + n2 + iso)
    if pick == 4:    # sparse uniform: plenty of isolated vertices
        n = int(N_POOL[rng.integers(2, len(N_POOL))])
        return graphs.uniform_random(n, n // 2, seed=seed)
    # prime-sized n, moderate density
    return graphs.uniform_random(211, int(rng.integers(200, 800)),
                                 seed=seed)


# ------------------------------------------------ core packed vs numpy ----
@given_seeds(max(8, CASES // 4))
def test_cc_packed_matches_union_find(seed):
    """Union-on-collision MS-BFS labels == union-find labels, bit-for-bit,
    at several lane widths; labels are canonical min-id per component."""
    g = random_graph(seed)
    ref = components.connected_components_ref(g)
    kappa = int(np.random.default_rng(seed + 1).choice([1, 8, 32]))
    got = components.connected_components_packed(g, kappa=kappa)
    assert np.array_equal(ref, got), (seed, kappa)
    # canonical-label structure: label <= own id, labels are fixpoints
    assert (ref <= np.arange(g.n)).all()
    assert np.array_equal(ref[ref], ref)
    # size consistency: the distinct components partition the vertex set
    sizes = components.component_sizes(ref)
    assert (sizes >= 1).all()
    assert int(sizes[np.unique(ref)].sum()) == g.n


@given_seeds(max(8, CASES // 4))
def test_mis_packed_matches_luby_ref(seed):
    """Bit-serial packed Luby == numpy Luby on identical rounds, and the
    result is independent + maximal (seed-free invariants)."""
    g = random_graph(seed)
    s = seed % 5
    ref = mis.mis_ref(g, seed=s)
    got = mis.mis_packed(g, seed=s)
    assert np.array_equal(ref, got), (seed, s, np.flatnonzero(ref != got))
    mis.mis_verify(g, got)


@given_seeds(max(8, CASES // 4))
def test_tpv_matches_dense_ref(seed):
    """Batched AND+popcount per-vertex triangle counts == the dense
    matrix formula; totals agree with the whole-graph counter and the
    on-demand single-vertex path agrees pointwise."""
    g = random_graph(seed)
    ref = triangles.triangles_per_vertex_ref(g)
    got = triangles.triangles_per_vertex(g, batch=256)
    assert np.array_equal(ref, got), seed
    assert int(ref.sum()) // 3 == triangles.triangle_count(g)
    st = triangles.TpvState(g)
    rng = np.random.default_rng(seed + 2)
    for v in rng.integers(0, g.n, 4):
        assert triangles.triangles_of_vertex(st, int(v)) == int(ref[v])
    # the sparse single-vertex oracle (verify_result's) == the dense one
    csr = g.symmetrized().csr
    assert [triangles.triangles_of_vertex_ref(csr, v)
            for v in range(g.n)] == ref.tolist(), seed


# ------------------------------------------- engine-in-the-loop parity ----
@given_seeds(max(4, CASES // 33))
def test_engine_analytics_differential(seed):
    """cc/mis/tpv served through the full ticket/session/scheduler path
    on a random graph match the pure-numpy references (the engine builds
    are the expensive part, so fewer seeds than the core properties)."""
    g = random_graph(seed)
    rng = np.random.default_rng(seed + 3)
    eng = BfsEngine(layout=["byteplane", "packed"][seed % 2],
                    use_pallas=False, switching="off",
                    megatick=[1, 4][(seed // 2) % 2], kappa=32)
    eng.register_graph("g", g)
    want = [eng.submit("g", int(rng.integers(0, g.n)), kind=kind)
            for kind in ("cc", "mis", "tpv") for _ in range(2)]
    res = eng.run()
    for t in want:
        q = t.query
        workloads.verify_result(res[int(t)], q,
                                ref_bfs.bfs_levels(g, q.source),
                                unreached=ref_bfs.UNREACHED, graph=g)


# ----------------------------------------------------- validation gaps ----
def test_verify_result_requires_graph_for_analytics_kinds():
    g = graphs.ring(16)
    lv = ref_bfs.bfs_levels(g, 0)
    for kind in ("cc", "mis", "tpv"):
        q = workloads.BfsQuery(rid=0, graph="g", source=0, kind=kind)
        res = workloads.BfsResult(
            rid=0, graph="g", source=0, kind=kind, levels=None, far=0,
            reach=0, closeness=None, admitted_at_level=0)
        with pytest.raises(ValueError, match="needs graph="):
            workloads.verify_result(res, q, lv,
                                    unreached=ref_bfs.UNREACHED)


def test_cc_kappa_validation():
    with pytest.raises(ValueError):
        components.connected_components_packed(graphs.ring(8), kappa=0)


def test_mis_seed_changes_set_but_not_validity():
    """Different seeds may pick different maximal independent sets; each
    is exactly reproduced by its reference and always valid."""
    g = graphs.rmat(5, seed=7)
    sets = []
    for s in range(3):
        got = mis.mis_packed(g, seed=s)
        assert np.array_equal(got, mis.mis_ref(g, seed=s))
        mis.mis_verify(g, got)
        sets.append(tuple(np.flatnonzero(got)))
    assert len(set(sets)) > 1, "three seeds all chose the identical MIS"
