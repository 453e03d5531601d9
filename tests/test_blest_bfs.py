"""SS-BFS correctness: every driver mode x update mechanics x layout against
the numpy CSR oracle."""
import numpy as np
import pytest

from repro.core import blest, ref_bfs
from repro.core.bvss import BvssConfig, build_bvss
from repro.core.graph import from_edges
from repro.data import graphs

FAMILIES = ["kron", "road", "rgg", "urand", "social"]


@pytest.fixture(scope="module")
def suite():
    out = {}
    for fam in FAMILIES:
        g = graphs.make(fam, scale=8, seed=0)
        out[fam] = (g, blest.to_device(build_bvss(g)))
    return out


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("lazy", [True, False])
def test_fused_matches_oracle(suite, family, lazy):
    g, bd = suite[family]
    for src in (0, g.n // 3, g.n - 1):
        want = ref_bfs.bfs_levels(g, src)
        got = np.asarray(blest.bfs_fused(bd, src, lazy=lazy))
        assert (got == want).all()


@pytest.mark.parametrize("family", ["kron", "road"])
@pytest.mark.parametrize("packed", [True, False])
def test_packed_layout_equivalent(suite, family, packed):
    g, bd = suite[family]
    want = ref_bfs.bfs_levels(g, 1)
    got = np.asarray(blest.bfs_fused(bd, 1, packed=packed))
    assert (got == want).all()


@pytest.mark.parametrize("family", FAMILIES)
def test_bucketed_matches_oracle(suite, family):
    g, bd = suite[family]
    runner = blest.BucketedBfs(bd)
    want = ref_bfs.bfs_levels(g, 2)
    assert (np.asarray(runner(2)) == want).all()


@pytest.mark.parametrize("eta", [None, 0.5, 10.0, float("inf")])
def test_switching_eta_never_changes_result(suite, eta):
    """Property: Eq.(6) switching is performance-only, never correctness."""
    g, bd = suite["kron"]
    want = ref_bfs.bfs_levels(g, 0)
    runner = blest.BucketedBfs(bd, eta=eta)
    assert (np.asarray(runner(0)) == want).all()


def test_unreachable_vertices():
    g = from_edges([0, 1, 3], [1, 2, 4], n=8)  # 5,6,7 isolated; 3,4 separate
    bd = blest.to_device(build_bvss(g))
    got = np.asarray(blest.bfs_fused(bd, 0))
    want = ref_bfs.bfs_levels(g, 0)
    assert (got == want).all()
    assert got[5] == blest.UNREACHED and got[3] == blest.UNREACHED


def test_single_vertex_frontier_terminates():
    g = from_edges([0], [1], n=4)
    bd = blest.to_device(build_bvss(g))
    got = np.asarray(blest.bfs_fused(bd, 1))  # vertex 1 has no out-edges
    assert got[1] == 0 and (got[[0, 2, 3]] == blest.UNREACHED).all()


def test_jit_cache_reused_across_sources(suite):
    g, bd = suite["kron"]
    f = blest.FusedBfs(bd)
    for src in (0, 1, 2):
        assert (np.asarray(f(src)) == ref_bfs.bfs_levels(g, src)).all()


@pytest.mark.parametrize("sigma,tau", [(8, 32), (4, 64)])
def test_nondefault_bvss_geometry(sigma, tau):
    g = graphs.make("kron", scale=7, seed=4)
    bd = blest.to_device(build_bvss(g, BvssConfig(sigma=sigma, tau=tau)))
    want = ref_bfs.bfs_levels(g, 0)
    got = np.asarray(blest.bfs_fused(bd, 0, packed=(tau % 4 == 0)))
    assert (got == want).all()


def test_levels_are_valid_bfs_labelling(suite):
    g, bd = suite["rgg"]
    got = np.asarray(blest.bfs_fused(bd, 0))
    assert ref_bfs.bfs_parents_valid(g, 0, got)


@pytest.mark.parametrize("seed", range(4))
def test_gather_ranges_matches_loop(seed):
    """The oracle's vectorized neighbour gather equals concatenating the
    CSR ranges one by one, empty ranges included."""
    rng = np.random.default_rng(seed)
    cols = rng.integers(0, 1 << 20, 5000).astype(np.int32)
    starts = rng.integers(0, 5000, 1500)
    lens = rng.integers(0, 9, 1500) * (rng.random(1500) < 0.8)  # some empty
    ends = np.minimum(starts + lens, 5000)
    want = np.concatenate([cols[s:e] for s, e in zip(starts, ends)])
    got = ref_bfs._gather_ranges(cols, starts, ends, int((ends - starts).sum()))
    assert got.dtype == want.dtype and np.array_equal(got, want)


# --------------------------------------------------------------------------
# property: driver equivalence on random digraphs (hypothesis, optional)
# --------------------------------------------------------------------------
from hypothesis_shim import given, settings, st  # noqa: E402


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(16, 80), st.integers(1, 4))
def test_all_drivers_agree_on_random_digraphs(seed, n, density):
    """fused(lazy) == fused(eager) == bucketed == oracle on arbitrary
    random digraphs, from an arbitrary source."""
    rng = np.random.default_rng(seed)
    m = n * density
    g = from_edges(rng.integers(0, n, m), rng.integers(0, n, m), n=n)
    bd = blest.to_device(build_bvss(g))
    src = int(rng.integers(0, n))
    want = ref_bfs.bfs_levels(g, src)
    assert (np.asarray(blest.bfs_fused(bd, src, lazy=True)) == want).all()
    assert (np.asarray(blest.bfs_fused(bd, src, lazy=False,
                                       packed=False)) == want).all()
    assert (np.asarray(blest.BucketedBfs(bd)(src)) == want).all()
