"""The packed dense level's destination-major gather-OR (DESIGN.md §11.2):
the slot table holds every real slot once and pads with zero slots and
the zero chunk, its chunk width follows the graph's slices per row, and
the gather over the jnp or Pallas (interpret) pull equals the scatter-OR
reference bit for bit."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import graphs
from repro.kernels.gather_or import (CHUNK_WIDTHS, chunk_width, gather_or,
                                     slot_table)
from repro.kernels.pull_ms_packed import (lanes_of, pull_ms_packed_lanes,
                                          pull_ms_packed_ref)
from repro.kernels.pull_scatter_ms_packed import pull_scatter_ms_packed_ref
from repro.serve.bfs_engine import build_artifacts

SIGMA, TAU = 8, 128


class _Grid:
    """A synthetic padded slot grid shaped like ``BvssDevice``: real VSSs
    with some empty slots (zero mask, sentinel row ``n_pad``), padding
    VSSs (zero masks, sentinel row and set), one hot row that receives
    more slices than the widest chunk, and rows shared across VSSs."""

    def __init__(self, seed: int):
        rng = np.random.default_rng(seed)
        self.n_pad = 64
        self.n_ext = self.n_pad + SIGMA
        self.num_sets = self.n_pad // SIGMA
        self.num_sets_ext = self.num_sets + 1
        num_vss, num_vss_pad = 12, 16
        masks = rng.integers(1, 256, (num_vss_pad, TAU)).astype(np.uint8)
        rows = rng.integers(0, self.n_pad, (num_vss_pad, TAU))
        rows[:, : 2 * CHUNK_WIDTHS[-1] // num_vss + 1] = 3   # the hot row
        empty = rng.random((num_vss_pad, TAU)) < 0.3
        masks[empty] = 0
        rows[empty] = self.n_pad
        masks[num_vss:] = 0
        rows[num_vss:] = self.n_pad
        v2r = rng.integers(0, self.num_sets, num_vss_pad)
        v2r[num_vss:] = self.num_sets
        self.masks = jnp.asarray(masks)
        self.row_ids = jnp.asarray(rows.astype(np.int32))
        self.v2r = jnp.asarray(v2r.astype(np.int32))


def _grids():
    bd = build_artifacts("g", graphs.make("kron", scale=7, seed=0)).bd
    return {"synthetic": _Grid(0), "kron": bd}


@pytest.fixture(scope="module")
def grids():
    return _grids()


def _frontier(kind, g, kw, rng):
    shape = (g.num_sets_ext, SIGMA, kw)
    if kind == "empty":
        return jnp.zeros(shape, jnp.uint32)
    if kind == "full":
        return jnp.full(shape, 0xFFFFFFFF, jnp.uint32)
    f = rng.integers(0, 2**32, shape, dtype=np.uint32)
    f[-1] = 0  # the sentinel set is never in the frontier
    return jnp.asarray(f & rng.integers(0, 2**32, shape, dtype=np.uint32))


@pytest.mark.parametrize("grid", ["synthetic", "kron"])
@pytest.mark.parametrize("kw", [1, 2])
@pytest.mark.parametrize("frontier", ["random", "empty", "full"])
@pytest.mark.parametrize("pull", ["jnp", "pallas"])
def test_gather_or_matches_scatter_reference(grids, grid, kw, frontier,
                                             pull):
    g = grids[grid]
    rng = np.random.default_rng(kw)
    v = jnp.asarray(rng.integers(0, 2**32, (g.n_ext, kw), dtype=np.uint32)
                    & np.uint32(0x11111111))
    f = _frontier(frontier, g, kw, rng)
    want = pull_scatter_ms_packed_ref(v, g.masks, f, g.v2r,
                                      g.row_ids.reshape(-1), sigma=SIGMA)
    if pull == "pallas":
        marks = pull_ms_packed_lanes(g.masks, f, g.v2r, sigma=SIGMA,
                                     interpret=True)
    else:
        marks = lanes_of(pull_ms_packed_ref(g.masks, f[g.v2r], sigma=SIGMA))
    t = slot_table(np.asarray(g.row_ids), np.asarray(g.masks), g.n_ext, kw)
    got = v | gather_or(marks, jnp.asarray(t.chunks), jnp.asarray(t.rows), kw)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert (np.asarray(got) == np.asarray(want)).all()
    if frontier == "empty":
        assert (np.asarray(got) == np.asarray(v)).all()


@pytest.mark.parametrize("grid", ["synthetic", "kron"])
@pytest.mark.parametrize("kw", [1, 2])
def test_slot_table_holds_every_real_slot_once(grids, grid, kw):
    g = grids[grid]
    masks, rows = np.asarray(g.masks), np.asarray(g.row_ids)
    tau = masks.shape[1]
    t = slot_table(rows, masks, g.n_ext, kw)
    chunks, chunk_ids = t.chunks.T, t.rows.T   # one chunk, one row a line
    # a word-0 mark index back to its slot q*tau + j
    slot_of = (chunks // (kw * tau)) * tau + chunks % tau
    assert (chunks % (kw * tau) < tau).all()
    zero_chunk = chunks.shape[0] - 1
    real = masks.reshape(-1) != 0
    pad = ~real[slot_of]
    assert pad[zero_chunk].all()          # the zero chunk is all padding
    listed = slot_of[~pad]
    assert np.array_equal(np.sort(listed), np.flatnonzero(real))
    # every real chunk belongs to exactly one row, and every slot in it
    # ORs into that row; padding in stage 2 names the zero chunk
    owner = np.full(zero_chunk, -1)
    for r, cs in enumerate(chunk_ids):
        for c in cs:
            if c == zero_chunk:
                continue
            assert owner[c] == -1
            owner[c] = r
    assert (owner >= 0).all()
    dest = rows.reshape(-1)[slot_of[:-1]]
    assert (dest == owner[:, None])[~pad[:-1]].all()
    # no row holds more chunks than it needs
    counts = np.bincount(rows.reshape(-1)[real], minlength=g.n_ext)
    d = chunks.shape[1]
    assert ((chunk_ids != zero_chunk).sum(axis=1) == -(-counts // d)).all()
    assert chunk_ids.shape[1] == max(1, int((-(-counts // d)).max()))
    assert t.entries == t.chunks.size + t.rows.size


def _counts(case: str, n: int = 256) -> np.ndarray:
    """Slices each of ``n`` rows receives: ``uniform<p>`` or a hub row of
    1000 slices among rows of one."""
    if case == "hub":
        return np.r_[1000, np.ones(n - 1, np.int64)]
    return np.full(n, int(case.removeprefix("uniform")))


@pytest.mark.parametrize("case,want", [
    ("uniform3", 8), ("uniform12", 16), ("uniform31", 32), ("uniform64", 64),
    ("hub", 32)])
def test_chunk_width_rule(case, want):
    """The least-cost D: padding pushes it down, a hot row's stage-2
    columns push it up."""
    counts = np.r_[_counts(case), np.zeros(SIGMA, np.int64)]
    assert chunk_width(counts) == want


@pytest.mark.parametrize("case", ["uniform3", "uniform12", "uniform31",
                                  "uniform64", "hub"])
def test_slot_table_width_follows_the_graph(case):
    """A slot grid whose rows receive ``case``'s slices gets the rule's D
    and one chunk per D slices of each row, plus the zero chunk."""
    counts = _counts(case)
    n = counts.size
    rows = np.repeat(np.arange(n), counts)
    pad = (-rows.size) % TAU + TAU             # at least one padding VSS
    masks = np.concatenate([np.ones(rows.size, np.uint8),
                            np.zeros(pad, np.uint8)]).reshape(-1, TAU)
    row_ids = np.concatenate([rows, np.full(pad, n)]).reshape(-1, TAU)
    t = slot_table(row_ids.astype(np.int32), masks, n + SIGMA, 1)
    d = chunk_width(np.r_[counts, np.zeros(SIGMA, np.int64)])
    assert t.chunks.shape == (d, int((-(-counts // d)).sum()) + 1)
    assert t.rows.shape == (int(-(-counts.max() // d)), n + SIGMA)
