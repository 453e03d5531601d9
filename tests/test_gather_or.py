"""The packed dense level's destination-major gather-OR (DESIGN.md §11.2):
the slot table holds every real slot once, with its parent slice set and
mask, pads with zero masks and the zero chunk, its chunk width follows the
graph's slices per row at the chip's per-row and per-index costs, and the
gather of parent frontier tiles equals the scatter-OR reference bit for
bit."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import graphs
from repro.kernels import gather_or as gather_or_mod
from repro.kernels.gather_or import (CHUNK_WIDTHS, chunk_width, gather_or,
                                     slot_table)
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


def _force_width(monkeypatch, width):
    """``width`` "rule" keeps the D rule and block size; a number forces
    that D, in blocks of one vector row of columns (many blocks)."""
    if width != "rule":
        monkeypatch.setattr(gather_or_mod, "CHUNK_WIDTHS", (width,))
        monkeypatch.setattr(gather_or_mod, "BLOCK_ROWS", width * 128)


def _table(g, monkeypatch, width):
    _force_width(monkeypatch, width)
    return slot_table(np.asarray(g.row_ids), np.asarray(g.masks),
                      np.asarray(g.v2r), g.n_ext)


@pytest.mark.parametrize("grid", ["synthetic", "kron"])
@pytest.mark.parametrize("kw", [1, 2])
@pytest.mark.parametrize("frontier", ["random", "empty", "full"])
@pytest.mark.parametrize("width", ["rule", 8, 128])
def test_gather_or_matches_scatter_reference(grids, grid, kw, frontier,
                                             width, monkeypatch):
    g = grids[grid]
    rng = np.random.default_rng(kw)
    v = jnp.asarray(rng.integers(0, 2**32, (g.n_ext, kw), dtype=np.uint32)
                    & np.uint32(0x11111111))
    f = _frontier(frontier, g, kw, rng)
    want = pull_scatter_ms_packed_ref(v, g.masks, f, g.v2r,
                                      g.row_ids.reshape(-1), sigma=SIGMA)
    t = _table(g, monkeypatch, width)
    if width != "rule":
        assert t.parents.shape[0] == width
    got = v | gather_or(f, jnp.asarray(t.parents), jnp.asarray(t.masks),
                        jnp.asarray(t.rows))
    assert got.dtype == want.dtype and got.shape == want.shape
    assert (np.asarray(got) == np.asarray(want)).all()
    if frontier == "empty":
        assert (np.asarray(got) == np.asarray(v)).all()


@pytest.mark.parametrize("grid", ["synthetic", "kron"])
@pytest.mark.parametrize("width", ["rule", 8])
def test_slot_table_holds_every_real_slot_once(grids, grid, width,
                                               monkeypatch):
    g = grids[grid]
    masks, rows = np.asarray(g.masks), np.asarray(g.row_ids)
    v2r = np.asarray(g.v2r)
    t = _table(g, monkeypatch, width)
    parents, slot_masks = t.parents.T, t.masks.T   # one chunk a line
    chunk_ids = t.rows.T                           # one row a line
    d = parents.shape[1]
    assert t.parents.dtype == np.int32 and t.masks.dtype == np.uint8
    assert t.masks.shape == t.parents.shape
    # whole blocks of whole vector rows of columns
    blk = gather_or_mod._block_cols(d, parents.shape[0])
    assert blk % 128 == 0 and parents.shape[0] % blk == 0
    assert blk * d <= gather_or_mod.BLOCK_ROWS
    # every real chunk belongs to exactly one row; padding in stage 2
    # names the zero chunk, which holds zero masks like every column after
    zero_chunk = int(chunk_ids.max())
    assert (slot_masks[zero_chunk:] == 0).all()
    owner = np.full(zero_chunk, -1)
    for r, cs in enumerate(chunk_ids):
        for c in cs:
            if c == zero_chunk:
                continue
            assert owner[c] == -1
            owner[c] = r
    assert (owner >= 0).all()
    # each real slot once, in a chunk of its own row, with its parent set
    # and mask; padding entries have zero masks
    real = masks != 0
    q = np.nonzero(real)[0]
    want = np.stack([rows[real], v2r[q], masks[real]], axis=1)
    live = slot_masks[:zero_chunk] != 0
    got = np.stack([np.broadcast_to(owner[:, None], live.shape)[live],
                    parents[:zero_chunk][live],
                    slot_masks[:zero_chunk][live]], axis=1)
    order = lambda a: a[np.lexsort(a.T[::-1])]
    assert np.array_equal(order(got.astype(np.int64)),
                          order(want.astype(np.int64)))
    # no row holds more chunks than it needs
    counts = np.bincount(rows[real], minlength=g.n_ext)
    assert ((chunk_ids != zero_chunk).sum(axis=1) == -(-counts // d)).all()
    assert chunk_ids.shape[1] == max(1, int((-(-counts // d)).max()))
    assert t.entries == t.parents.size + t.rows.size


def _counts(case: str, n: int = 256) -> np.ndarray:
    """Slices each of ``n`` rows receives: ``uniform<p>``, a hub row of
    1000 slices among rows of one, or a urand-like (mean 31, max 63) or
    rgg-like (mean 5, max 13) profile over 4096 rows."""
    if case == "hub":
        return np.r_[1000, np.ones(n - 1, np.int64)]
    if case in ("urand", "rgg"):
        mean, top = (31, 63) if case == "urand" else (5, 13)
        counts = np.minimum(np.random.default_rng(0).poisson(mean, 4096), top)
        counts[0] = top
        return counts
    return np.full(n, int(case.removeprefix("uniform")))


@pytest.mark.parametrize("case,want", [
    ("uniform3", 8), ("uniform12", 16), ("uniform31", 32), ("uniform64", 64),
    ("hub", 64), ("urand", 32), ("rgg", 8)])
def test_chunk_width_rule(case, want):
    """The least modelled time, a stage-1 row at ``ROW_NS`` and a stage-2
    index at ``SCALAR_NS``: padding pushes D down, a hot row's stage-2
    columns push it up."""
    counts = np.r_[_counts(case), np.zeros(SIGMA, np.int64)]
    assert chunk_width(counts) == want


@pytest.mark.parametrize("case", ["uniform3", "uniform12", "uniform31",
                                  "uniform64", "hub"])
def test_slot_table_width_follows_the_graph(case):
    """A slot grid whose rows receive ``case``'s slices gets the rule's D
    and one chunk per D slices of each row, plus the zero chunk, padded to
    whole blocks."""
    counts = _counts(case)
    n = counts.size
    rows = np.repeat(np.arange(n), counts)
    pad = (-rows.size) % TAU + TAU             # at least one padding VSS
    masks = np.concatenate([np.ones(rows.size, np.uint8),
                            np.zeros(pad, np.uint8)]).reshape(-1, TAU)
    row_ids = np.concatenate([rows, np.full(pad, n)]).reshape(-1, TAU)
    v2r = np.zeros(masks.shape[0], np.int32)
    t = slot_table(row_ids.astype(np.int32), masks, v2r, n + SIGMA)
    d = chunk_width(np.r_[counts, np.zeros(SIGMA, np.int64)])
    num_chunks = int((-(-counts // d)).sum())
    cols = t.parents.shape[1]
    assert t.parents.shape[0] == d and cols >= num_chunks + 1
    assert cols % gather_or_mod._block_cols(d, cols) == 0
    assert (t.masks[:, num_chunks:] == 0).all()
    assert (t.masks != 0).sum() == counts.sum()
    assert t.rows.shape == (int(-(-counts.max() // d)), n + SIGMA)
