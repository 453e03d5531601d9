"""Megatick fused traversal (DESIGN.md §11): the on-device level loop is
bit-identical to ``core/ref_bfs.py`` across both lane substrates x
{dense, queued, auto} policies x megatick ∈ {1, 4, 64}, including
mid-flight admission landing inside a megatick window; the fused
pull+scatter kernel matches its composed references; the serve-aware
probe replaces the single-source proxy; and the extraction gather /
host-side reach satellites stay exact."""
import functools

import numpy as np
import pytest

from repro.core import ref_bfs
from repro.data import graphs
from repro.serve.bfs_engine import BfsEngine, build_artifacts

UNREACHED = ref_bfs.UNREACHED

# (switching, eta): dense-forced, queued-forced, probe-gated auto
MODES = [("off", 10.0), ("on", 0.0), ("auto", 10.0)]
LAYOUTS = ["byteplane", "packed"]
MEGATICKS = [1, 4, 64]


def _engine(**kw):
    kw.setdefault("layout", "byteplane")
    kw.setdefault("use_pallas", False)
    return BfsEngine(**kw)


@pytest.fixture(scope="module")
def duo():
    """Ring (max diameter: windows span many levels, lanes finish together)
    and a scale-free kron (small diameter, staggered finishes)."""
    return {
        "ring": graphs.make("ring", scale=6),
        "kron": graphs.make("kron", scale=7, seed=0),
    }


# ------------------------------------------------------ megatick x oracle --
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("switching,eta", MODES)
@pytest.mark.parametrize("megatick", MEGATICKS)
def test_megatick_matches_oracle(duo, layout, switching, eta, megatick):
    eng = _engine(layout=layout, switching=switching, eta=eta,
                  megatick=megatick)
    for name, g in duo.items():
        eng.register_graph(name, g)
    rng = np.random.default_rng(0)
    want = {}
    for name, g in duo.items():
        for s in rng.integers(0, g.n, 6):
            want[eng.submit(name, int(s))] = (g, int(s))
    res = eng.run()
    for rid, (g, src) in want.items():
        assert (res[rid].levels == ref_bfs.bfs_levels(g, src)).all(), \
            (layout, switching, eta, megatick)
    if megatick > 1 and switching == "off":
        assert eng.stats["megaticks"] > 0  # windows actually ran


def test_megatick_windows_amortize_syncs(duo):
    """A kappa-sized burst on the ring: one generation, empty queue, so
    windows run to T and host syncs per level drop well below 1."""
    g = duo["ring"]
    eng = _engine(kappa=32, switching="off", megatick=64)
    eng.register_graph("g", g)
    rng = np.random.default_rng(1)
    want = {eng.submit("g", int(s)): int(s)
            for s in rng.integers(0, g.n, 32)}
    res = eng.run()
    s = eng.stats
    assert s["megaticks"] >= 1
    assert s["levels"] > 30  # ring scale 6: ~n/2 levels
    assert s["host_syncs"] / s["levels"] < 1.0
    for rid, src in want.items():
        assert (res[rid].levels == ref_bfs.bfs_levels(g, src)).all()


@pytest.mark.parametrize("layout", LAYOUTS)
def test_midflight_admission_lands_inside_window(duo, layout):
    """More requests than lanes at megatick=4: late arrivals are admitted
    into freed slots at levels that are not window-aligned, their lanes
    traverse across window boundaries, and every result stays exact."""
    g = duo["ring"]
    eng = _engine(kappa=32, layout=layout, switching="off", megatick=4)
    eng.register_graph("g", g)
    rng = np.random.default_rng(3)
    want = {eng.submit("g", int(s)): int(s)
            for s in rng.integers(0, g.n, 72)}
    res = eng.run()
    assert eng.stats["admissions_midflight"] > 0
    assert eng.stats["megaticks"] > 0
    late = [r.admitted_at_level for r in res.values()
            if r.admitted_at_level > 0]
    assert late and any(lv % 4 != 0 for lv in late)  # inside a window
    for rid, src in want.items():
        assert (res[rid].levels == ref_bfs.bfs_levels(g, src)).all()


def test_megatick_queued_fallback(duo):
    """Forced-queued policy under megatick: every window returns zero ticks
    (the on-device Eq. (6) verdict), the host runs the bucketed queued
    levels, and results stay exact — the worst case for the window, the
    invariant case for correctness."""
    g = duo["ring"]
    eng = _engine(kappa=32, switching="on", eta=0.0, megatick=4)
    eng.register_graph("g", g)
    want = {eng.submit("g", s): s for s in (0, 5, g.n - 1)}
    res = eng.run()
    assert eng.stats["levels_queued"] > 0
    assert eng.stats["levels_dense"] == 0
    assert eng.stats["megaticks"] == 0  # every window exited pre-tick
    for rid, src in want.items():
        assert (res[rid].levels == ref_bfs.bfs_levels(g, src)).all()


def test_megatick_closeness(duo):
    g = duo["kron"]
    eng = _engine(megatick=64, switching="off")
    eng.register_graph("g", g)
    rids = {eng.submit("g", s, kind="closeness"): s
            for s in (0, 1, g.n - 1)}
    res = eng.run()
    for rid, s in rids.items():
        lv = ref_bfs.bfs_levels(g, s)
        reached = lv[lv != UNREACHED]
        assert res[rid].far == int(reached.sum())
        assert res[rid].reach == reached.size


def test_megatick_pallas_packed_path():
    """The dense level's Pallas pull (interpret mode) and slot-table
    gather-OR inside the while_loop window: packed substrate, megatick=4,
    oracle-exact, every windowed level counted in ``dense_gathered``."""
    g = graphs.make("road", scale=5, seed=0)
    eng = BfsEngine(kappa=32, layout="packed", use_pallas=True,
                    switching="off", megatick=4)
    eng.register_graph("tiny", g)
    rids = {eng.submit("tiny", s): s for s in (0, 7, g.n - 1)}
    res = eng.run()
    assert eng.stats["megaticks"] > 0
    assert eng.stats["dense_gathered"] == (
        eng.stats["levels_dense"] * eng._runners["tiny"].dense_gathered) > 0
    for rid, s in rids.items():
        assert (res[rid].levels == ref_bfs.bfs_levels(g, s)).all()


def test_invalid_megatick():
    with pytest.raises(ValueError):
        BfsEngine(megatick=0)


# ---------------------------------------------------------- fused kernel ---
def test_fused_kernel_matches_refs(duo):
    """pull_scatter_ms_packed (interpret) == its jnp twin == the unfused
    pull_ms_packed_ref + scatter_or_ref pipeline, on random state."""
    import jax.numpy as jnp

    from repro.kernels.pull_ms_packed import pull_ms_packed_ref
    from repro.kernels.pull_scatter_ms_packed import (
        pull_scatter_ms_packed, pull_scatter_ms_packed_ref)
    from repro.kernels.scatter_or import scatter_or_ref

    bd = build_artifacts("g", duo["kron"]).bd
    rng = np.random.default_rng(0)
    kw = 1
    v = jnp.asarray(rng.integers(0, 2**32, (bd.n_ext, kw), dtype=np.uint32))
    f = jnp.asarray(rng.integers(0, 2**32, (bd.num_sets_ext, bd.sigma, kw),
                                 dtype=np.uint32))
    rows = bd.row_ids.reshape(-1)
    want = pull_scatter_ms_packed_ref(v, bd.masks, f, bd.v2r, rows,
                                      sigma=bd.sigma)
    marks = pull_ms_packed_ref(bd.masks, f[bd.v2r], sigma=bd.sigma)
    unfused = scatter_or_ref(v, rows, marks.reshape(-1, kw))
    got = pull_scatter_ms_packed(v, bd.masks, f, bd.v2r, rows,
                                 sigma=bd.sigma, interpret=True)
    assert (np.asarray(want) == np.asarray(unfused)).all()
    assert (np.asarray(got) == np.asarray(want)).all()


# ------------------------------------------------------- serve-aware probe --
def test_auto_probe_is_serve_aware(duo):
    """BfsEngine(switching='auto') probes with the kappa-lane runner, not
    the single-source BucketedBfs proxy; build_artifacts without a runner
    factory keeps the single-source probe."""
    eng = _engine(switching="auto")
    eng.register_graph("g", duo["kron"])
    eng.submit("g", 0)
    eng.run()
    sw = eng.cache.peek("g").switching
    assert sw is not None and sw.proxy == "serve"
    assert isinstance(sw.enabled, bool)
    plain = build_artifacts("g", duo["kron"], probe=True)
    assert plain.switching.proxy == "single"


# ------------------------------------------------ extraction gather bucket --
def test_extraction_gather_buckets(duo):
    """gather_level_cols pads to power-of-two buckets and returns exactly
    the requested columns."""
    from repro.serve.bfs_engine import _LaneRunner

    art = build_artifacts("g", duo["kron"])
    r = _LaneRunner(art.bd, 32, layout="byteplane", use_pallas=False)
    state = r.init_state()
    srcs = np.arange(32, dtype=np.int32)
    state = r.reseed(state, np.ones(32, bool), srcs, 0)
    full = np.asarray(state.levels)[: art.bd.n]
    for cols in ([3], [0, 31], [1, 2, 3], list(range(7))):
        got = r.gather_level_cols(state.levels, cols)
        assert got.shape == (art.bd.n, len(cols))
        assert (got == full[:, cols]).all()


# ------------------------------------------------ graph arrays as operands --
@pytest.mark.parametrize("layout", ["byteplane", "packed", "mma"])
def test_runner_programs_take_graph_as_argument(layout):
    """The lane runner's jitted steps take the graph's arrays as arguments.
    A closure over them embeds them in every program as constants: at
    scale 20 that is minutes of compilation per program and a device copy
    per executable."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.serve.bfs_engine import _LaneRunner

    art = build_artifacts("g", graphs.make("urand", scale=12, seed=0))
    r = _LaneRunner(art.bd, 32, layout=layout, use_pallas=False)
    st = r.init_state()
    ell = jnp.int32(1)
    texts = [
        r._level_fn.lower(r._ops, st, ell).as_text(),
        r._level_queued_fn.lower(r._ops, st, ell,
                                 jnp.zeros(8, jnp.int32)).as_text(),
        jax.jit(functools.partial(r._megatick, T=4, policy_on=True,
                                  eta=10.0)).lower(
            r._ops, st, jnp.zeros(32, jnp.int32), ell,
            jnp.ones(32, bool), jnp.zeros(32, jnp.int32)).as_text()]
    graph_bytes = min(int(x.nbytes) for x in jax.tree.leaves(r._ops)
                      if x.size > 1024)
    for text in texts:
        consts = re.findall(r'dense<"0x([0-9A-F]*)"', text)
        biggest = max((len(c) // 2 for c in consts), default=0)
        assert biggest < graph_bytes // 4, (layout, biggest, graph_bytes)
