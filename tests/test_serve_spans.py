"""The serving path's host spans and counters (serve/spans.py and the
``stats`` keys of ``BfsEngine``): every key exists from construction, the
read-back counts add up to ``host_syncs``, the self times of the spans
add up to no more than the wall time of the steps, queued levels count
their real and padded rows, and a profiler trace nests the spans as the
code does.  Also pins the names of the jitted level programs, which the
chip benchmark finds in a device trace."""
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blest import VSS_PAD
from repro.data import graphs
from repro.serve.bfs_engine import (
    SPAN_NAMES, SYNC_SITES, BfsEngine, _LaneRunner, build_artifacts)

COUNTERS = ("dispatches", "queued_vss", "queued_rows", "dense_gathered")


def _engine():
    # the packed substrate on its jnp kernels: a queued bucket holds VSS
    # ids (the slice-compacted byteplane path counts slices instead)
    return BfsEngine(kappa=32, layout="packed", use_pallas=False,
                     switching="on")


def _submit(eng, g, n, seed):
    rng = np.random.default_rng(seed)
    for i in range(n):
        if i % 3:
            eng.submit("g", int(rng.integers(g.n)), kind="distance",
                       target=int(rng.integers(g.n)))
        else:
            eng.submit("g", int(rng.integers(g.n)))


@pytest.fixture(scope="module")
def served():
    """A road graph served under backlog (96 queries over 32 lanes, one
    third bfs and two thirds distance), with the stats after every step
    and the wall seconds of every step call."""
    g = graphs.make("road", scale=8, seed=0)
    eng = _engine()
    eng.register_graph("g", g)
    _submit(eng, g, 96, seed=0)
    snaps, walls = [dict(eng.stats)], []
    while eng.has_work():
        t = time.perf_counter()
        eng.step()
        walls.append(time.perf_counter() - t)
        snaps.append(dict(eng.stats))
    return eng, g, snaps, walls


def test_every_key_exists_at_construction():
    stats = BfsEngine().stats
    for name in SPAN_NAMES:
        assert stats["host_s:" + name] == 0.0
    for site in SYNC_SITES:
        assert stats["syncs:" + site] == 0
    assert {"serve.sync." + s for s in SYNC_SITES} <= set(SPAN_NAMES)
    for key in COUNTERS:
        assert stats[key] == 0


def test_run_has_queued_and_dense_levels(served):
    _, _, snaps, _ = served
    end = snaps[-1]
    assert end["levels_queued"] > 0 and end["levels_dense"] > 0
    assert end["syncs:watch"] > 0 and end["syncs:gather_cols"] > 0


def test_host_syncs_is_the_sum_of_the_sites(served):
    _, _, snaps, _ = served
    for s in snaps:
        assert s["host_syncs"] == sum(s["syncs:" + k] for k in SYNC_SITES)
    end = snaps[-1]
    # one new-lane read and one Eq. 6 mask read per level under the policy
    assert end["syncs:new_lane"] == end["levels"]
    assert end["syncs:active_mask"] == end["levels"]
    # every level is a program, and so is every read but new_lane's
    assert end["dispatches"] >= (end["levels"] + end["syncs:active_mask"]
                                 + end["syncs:watch"]
                                 + end["syncs:gather_cols"])


def test_self_times_are_positive_and_within_the_steps(served):
    _, _, snaps, walls = served
    keys = ["host_s:" + n for n in SPAN_NAMES]
    first, end = snaps[0], snaps[-1]
    spent = [end[k] - first[k] for k in keys]
    assert min(spent) >= 0.0
    assert sum(spent) <= sum(walls)
    for s0, s1, wall in zip(snaps, snaps[1:], walls):
        assert sum(s1[k] - s0[k] for k in keys) <= wall
    assert end["host_s:serve.dispatch"] > 0
    assert end["host_s:serve.sync.new_lane"] > 0


def test_queued_rows_are_the_padded_buckets(served):
    _, _, snaps, _ = served
    assert snaps[-1]["queued_rows"] >= snaps[-1]["queued_vss"] > 0
    for s0, s1 in zip(snaps, snaps[1:]):
        vss = s1["queued_vss"] - s0["queued_vss"]
        rows = s1["queued_rows"] - s0["queued_rows"]
        if s1["levels_queued"] == s0["levels_queued"]:
            assert vss == rows == 0
            continue
        assert rows >= vss
        assert rows < 2 * vss or rows == VSS_PAD


def test_dense_gathered_counts_every_dense_level(served):
    """Each dense level adds the runner's slot-table entries, host-side."""
    eng, _, snaps, _ = served
    per_level = eng._runners["g"].dense_gathered
    assert per_level > 0
    for s0, s1 in zip(snaps, snaps[1:]):
        dense = s1["levels_dense"] - s0["levels_dense"]
        assert s1["dense_gathered"] - s0["dense_gathered"] == dense * per_level


def _events(xplane):
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(xplane)
    out = {}
    for plane in prof.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return out


def _inside(inner, outer):
    return all(any(a <= s and e <= b for a, b, _ in outer)
               for s, e, _ in inner)


def test_profiler_trace_nests_the_spans(served, tmp_path):
    eng, g, _, _ = served
    _submit(eng, g, 40, seed=1)
    eng.step()  # the session opens: the steps below tick
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(6):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    eng.run()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert len(found) == 1
    ev = _events(found[0])
    assert len(ev["serve.step"]) == 6
    assert len(ev["serve.tick"]) == 6
    assert ev["serve.sync.new_lane"]
    assert _inside(ev["serve.housekeep"], ev["serve.step"])
    assert _inside(ev["serve.tick"], ev["serve.step"])
    assert _inside(ev["serve.sync.new_lane"], ev["serve.tick"])
    assert _inside(ev["serve.sync.active_mask"], ev["serve.decide"])
    for _, _, args in ev["serve.tick"]:
        assert args["graph"] == "g"
        assert args["mode"] in ("dense", "queued")
        assert isinstance(args["level"], int)


@pytest.mark.parametrize("layout", ["packed", "byteplane"])
def test_level_programs_keep_their_names(layout):
    """The chip benchmark finds a level's device time by these program
    names (``jit__level``, ``jit__level_queued``) in the trace."""
    art = build_artifacts("g", graphs.make("road", scale=6, seed=0))
    runner = _LaneRunner(art.bd, 32, layout=layout, use_pallas=False)
    state = runner.init_state()
    dense = runner._level_fn.lower(runner._ops, state, jnp.int32(1))
    qids = runner.bucket_qids(np.arange(3, dtype=np.int32))
    queued = runner._level_queued_fn.lower(runner._ops, state, jnp.int32(1),
                                           jnp.asarray(qids))
    assert re.match(r"HloModule jit__level\b", dense.compile().as_text())
    assert re.match(r"HloModule jit__level_queued\b",
                    queued.compile().as_text())
