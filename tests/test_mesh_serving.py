"""Mesh serving (DESIGN.md §17): source-parallel replication,
graph-parallel row-sharded admission, per-device cache accounting and
eviction, per-shard fault injection, and the mesh health surface.

The multi-device tests need the virtual CPU devices CI's ``mesh-cpu``
job forces (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and
skip on a single-device run; the per-device accounting and launcher
tests run everywhere (a single device is a degenerate mesh).
"""
import json
import sys

import jax
import numpy as np
import pytest

from repro.core import ref_bfs
from repro.data import graphs
from repro.serve import mesh as mesh_mod
from repro.serve import workloads
from repro.serve.bfs_engine import BfsEngine, TicketState
from repro.serve.lifecycle import (
    PermanentBuildError, ScriptedFaults, TransientBuildError)
from repro.serve.mesh import EngineMesh, OversizedGraphError

from workload_matrix import (
    MESH_MATRIX, matrix_graphs, min_projected_bytes, run_mesh_cell)

UNREACHED = ref_bfs.UNREACHED

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _verify_all(eng, tickets, g):
    for t in tickets:
        assert t.state == TicketState.DONE, (int(t), t.state, t.error)
        q = t.query
        workloads.verify_result(t.result(wait=False), q,
                                ref_bfs.bfs_levels(g, q.source),
                                unreached=UNREACHED, graph=g)


# ------------------------------------------------ EngineMesh shape ---------
def test_engine_mesh_groups():
    devs = jax.devices()
    m = EngineMesh(devs)
    assert m.n_devices == len(devs)
    assert m.groups == (tuple(devs),)
    assert m.device_ids() == [int(d.id) for d in devs]
    with pytest.raises(ValueError, match="at least one device"):
        EngineMesh([])
    if len(devs) >= 2:
        with pytest.raises(ValueError, match="must divide"):
            EngineMesh(devs, group_size=len(devs) + 1)
        m2 = EngineMesh(devs, group_size=1)
        assert len(m2.groups) == len(devs)


def test_projected_device_bytes_matches_to_device():
    """The host-side §17.2 projection must equal what the real transfer
    would charge — the admission decision and the accounting agree."""
    from repro.core import blest
    from repro.core.bvss import BvssConfig, build_bvss
    from repro.core import reorder as reorder_mod

    g = graphs.make("kron", scale=5, seed=1)
    cfg = BvssConfig()
    rr = reorder_mod.reorder(g, sigma=cfg.sigma)
    b = build_bvss(g.permuted(rr.perm), cfg)
    bd = blest.to_device(b)
    arrays = [bd.masks, bd.row_ids, bd.v2r, bd.real_ptrs]
    if bd.masks_packed is not bd.masks:
        arrays.append(bd.masks_packed)
    assert mesh_mod.projected_device_bytes(b) == \
        sum(int(a.nbytes) for a in arrays)


# ------------------------------------------------ oracle matrix (§17) -----
@needs_mesh
@pytest.mark.parametrize("layout,mode,megatick", MESH_MATRIX)
def test_mesh_matrix_cell(layout, mode, megatick):
    run_mesh_cell(layout, mode, megatick)


# ------------------------------------------------ §17.1 acceptance --------
@needs_mesh
def test_source_parallel_lane_capacity_and_stream_parity():
    """Acceptance bar (1): a source-parallel engine puts kappa x 8 lanes
    in flight on one graph and its results are bit-identical to the
    single-device engine on the same request stream."""
    g = graphs.make("ring", scale=6)  # high diameter: lanes accumulate
    kappa, n_dev = 32, len(jax.devices())
    stream = [(i * 7) % g.n for i in range(9 * kappa)]

    def serve(mesh):
        eng = BfsEngine(kappa=kappa, layout="byteplane", switching="off",
                        use_pallas=False, build_workers=0, mesh=mesh)
        eng.register_graph("g", g)
        tickets = [eng.submit("g", s) for s in stream]
        max_in_flight = 0
        while eng.has_work():
            eng.step()
            max_in_flight = max(max_in_flight, eng.in_flight)
        return eng, tickets, max_in_flight

    eng_m, tk_m, mif_m = serve(EngineMesh(jax.devices()))
    eng_1, tk_1, mif_1 = serve(None)
    assert mif_m == kappa * n_dev, mif_m  # kappa x 8 concurrent lanes
    assert mif_1 == kappa
    for tm, t1 in zip(tk_m, tk_1):
        assert tm.state == TicketState.DONE and t1.state == TicketState.DONE
        rm, r1 = tm.result(wait=False), t1.result(wait=False)
        assert np.array_equal(np.asarray(rm.levels),
                              np.asarray(r1.levels)), int(tm)
    _verify_all(eng_m, tk_m, g)
    # one session group = one replica session per device
    assert len(eng_m._mesh_runners["g"]) == n_dev


# ------------------------------------------------ §17.2 acceptance --------
@needs_mesh
def test_oversized_graph_rejected_single_device_served_sharded():
    """Acceptance bar (2): over the per-device budget, the single-device
    engine must reject (FAILED, permanent — no silent truncation), while
    the mesh engine admits via a row-sharded artifact and serves
    oracle-exact results."""
    g = matrix_graphs()["ksym"]
    budget = min_projected_bytes({"g": g}) - 1

    eng1 = BfsEngine(kappa=32, switching="off", use_pallas=False,
                     build_workers=0, device_budget=budget)
    eng1.register_graph("g", g)
    t = eng1.submit("g", 0)
    eng1.run()
    assert t.state == TicketState.FAILED
    assert "byte budget" in t.error
    with pytest.raises(OversizedGraphError):
        mesh_mod.build_mesh_artifacts("g", g, device_budget=budget)

    eng = BfsEngine(kappa=32, switching="off", use_pallas=False,
                    build_workers=0, megatick=8,
                    mesh=EngineMesh(jax.devices()), device_budget=budget)
    eng.register_graph("g", g)
    tickets = [eng.submit("g", (i * 11) % g.n) for i in range(40)]
    eng.run()
    art = eng.cache.peek("g")
    assert art.sharded is not None
    assert art.sharded.n_shards == len(jax.devices())
    assert art.placement == tuple(int(d.id) for d in jax.devices())
    # per-device accounting: each shard charged to its own device
    per = eng.cache.per_device()
    assert set(per) == {int(d.id) for d in jax.devices()}
    assert all(b <= budget for b in per.values())
    _verify_all(eng, tickets, g)


@needs_mesh
def test_sharded_runner_is_policy_off():
    g = matrix_graphs()["kdir"]
    eng = BfsEngine(kappa=32, switching="on", eta=0.0, use_pallas=False,
                    build_workers=0, mesh=EngineMesh(jax.devices()),
                    device_budget=min_projected_bytes({"g": g}) - 1)
    eng.register_graph("g", g)
    tickets = [eng.submit("g", i % g.n) for i in range(8)]
    eng.run()
    _verify_all(eng, tickets, g)
    # switching='on' would force queued sweeps, but the sharded runner
    # has no queued formulation: every level must have run dense
    assert eng.stats["levels_queued"] == 0
    assert eng.stats["levels_dense"] > 0


# ------------------------------------------------ fault injection (§14/16) -
@needs_mesh
def test_transient_shard_fault_retries_to_done():
    g = graphs.make("kron", scale=5, seed=3)
    faults = ScriptedFaults({"g#shard1": [TransientBuildError("flaky"),
                                          None]})
    eng = BfsEngine(kappa=32, switching="off", use_pallas=False,
                    mesh=EngineMesh(jax.devices()),
                    device_budget=min_projected_bytes({"g": g}) - 1,
                    build_fault_hook=faults, build_retries=2,
                    build_backoff=0.01, build_backoff_cap=0.05)
    eng.register_graph("g", g)
    tickets = [eng.submit("g", i % g.n) for i in range(4)]
    eng.run()
    _verify_all(eng, tickets, g)
    assert faults.calls["g#shard1"] == 2  # failed once, retried through
    assert eng.cache.retries >= 1
    assert eng.stats["build_failures"] == 0


@needs_mesh
def test_permanent_replica_fault_fails_tickets():
    g = graphs.make("kron", scale=5, seed=3)
    faults = ScriptedFaults({"g#replica2": [PermanentBuildError("boom")]})
    eng = BfsEngine(kappa=32, switching="off", use_pallas=False,
                    mesh=EngineMesh(jax.devices()),
                    build_fault_hook=faults, build_retries=3)
    eng.register_graph("g", g)
    t = eng.submit("g", 0)
    eng.run()
    assert t.state == TicketState.FAILED
    assert faults.calls["g#replica2"] == 1  # permanent: no retry burned
    assert eng.stats["build_failures"] == 1


# ------------------------------------------------ per-device cache (§17.3) -
def test_per_device_eviction_under_device_budget():
    """Runs on any device count: two graphs that individually fit the
    per-device budget but together exceed it — installing the second
    must evict the first (LRU on the over-budget device), never the
    entry being installed."""
    g1 = graphs.make("kron", scale=5, seed=0)
    g2 = graphs.make("kron", scale=5, seed=1)
    probe = BfsEngine(switching="off", use_pallas=False, build_workers=0)
    probe.register_graph("a", g1)
    probe.register_graph("b", g2)
    bytes_a = probe.cache.get("a").total_bytes
    bytes_b = probe.cache.get("b").total_bytes

    eng = BfsEngine(switching="off", use_pallas=False, build_workers=0,
                    device_budget=bytes_a + bytes_b - 1)
    eng.register_graph("a", g1)
    eng.register_graph("b", g2)
    ta = eng.submit("a", 0)
    eng.run()
    assert "a" in eng.cache
    tb = eng.submit("b", 0)
    eng.run()
    assert ta.state == TicketState.DONE and tb.state == TicketState.DONE
    assert "b" in eng.cache and "a" not in eng.cache
    assert eng.cache.evictions == 1
    budget = eng.cache.device_budget
    assert all(v <= budget for v in eng.cache.per_device().values())


def test_health_reports_device_occupancy():
    g = graphs.make("kron", scale=5, seed=0)
    eng = BfsEngine(switching="off", use_pallas=False, build_workers=0)
    eng.register_graph("g", g)
    t = eng.submit("g", 0)
    h = eng.health()
    # queued work and (sync-built) artifact bytes land on the default
    # device when no mesh placement exists
    dev = eng.cache.default_device_id
    assert h.device_queue_depth == {dev: 1}
    assert h.device_bytes == {dev: eng.cache.get("g").total_bytes}
    eng.run()
    assert t.state == TicketState.DONE
    assert eng.health().device_queue_depth == {}


@needs_mesh
def test_health_reports_mesh_occupancy():
    g = graphs.make("kron", scale=5, seed=0)
    eng = BfsEngine(switching="off", use_pallas=False, build_workers=0,
                    mesh=EngineMesh(jax.devices()))
    eng.register_graph("g", g)
    eng.submit("g", 0)
    h = eng.health()
    ids = {int(d.id) for d in jax.devices()}
    assert set(h.device_bytes) == ids
    # the queue depth lands on every device in the graph's placement
    assert set(h.device_queue_depth) == ids
    assert all(v == 1 for v in h.device_queue_depth.values())
    eng.run()


# ------------------------------------------------ launcher (--health-json) -
@pytest.fixture
def launcher_cache(tmp_path, monkeypatch):
    """The launcher turns JAX's persistent compilation cache on for the
    whole process: point its default directory at a temporary one and
    restore the cache settings afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", tmp_path / "jax_cache")
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(jax.config, k) for k in names}
    yield tmp_path / "jax_cache"
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_launcher_health_json(tmp_path, monkeypatch, launcher_cache):
    from repro.launch import serve_bfs

    path = tmp_path / "health.json"
    monkeypatch.setattr(sys, "argv", [
        "serve_bfs", "--families", "kron", "--scale", "5", "--requests",
        "6", "--switching", "off", "--health-json", str(path),
        "--health-interval", "0.01", "--verify"])
    serve_bfs.main()
    snap = json.loads(path.read_text())
    assert snap["queue_depths"] == {} and snap["in_flight"] == 0
    assert "device_bytes" in snap and "device_queue_depth" in snap
    assert "ts" in snap
    # the launcher compiled through the persistent cache it switched on
    assert any(launcher_cache.iterdir())


@pytest.mark.parametrize("fault", ["degraded", "FAILED"])
def test_launcher_verify_fails_on_absorbed_fault(fault, monkeypatch,
                                                 launcher_cache):
    """``--verify`` exits non-zero, naming the cause, when the service
    absorbed a fault: a degraded (graph, layout) pair or a FAILED ticket."""
    import repro.serve.bfs_engine as engine_mod
    from repro.launch import serve_bfs

    def boom(*args, **kwargs):
        raise PermanentBuildError("injected fault")

    if fault == "degraded":
        monkeypatch.setattr(engine_mod.mma_mod, "prep_mma_tiles", boom)
    else:
        monkeypatch.setattr(engine_mod, "build_artifacts", boom)
    monkeypatch.setattr(sys, "argv", [
        "serve_bfs", "--families", "kron", "--scale", "5", "--requests",
        "4", "--layout", "mma", "--switching", "off", "--verify"])
    with pytest.raises(SystemExit, match=f"(?s)verify failed:.*{fault}"):
        serve_bfs.main()
