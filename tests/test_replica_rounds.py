"""Source-parallel replicas in rounds (DESIGN.md §17.1): ``BfsEngine(mesh=4)``
on four virtual CPU devices, run in a subprocess so that this process keeps
its single-device view.

A round puts every active replica's level in flight before it waits on any
of them; each replica's runner keeps its slot table and lane state on its
own device; the counters ``rounds`` and ``levels:replica<k>`` add up; and
the answers equal the oracle's.  ``BfsEngine(mesh=4)`` in a one-device
process serves as a single device.
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from repro.serve.mesh import EngineMesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_devices(script: str, n_devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


SERVE = textwrap.dedent("""
    import json, numpy as np, jax
    from repro.core import ref_bfs
    from repro.data import graphs
    from repro.serve import workloads
    from repro.serve.bfs_engine import BfsEngine, TicketState

    g = graphs.make("urand", scale=8, seed=0)
    eng = BfsEngine(kappa=32, layout="packed", use_pallas=False,
                    switching="off", build_workers=0, mesh=4)
    eng.register_graph("g", g)
    rng = np.random.default_rng(0)
    kinds = ("bfs", "closeness", "distance")
    tickets = []
    for i in range(6 * 32):  # 1.5 x the lanes of four replicas
        kind = kinds[i % 3]
        tickets.append(eng.submit(
            "g", int(rng.integers(g.n)), kind=kind,
            target=int(rng.integers(g.n)) if kind == "distance" else None))

    # every span entered, with the replica its tick half names
    log, spans = [], eng._spans
    def record(name, **args):
        log.append((name, args.get("replica")))
        return spans(name, **args)
    eng._spans = record

    out = {"mesh_devices": eng.stats["mesh_devices"]}
    eng.step()  # the session group opens and runs its first round
    group = eng._sessions["g"]
    devs = jax.devices()[:4]
    runners = eng._mesh_runners["g"]
    art = eng.cache.peek("g")
    out["placement"] = [
        all(a.devices() == {devs[k]} and a.committed
            for a in (r._ops.slot_parents, r._ops.slot_masks,
                      r._ops.chunk_rows, *r.init_state(),
                      *group.replicas[k].state))
        for k, r in enumerate(runners)]
    # every chip's stage-1 parents and masks and stage-2 rows are copies
    # of the one host table the replicas share
    out["one_host_table"] = all(
        r.table is runners[0].table
        and np.array_equal(np.asarray(r._ops.slot_parents), r.table.parents)
        and np.array_equal(np.asarray(r._ops.slot_masks), r.table.masks)
        and np.array_equal(np.asarray(r._ops.chunk_rows), r.table.rows)
        for r in runners)
    out["replica0_is_bd"] = art.replicas[0] is art.bd
    out["replica_bd_devices"] = [
        bd.row_ids.devices() == {devs[k]} and bd.masks.devices() == {devs[k]}
        for k, bd in enumerate(art.replicas)]
    out["runner_for_is_replica0"] = eng._runner_for(art) is runners[0]
    out["runners"] = len(runners)
    while eng.has_work():
        eng.step()

    wrong = 0
    for t in tickets:
        try:
            assert t.state == TicketState.DONE
            workloads.verify_result(t.result(wait=False), t.query,
                                    ref_bfs.bfs_levels(g, t.query.source),
                                    unreached=ref_bfs.UNREACHED, graph=g)
        except AssertionError:
            wrong += 1
    out["answered"], out["wrong"] = len(tickets), wrong

    # split the span log into rounds: each from its serve.round to the
    # next serve.step
    rounds, cur = [], None
    for name, replica in log:
        if name == "serve.round":
            cur = []
            rounds.append(cur)
        elif name == "serve.step":
            cur = None
        elif cur is not None:
            cur.append((name, replica))
    issued, ordered = [], True
    for r in rounds:
        who, dispatched, first_collect = None, [], None
        for i, (name, replica) in enumerate(r):
            if name == "serve.tick":
                who = replica
            elif name == "serve.dispatch":
                dispatched.append(who)
            elif name == "serve.sync.new_lane" and first_collect is None:
                first_collect = i
                ordered &= all(n != "serve.dispatch" for n, _ in r[i:])
        issued.append(sorted(dispatched))
    out["rounds_logged"] = sum(1 for d in issued if d)
    out["dispatch_before_collect"] = ordered
    out["max_issued"] = max(len(d) for d in issued)
    out["issued_replicas_distinct"] = all(len(set(d)) == len(d)
                                          for d in issued)
    s = eng.stats
    out["levels"] = s["levels"]
    out["levels_per_replica"] = [s[f"levels:replica{k}"] for k in range(4)]
    out["rounds"] = s["rounds"]
    out["round_span_s"] = s["host_s:serve.round"]
    print(json.dumps(out))
""")

SINGLE = textwrap.dedent("""
    import json, numpy as np, jax
    from repro.core import ref_bfs
    from repro.data import graphs
    from repro.serve import workloads
    from repro.serve.bfs_engine import BfsEngine, TicketState, _GraphSession

    g = graphs.make("urand", scale=8, seed=0)
    eng = BfsEngine(kappa=32, layout="packed", use_pallas=False,
                    switching="off", build_workers=0, mesh=4)
    eng.register_graph("g", g)
    tickets = [eng.submit("g", s, kind="closeness") for s in range(0, 96, 3)]
    eng.step()
    lone = type(eng._sessions["g"]) is _GraphSession
    eng.run()
    ok = all(t.state == TicketState.DONE for t in tickets)
    for t in tickets:
        workloads.verify_result(t.result(wait=False), t.query,
                                ref_bfs.bfs_levels(g, t.query.source),
                                unreached=ref_bfs.UNREACHED, graph=g)
    print(json.dumps({
        "devices": jax.device_count(), "mesh_devices": eng.stats["mesh_devices"],
        "replicas": eng.cache.peek("g").replicas, "lone": lone, "ok": ok,
        "levels": eng.stats["levels"],
        "levels_replica0": eng.stats["levels:replica0"],
        "rounds": eng.stats["rounds"]}))
""")


@pytest.fixture(scope="module")
def served():
    return run_in_devices(SERVE, 4)


def test_replicas_answer_as_the_oracle(served):
    """bfs, closeness and distance on four replicas, every answer exact."""
    assert served["mesh_devices"] == 4 and served["runners"] == 4
    assert served["answered"] == 6 * 32
    assert served["wrong"] == 0


def test_round_dispatches_every_replica_before_it_collects(served):
    """Within a round, every replica's ``serve.dispatch`` precedes the
    first ``serve.sync.new_lane``: the levels are in flight together."""
    assert served["dispatch_before_collect"]
    assert served["max_issued"] == 4
    assert served["issued_replicas_distinct"]


def test_round_and_replica_counters_add_up(served):
    assert sum(served["levels_per_replica"]) == served["levels"] > 0
    assert min(served["levels_per_replica"]) > 0
    assert served["rounds"] == served["rounds_logged"] > 0
    assert served["round_span_s"] > 0.0


def test_each_replica_keeps_its_arrays_on_its_device(served):
    """Slot table, empty state and live state of replica k live on device
    k only, committed; one host table serves all four; replica 0 is the
    artifact itself, not a second copy on device 0."""
    assert served["placement"] == [True] * 4
    assert served["one_host_table"]
    assert served["replica0_is_bd"]
    assert served["replica_bd_devices"] == [True] * 4


def test_runner_for_a_replicated_artifact_is_replica0s(served):
    assert served["runner_for_is_replica0"]


def test_mesh_of_four_on_one_device_serves_as_a_single_device():
    out = run_in_devices(SINGLE, 1)
    assert out["devices"] == 1 and out["mesh_devices"] == 1
    assert out["replicas"] is None and out["lone"] and out["ok"]
    assert out["levels_replica0"] == out["levels"] > 0
    assert out["rounds"] == 0


@pytest.mark.parametrize("bad", [0, -1, True, 2.0])
def test_mesh_count_must_be_a_positive_int(bad):
    with pytest.raises(ValueError, match="device count"):
        EngineMesh.of(bad)


def test_mesh_of_passes_a_mesh_and_none_through():
    assert EngineMesh.of(None) is None
    m = EngineMesh()
    assert EngineMesh.of(m) is m
    assert EngineMesh.of(64).n_devices == m.n_devices  # clamped


def test_mesh_build_s_counts_the_host_build(monkeypatch):
    """``build_s`` of a mesh-path artifact counts the reorder and BVSS
    build that run before ``build_artifacts`` is handed them, as the
    single-device path counts them."""
    from repro.core import reorder as reorder_mod
    from repro.data import graphs
    from repro.serve import mesh as mesh_mod

    slow = reorder_mod.reorder

    def reorder(*args, **kw):
        time.sleep(0.3)
        return slow(*args, **kw)

    monkeypatch.setattr(reorder_mod, "reorder", reorder)
    art = mesh_mod.build_mesh_artifacts(
        "g", graphs.make("urand", scale=6, seed=0), group=EngineMesh().groups[0])
    assert art.build_s >= 0.3
