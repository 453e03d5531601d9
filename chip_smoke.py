#!/usr/bin/env python3
"""Chip smoke: serve graph queries on a TPU through the Pallas kernels.

    python chip_smoke.py              # one chip, every phase below
    python chip_smoke.py --chips 4    # the mesh path on four chips, only

One chip, one process, every answer checked against the numpy oracle:

  device  the first JAX device must be a TPU; there is no CPU fallback
  serve   ``BfsEngine`` with the ``repro.launch.serve_bfs`` defaults
          (layout=auto, switching=auto, kappa=32) on urand scale 20:
          bfs/closeness/distance/reach, a cold wave then a warm wave
  mma     layout=mma with megatick=64 on urand scale 16, all seven kinds
  queued  switching=on on road scale 16 (queued pull + scatter-OR kernels)
  single  the ``repro.launch.bfs`` entry: bfs on urand scale 20, --verify

With ``--chips 4`` only the mesh path runs, on urand scale 20: the same
request stream on one device, on source-parallel replicas over the four
chips, and row-sharded over them (forced by a per-device budget below the
artifact's bytes).

The run fails on any ticket that is not DONE, any degraded (graph,
layout) pair, any single-device runner off the Pallas kernels or in
interpret mode, and any oracle mismatch.  The last line of standard output
is one JSON object naming the device; it is printed only when every phase
passed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
KINDS_TRAVERSAL = ("bfs", "closeness", "distance", "reach")
KINDS_ALL = ("bfs", "closeness", "distance", "reach", "cc", "mis", "tpv")


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def device_check(chips: int):
    """Find the TPU before anything compiles; returns the devices."""
    src = ROOT / "src"
    check((src / "repro").is_dir(),
          "the repro package is missing: run chip_smoke.py from a checkout")
    sys.path.insert(0, str(src))
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"no TPU: JAX found no devices ({e})") from e
    check(devs[0].platform == "tpu",
          f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
    check(len(devs) >= chips,
          f"need {chips} TPU chips, JAX sees {len(devs)}")
    say("device", platform=devs[0].platform, kind=repr(devs[0].device_kind),
        count=len(devs))
    return devs


class Oracle:
    """CPU reference levels, memoized per (graph, source)."""

    def __init__(self):
        self._memo = {}

    def levels(self, name, g, src):
        from repro.core import ref_bfs

        key = (name, src)
        if key not in self._memo:
            self._memo[key] = ref_bfs.bfs_levels(g, src)
        return self._memo[key]

    def verify(self, tickets, results, fleet) -> int:
        from repro.core import ref_bfs
        from repro.serve.workloads import verify_result

        for t in tickets:
            q = t.query
            g = fleet[q.graph]
            try:
                verify_result(results[int(t)], q,
                              self.levels(q.graph, g, q.source),
                              unreached=ref_bfs.UNREACHED, graph=g)
            except AssertionError as e:
                raise SmokeFailure(f"oracle mismatch on {q}: {e}") from e
        return len(tickets)


def make_stream(rng, fleet, n: int, kinds, sources=None):
    """``n`` requests drawn uniformly over graphs and kinds; ``sources``
    (optional) reuses a fixed source pool."""
    names = sorted(fleet)
    out = []
    for i in range(n):
        name = names[int(rng.integers(len(names)))]
        g = fleet[name]
        src = (int(sources[i % len(sources)]) if sources is not None
               else int(rng.integers(g.n)))
        kind = kinds[i % len(kinds)]
        target = int(rng.integers(g.n)) if kind == "distance" else None
        out.append((name, src, kind, target))
    return out


def serve(eng, stream):
    """Submit ``stream``, drain; returns (tickets, results, seconds,
    levels advanced)."""
    from repro.serve.bfs_engine import TicketState

    lv0 = eng.stats["levels"]
    t0 = time.perf_counter()
    tickets = [eng.submit(name, src, kind=kind, target=target)
               for name, src, kind, target in stream]
    results = eng.run()
    dt = time.perf_counter() - t0
    bad = [(int(t), t.state.name, t.error) for t in tickets
           if t.state != TicketState.DONE]
    check(not bad, f"tickets not DONE: {bad[:5]}")
    return tickets, results, dt, eng.stats["levels"] - lv0


def check_engine(eng, *, sharded_ok: bool = False) -> list[str]:
    """No degradation, and every runner on compiled Pallas kernels (the
    graph-parallel runner pulls through the jnp reference by design)."""
    from repro.serve.mesh import ShardedLaneRunner

    h = eng.health()
    check(not h.degraded, f"degraded pairs: {dict(h.degraded)}")
    check(eng.stats["degraded"] == 0,
          f"stats degraded={eng.stats['degraded']}")
    runners = list(eng._runners.items())
    runners += [(f"{n}#{k}", r) for n, grp in eng._mesh_runners.items()
                for k, r in enumerate(grp)]
    check(runners, "no runner was built")
    seen = []
    for name, r in runners:
        if isinstance(r, ShardedLaneRunner):
            check(sharded_ok, f"unexpected sharded runner for {name}")
            seen.append(f"{name}:sharded(jnp-reference-pull)")
            continue
        check(r.use_pallas and not r._interpret,
              f"runner {name} not on compiled Pallas kernels "
              f"(use_pallas={r.use_pallas} interpret={r._interpret})")
        seen.append(f"{name}:{r.layout}/{r.substrate}/pallas")
    return seen


def peak_bytes(devs) -> dict:
    return {int(d.id): int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", -1)) for d in devs}


def phase_serve(devs, oracle, rng) -> None:
    from repro.data import graphs
    from repro.serve.bfs_engine import BfsEngine

    t0 = time.perf_counter()
    g = graphs.make("urand", scale=20, seed=0)
    gen_s = time.perf_counter() - t0
    fleet = {"urand": g}
    eng = BfsEngine(kappa=32, layout="auto", switching="auto")
    eng.register_graph("urand", g)
    stream = make_stream(rng, fleet, 64, KINDS_TRAVERSAL)
    tickets, results, cold_s, cold_lv = serve(eng, stream)
    n_ok = oracle.verify(tickets, results, fleet)
    # the warm wave reuses the cold wave's sources (the oracle is memoized)
    warm = make_stream(rng, fleet, 64, KINDS_TRAVERSAL,
                       sources=[s for _, s, _, _ in stream])
    tickets, results, warm_s, warm_lv = serve(eng, warm)
    n_ok += oracle.verify(tickets, results, fleet)
    art = eng.cache.peek("urand")
    sw = art.switching
    runners = check_engine(eng)
    say("serve", graph="urand", scale=20, n=g.n, m=g.m,
        graph_gen_s=f"{gen_s:.2f}", build_s=f"{art.build_s:.2f}",
        probe_s=f"{art.probe_s:.2f}",
        probe_chose=("mma" if sw and sw.dense_layout == "mma"
                     else eng._base_layout()),
        policy=("on" if sw and sw.enabled else "off"),
        runners=",".join(runners), artifact_bytes=art.total_bytes)
    say("serve", wave="cold", requests=len(stream), seconds=f"{cold_s:.3f}",
        levels=cold_lv)
    say("serve", wave="warm", requests=len(warm), seconds=f"{warm_s:.3f}",
        levels=warm_lv,
        steady_s_per_level=f"{warm_s / max(warm_lv, 1):.6f}",
        verified=n_ok, peak_bytes_in_use=peak_bytes(devs[:1])[devs[0].id])


def phase_mma(devs, oracle, rng) -> None:
    from repro.data import graphs
    from repro.serve.bfs_engine import BfsEngine

    g = graphs.make("urand", scale=16, seed=1)
    fleet = {"urand16": g}
    eng = BfsEngine(kappa=32, layout="mma", megatick=64)
    eng.register_graph("urand16", g)
    stream = make_stream(rng, fleet, 8 * len(KINDS_ALL), KINDS_ALL)
    tickets, results, dt, lv = serve(eng, stream)
    n_ok = oracle.verify(tickets, results, fleet)
    runners = check_engine(eng)
    check(all(":mma/" in r for r in runners), f"not on mma: {runners}")
    say("mma", graph="urand", scale=16, n=g.n, m=g.m, kinds=len(KINDS_ALL),
        requests=len(stream), seconds=f"{dt:.3f}", levels=lv,
        megaticks=eng.stats["megaticks"], verified=n_ok,
        runners=",".join(runners),
        peak_bytes_in_use=peak_bytes(devs[:1])[devs[0].id])


def phase_queued(devs, oracle, rng) -> None:
    from repro.data import graphs
    from repro.serve.bfs_engine import BfsEngine

    g = graphs.make("road", scale=16, seed=0)
    fleet = {"road": g}
    eng = BfsEngine(kappa=32, switching="on")
    eng.register_graph("road", g)
    stream = make_stream(rng, fleet, 32, KINDS_TRAVERSAL)
    tickets, results, dt, lv = serve(eng, stream)
    n_ok = oracle.verify(tickets, results, fleet)
    runners = check_engine(eng)
    s = eng.stats
    check(s["levels_queued"] > 0, "no queued level ran")
    say("queued", graph="road", scale=16, n=g.n, m=g.m,
        requests=len(stream), seconds=f"{dt:.3f}", levels=lv,
        dense=s["levels_dense"], queued=s["levels_queued"],
        s_per_level=f"{dt / max(lv, 1):.6f}", verified=n_ok,
        runners=",".join(runners),
        peak_bytes_in_use=peak_bytes(devs[:1])[devs[0].id])


def phase_single(devs, oracle, rng) -> None:
    from repro.launch import bfs as bfs_launch

    argv = ["repro.launch.bfs", "--family", "urand", "--scale", "20",
            "--workload", "bfs", "--src", "0", "--verify"]
    saved, sys.argv = sys.argv, argv
    t0 = time.perf_counter()
    try:
        bfs_launch.main()
    except AssertionError as e:
        raise SmokeFailure(f"single-source bfs mismatch: {e}") from e
    finally:
        sys.argv = saved
    say("single", entry="repro.launch.bfs", graph="urand", scale=20,
        seconds=f"{time.perf_counter() - t0:.3f}", verified=1,
        peak_bytes_in_use=peak_bytes(devs[:1])[devs[0].id])


def phase_mesh(devs, oracle, rng) -> None:
    """Four chips: one device vs source-parallel replicas vs row-sharded
    graph-parallel serving of one request stream."""
    import numpy as np

    from repro.core import reorder as reorder_mod
    from repro.data import graphs
    from repro.serve.bfs_engine import BfsEngine
    from repro.serve.mesh import EngineMesh

    devs = devs[:4]
    g0 = graphs.make("urand", scale=20, seed=0)
    t0 = time.perf_counter()
    # reorder once: the three engines then serve the same RCM-ordered
    # graph with reorder="natural" instead of each re-running RCM
    g = g0.permuted(reorder_mod.reorder(g0, sigma=8).perm)
    say("mesh", graph="urand", scale=20, n=g.n, m=g.m,
        reorder_s=f"{time.perf_counter() - t0:.2f}")
    fleet = {"urand": g}
    stream = make_stream(rng, fleet, 64, KINDS_TRAVERSAL)
    answers = {}

    def run(label, eng):
        eng.register_graph("urand", g, reorder="natural")
        tickets, results, dt, lv = serve(eng, stream)
        n_ok = oracle.verify(tickets, results, fleet)
        answers[label] = [results[int(t)] for t in tickets]
        return eng, dt, lv, n_ok

    # dense levels only: the graph-parallel runner pins the policy off, so
    # all three serve the same level schedule (and no probe runs)
    one, dt, lv, n_ok = run("one", BfsEngine(kappa=32, switching="off"))
    runners = check_engine(one)
    art = one.cache.peek("urand")
    say("mesh", mode="one-device", seconds=f"{dt:.3f}", levels=lv,
        verified=n_ok, runners=",".join(runners),
        artifact_bytes=art.device_bytes)

    sp, dt, lv, n_ok = run("source", BfsEngine(
        kappa=32, switching="off", mesh=EngineMesh(devs)))
    runners = check_engine(sp)
    art_sp = sp.cache.peek("urand")
    check(art_sp.replicas is not None and len(art_sp.replicas) == 4,
          "source-parallel build made no replicas")
    placed = [sorted(int(d.id) for d in bd.masks.devices())
              for bd in art_sp.replicas]
    check(placed == [[int(d.id)] for d in devs],
          f"replicas not one per device: {placed}")
    say("mesh", mode="source-parallel", seconds=f"{dt:.3f}", levels=lv,
        verified=n_ok, replica_devices=placed, runners=",".join(runners))

    budget = art.device_bytes // 2
    gp, dt, lv, n_ok = run("graph", BfsEngine(
        kappa=32, switching="off", mesh=EngineMesh(devs),
        device_budget=budget))
    runners = check_engine(gp, sharded_ok=True)
    art_gp = gp.cache.peek("urand")
    check(art_gp.sharded is not None, "graph-parallel build did not shard")
    shard_devs = sorted(int(s.device.id)
                        for s in art_gp.sharded.rs.masks.addressable_shards)
    check(shard_devs == sorted(int(d.id) for d in devs),
          f"shards not on all four devices: {shard_devs}")
    say("mesh", mode="graph-parallel", seconds=f"{dt:.3f}", levels=lv,
        verified=n_ok, device_budget=budget, shard_devices=shard_devs,
        per_device_bytes=art_gp.per_device_bytes,
        pull="jnp-reference (ShardedLaneRunner, DESIGN.md 17.2)",
        runners=",".join(runners))

    for i, (a, b, c) in enumerate(zip(answers["one"], answers["source"],
                                      answers["graph"])):
        for x in (b, c):
            same = (a.far, a.reach, a.distance) == (x.far, x.reach,
                                                     x.distance)
            if a.levels is not None:
                same = same and np.array_equal(a.levels, x.levels)
            check(same, f"request {i}: mesh answer differs from one device")
    peaks = peak_bytes(devs)
    check(all(p > 0 for p in peaks.values()),
          f"a device held no memory: {peaks}")
    say("mesh", compared=len(stream), match="one==source==graph==oracle",
        peak_bytes_in_use=peaks)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the mesh path, on four chips")
    args = ap.parse_args()
    t_start = time.perf_counter()
    try:
        devs = device_check(args.chips)
        import numpy as np

        from repro.launch import compile_cache

        cache_dir = compile_cache.enable()
        stats = compile_cache.CompileStats()
        say("cache", dir=cache_dir)
        rng = np.random.default_rng(0)
        oracle = Oracle()
        phases = ([phase_mesh] if args.chips == 4
                  else [phase_serve, phase_mma, phase_queued, phase_single])
        for phase in phases:
            t0, c0 = time.perf_counter(), stats.seconds
            phase(devs, oracle, rng)
            say("time", of=phase.__name__.removeprefix("phase_"),
                seconds=f"{time.perf_counter() - t0:.2f}",
                compile_s=f"{stats.seconds - c0:.2f}")
        say("cache", dir=cache_dir, compile_s=f"{stats.seconds:.2f}",
            compiles=stats.compiles, hits=stats.hits, misses=stats.misses)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    say("done", seconds=f"{time.perf_counter() - t_start:.1f}")
    d0 = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
