"""One run of one cell: set-up, the measured window, the check, the metrics.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) is found by
name, and everything it needs by the names in that entry:

* ``configs/<config>.json`` (the path in the ``configs`` entry): the
  graph's generator, sizes and fixed seed (``graph``) and the
  ``BfsEngine`` arguments a deployment passes (``engine``);
* ``traffic/<traffic>.json``: the query mix (see ``traffic.py``);
* ``metrics/<metric>.py``: one reader per metric, ``read(rec)`` returning
  the number or ``None`` where the run has nothing to read.

The program is driven only through its ticket path: ``BfsEngine``,
``register_graph``, ``submit`` and ``step``, with its ``stats`` counters,
its tickets' timestamps and the cached artifact's set-up seconds read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import re
import shutil
import tempfile
import time

import numpy as np

import compare
import generators
import reference
import trace as trace_mod
import traffic as traffic_mod

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH_DIR = HERE.relative_to(ROOT)
# how long past the window's close a due answer is waited for, and the
# longest the warm-up may take
DRAIN_S = 60.0
WARMUP_S = 600.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENT = "/jax/compilation_cache/cache_"
# the query kinds whose answer is a whole level array
LEVEL_KINDS = {"bfs"}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench_dir: pathlib.Path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; "
                       f"cells: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    bench_dir = root / BENCH_DIR
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=json.loads((root / cfg["file"]).read_text()),
        traffic=json.loads(
            (bench_dir / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)


def reader(bench_dir: pathlib.Path, metric: str):
    """``read`` of ``metrics/<metric>.py``."""
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def sub_seed(seed: int, stream: int) -> int:
    """Independent seeds for the warm-up's queries (1) and the window's
    queries (2), drawn from ``--seed``.  The graph is the deployment's
    data and comes from its configuration's own ``graph.seed``: its
    padded sizes set the shapes of the compiled programs, so a graph drawn
    from ``--seed`` would recompile them on every seed."""
    ss = np.random.SeedSequence([seed & (2**64 - 1), stream])
    return int(ss.generate_state(1, np.uint64)[0])


class _Compiles:
    """Backend compiles (persistent-cache loads included) in this
    process, from JAX's monitoring events."""

    count = 0
    seconds = 0.0
    names: list = []
    cache = {"hits": 0, "misses": 0}
    _on = False

    @classmethod
    def install(cls) -> type:
        if not cls._on:
            import jax

            def on(event, secs, fun_name="?", **_):
                if event == COMPILE_EVENT:
                    cls.count += 1
                    cls.seconds += secs
                    cls.names.append(fun_name)

            def on_cache(event, **_):
                for key in cls.cache:
                    if event == CACHE_EVENT + key:
                        cls.cache[key] += 1

            jax.monitoring.register_event_duration_secs_listener(on)
            jax.monitoring.register_event_listener(on_cache)
            cls._on = True
        return cls


@dataclasses.dataclass
class Sent:
    query: traffic_mod.Query
    ticket: object
    sent_at: float


class _Load:
    """Submits a mix's queries to the engine and pumps it."""

    def __init__(self, eng, graph: str, traced: bool):
        self.eng, self.graph = eng, graph
        self.traced = traced
        self.sent: list[Sent] = []

    def span(self, name: str):
        if not self.traced:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def send(self, q: traffic_mod.Query) -> None:
        t = self.eng.submit(self.graph, q.source, kind=q.kind,
                            target=q.target)
        self.sent.append(Sent(q, t, time.monotonic()))

    def closed(self, stream, clients: int, until) -> None:
        """Closed loop: ``clients`` queries outstanding until ``until()``."""
        with self.span("bench.submit"):
            for _ in range(clients):
                self.send(stream.next())
        while not until():
            with self.span("bench.step"):
                done = self.eng.step()
            if done:
                with self.span("bench.submit"):
                    for _ in done:
                        if not until():
                            self.send(stream.next())
            elif self.eng.in_flight == 0:
                time.sleep(0.001)  # an artifact build is still running

    def drain(self, timeout: float) -> None:
        """Pump until every sent query is terminal, or ``timeout``."""
        stop = time.monotonic() + timeout
        while (any(not s.ticket.done() for s in self.sent)
               and time.monotonic() < stop):
            if self.eng.step():
                continue
            if not self.eng.has_work():
                break
            if self.eng.in_flight == 0:
                time.sleep(0.001)  # an artifact build is still running


def _warm_up(eng, graph: str, mix: dict, n: int, seed: int,
             timeout: float) -> None:
    """Answer ``warmup_queries`` of the mix, as a closed loop, so that the
    artifact is built and every shape the window uses is compiled; give
    up after ``timeout`` seconds (the window then shows what is wrong).

    Queries that return a whole level array finish in groups, and the
    engine ships each group's arrays in one transfer shaped by the group's
    size rounded up to a power of two.  So such queries are also sent as
    groups of 1, 2, 4, ... kappa/2 from one source, one tick apart (they
    then finish on different ticks), and as one group of kappa."""
    drv = _Load(eng, graph, traced=False)
    stream = traffic_mod.Stream(mix, n, seed)
    total = int(mix["warmup_queries"])
    clients = min(int(mix.get("clients", 2 * eng.kappa)), total)
    stop = time.monotonic() + timeout
    drv.closed(stream, clients,
               lambda: len(drv.sent) >= total or time.monotonic() > stop)
    drv.drain(max(0.0, stop - time.monotonic()))
    source = int(np.random.default_rng(seed).integers(n))
    for kind in sorted(set(mix["kinds"]) & LEVEL_KINDS):
        q = traffic_mod.Query(kind, source, None)
        size = 1
        while size < eng.kappa:
            for _ in range(size):
                drv.send(q)
            eng.step()
            size *= 2
        drv.drain(max(0.0, stop - time.monotonic()))
        for _ in range(eng.kappa):
            drv.send(q)
        drv.drain(max(0.0, stop - time.monotonic()))


def _warm_levels(eng, graph: str) -> int:
    """Compile the dense level and the queued level at every bucket size
    the Eq. 6 policy can pick on this graph (powers of two below the
    padded VSS count, where the engine's bucket guard turns to dense).

    Which buckets a traffic warm-up reaches depends on the frontiers its
    queries happen to make, so without this a window of many queries can
    meet a bucket the warm-up did not.  The programs are the serving
    runner's own (its jit cache), run on a scratch state.  Returns how
    many programs ran, or 0 where the engine no longer has these
    internals (the window's ``compiles`` count then shows what is left)."""
    import jax

    from repro.core.blest import bucket_size

    art = eng.cache.peek(graph)
    if art is None:
        return 0
    runner = eng._runner_for(art)
    num_vss, num_vss_pad = int(art.bd.num_vss), int(art.bd.num_vss_pad)
    state = runner.init_state()
    jax.block_until_ready(runner.level(state, 1))
    ran, k = 1, 1
    while bucket_size(k) < num_vss_pad and k <= num_vss:
        qids = runner.bucket_qids(np.arange(k, dtype=np.int32))
        jax.block_until_ready(runner.level_queued(state, 1, qids))
        ran += 1
        k = 2 * bucket_size(k)
    return ran


def artifact_summary(art, kappa: int) -> dict:
    """What the metric readers may read of the cached artifact."""
    bd = getattr(art, "bd", None)
    sw = getattr(art, "switching", None)
    shape_keys = ("n_ext", "num_vss_pad", "tau", "sigma", "num_sets",
                  "num_sets_ext")
    return {
        "build_s": getattr(art, "build_s", None),
        "probe_s": getattr(art, "probe_s", None) if sw is not None else None,
        "probe_dense_layout": getattr(sw, "dense_layout", None),
        "probe_policy_on": getattr(sw, "enabled", None),
        "reorder": getattr(getattr(art, "reorder", None), "algorithm", None),
        "shapes": ({k: int(getattr(bd, k)) for k in shape_keys}
                   if bd is not None else None),
        "kappa": kappa,
    }


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             devices, peaks: dict, t_start: float,
             drain_s: float = DRAIN_S, warmup_s: float = WARMUP_S,
             log=print) -> dict:
    """Run ``cell`` once and return its result line (a dict).  ``devices``
    are the chips the cell uses; ``t_start`` is the process's start on the
    ``time.perf_counter`` clock; ``log`` takes the lines for stderr."""
    import jax

    from repro.core.graph import Graph
    from repro.serve.bfs_engine import BfsEngine

    compiles = _Compiles.install()
    c_start = compiles.count
    mix = cell.traffic
    t = time.perf_counter()
    n, src, dst = generators.make(cell.config["graph"])
    gen_s = time.perf_counter() - t
    eng = BfsEngine(**cell.config["engine"])
    graph = cell.config_name
    eng.register_graph(graph, Graph(n, src, dst))
    c0, t = compiles.seconds, time.perf_counter()
    _warm_up(eng, graph, mix, n, sub_seed(seed, 1), warmup_s)
    warmed_levels = _warm_levels(eng, graph)
    warm_s = time.perf_counter() - t
    art = artifact_summary(eng.cache.peek(graph), eng.kappa)
    drv = _Load(eng, graph, traced)
    stream = traffic_mod.Stream(mix, n, sub_seed(seed, 2))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    with (trace_mod.record(trace_dir) if traced
          else contextlib.nullcontext()):
        c_win = compiles.count
        stats0 = dict(eng.stats)
        t0 = time.monotonic()
        setup_s = time.perf_counter() - t_start
        t_end = t0 + seconds
        with drv.span("bench.window"):
            drv.closed(stream, int(mix["clients"]),
                       lambda: time.monotonic() >= t_end)
        t_stop = time.monotonic()
        stats1 = dict(eng.stats)
        window_compiles = compiles.count - c_win
    drv.drain(drain_s)
    memory_peak = peak_bytes(devices)
    sent = drv.sent
    done = [s for s in sent if s.ticket.state == "DONE"]
    missing = len(sent) - len(done)
    # every query sent in the window, with the seconds it waited for its
    # answer (drained after the close where need be; None if none came)
    window = [{"kind": s.query.kind,
               "latency_s": (s.ticket.completed_at - s.sent_at
                             if s.ticket.state == "DONE" else None),
               "queue_wait_s": s.ticket.queue_wait,
               "in_window": (s.ticket.state == "DONE"
                             and s.ticket.completed_at <= t_end)}
              for s in sent]
    answers = [compare.program_answer(s.query.kind, s.ticket.result())
               for s in done]
    queries = [s.query for s in done]
    del eng, drv, sent
    gc.collect()
    t = time.perf_counter()
    verdict = compare.compare(reference.Csr(n, src, dst), queries, answers,
                              missing)
    check_s = time.perf_counter() - t
    red = None
    if traced:
        red = trace_mod.reduce(trace_mod.xplane_file(trace_dir),
                               chips=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup = {"gen_s": gen_s, "build_s": art["build_s"],
             "probe_s": art["probe_s"], "warm_s": warm_s,
             "compile_s": compiles.seconds - c0, "setup_s": setup_s}
    rec = {"cell": cell.name, "seed": seed, "setup": setup, "artifact": art,
           "window": {"seconds": seconds, "elapsed_s": t_stop - t0,
                      "compiles": window_compiles},
           "queries": window, "stats": {"start": stats0, "end": stats1},
           "trace": red, "peaks": peaks}
    log("[setup] " + " ".join(
        f"{k}={v}" for k, v in {**setup, "reorder": art["reorder"],
                                "probe_dense_layout": art["probe_dense_layout"],
                                "probe_policy_on": art["probe_policy_on"],
                                "warmed_levels": warmed_levels,
                                "compiles": c_win - c_start,
                                "cache": compiles.cache}.items()))
    log(f"[window] seconds={seconds} elapsed_s={t_stop - t0} "
        f"sent={len(window)} "
        f"answered_in_window={sum(q['in_window'] for q in window)} "
        f"compiles={window_compiles} "
        f"compiled={','.join(compiles.names[c_win:c_win + window_compiles])}")
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = reader(cell.bench_dir, m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    out = {"correct": verdict.correct,
           "attempted": len(window),
           "failed": verdict.wrong + missing,
           "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = red.busy_s
        device["window_s"] = t_stop - t0
        out["breakdown"] = trace_mod.breakdown(red)
    log(f"[check] checked={verdict.checked} check_s={check_s} "
        f"first_wrong={verdict.first_wrong}")
    for name, c in verdict.limits().items():
        log(f"check {name}={c['value']} pass_if {c['pass_if']} {c['limit']}")
    out["checks"] = verdict.limits()
    return out
