"""Batched BFS query engine over packed MS-BFS lanes (DESIGN.md §6).

The paper's headline serving scenario — millions of single-source
traversal queries against a fleet of preprocessed graphs — needs three
things the script-style drivers in :mod:`repro.core` do not provide:

  1. an **admission queue**: independent BFS / closeness requests against
     *named* graphs arrive in any order and are served in FIFO order;
  2. **lane packing**: up to ``kappa`` concurrent requests against the same
     graph are packed into one multi-source traversal (one bit-lane per
     request, the kappa-bit state of ``core/msbfs_packed.py``), so the BVSS
     masks are streamed once per level for the whole batch instead of once
     per query;
  3. **continuous batching**: lanes have independent lifecycles.  A lane
     whose frontier empties is *early-exited* (its result is extracted and
     returned) and its slot is re-seeded with the next queued request
     **mid-flight**, without restarting the lanes still traversing — the
     graph-query analogue of slot refill in ``serve/serve_loop.BatchEngine``.

Per-graph artifacts (reordering permutation + BVSS + device arrays) are
built once and held in :class:`GraphCache`, an LRU keyed on the graph name
and bounded by device bytes, so a long-running service can serve many more
graphs than fit on the accelerator at once.

Lane substrates
---------------
Three bit-for-bit equivalent lane layouts implement the level step:

* ``layout='packed'`` — the paper-faithful kappa-bit packed words
  (``(n_ext, kappa/32)`` uint32).  Dense levels run the
  ``kernels/pull_ms_packed.py`` Pallas pull and OR its marks into their
  rows with a gather over a static slot table (``kernels/gather_or.py``,
  DESIGN.md §11.2); queued levels run
  ``kernels/pull_ms_packed_queued.py`` + ``kernels/scatter_or.py`` (or
  their jnp references when ``use_pallas=False``).  1/8 the state
  traffic; the TPU path.
* ``layout='byteplane'`` — ``(n_ext, kappa)`` uint8 byte-planes using the
  XLA-native scatter-max OR (``core/msbfs.py`` mechanics), slice-compacted
  to the static nonzero-mask slot list on the jnp path (§11.2).  The fast
  path on CPU backends, where Pallas interpret mode is impractical.
* ``layout='mma'`` — the tensor-core formulation (DESIGN.md §13): dense
  levels route the pull through blocked binary matrix products
  (``kernels/pull_mma_ms_packed.py``) instead of selective-OR ladders,
  over the packed substrate when Pallas kernels are on (the fused MMA
  scatter variant feeds the MXU) and over the slice-compacted byteplane
  substrate otherwise (the AND-OR/popcount fallback).  Queued levels are
  substrate-shared with the host layout.  Needs the per-graph
  :class:`~repro.kernels.pull_mma_ms_packed.MmaTiles` (int8 mask planes,
  built by ``GraphArtifacts`` tile prep and counted against the cache
  budget).

``layout='auto'`` picks packed on TPU, byteplane elsewhere — unless the
switching probe also timed the MMA runner and its ``dense_layout`` verdict
says the bit-MMA dense path wins on this graph (§13.4).  Results are
identical in every layout (tests/test_serve_engine.py,
tests/test_mma_layout.py assert it), so the choice is purely a
performance knob.

Per-level mode switching (DESIGN.md §10)
----------------------------------------
Each level is executed by one of two sweeps, chosen by the paper's Eq. (6)
policy (``core/switching.decide_mode``) over the *aggregate* frontier of
all packed lanes:

* ``dense``  — the full sweep over every VSS (work ~ N_v * tau), inactive
  VSSs neutralized by zero frontier words; the bottom-up analogue and the
  only mode the engine had before switching landed.
* ``queued`` — frontier-compacted: the union of active VSSs across lanes is
  expanded host-side (realPtrs ranges), bucket-padded to a power of two,
  and pulled via ``kernels/pull_ms_packed_queued.py`` (packed substrate,
  blocked over the device-gathered queued VSSs, work ~ |Q| * tau) or an XLA
  take-based path (byteplane; slice-compacted through ``_nz_ptrs`` on the
  jnp path, work ~ |active slices| — §11.2).

Whether the policy runs at all is the ``switching`` knob: ``'off'`` forces
dense (legacy behaviour), ``'on'`` applies Eq. (6) unconditionally, and
``'auto'`` defers to the per-graph preprocessing probe — the serve-aware
``probe_switching_benefit_serve``, which times this engine's own lane
runner (DESIGN.md §11.3) — run once per admitted graph by
:class:`GraphCache` and cached in the artifact (DESIGN.md §10.3).  Switching is
performance-only: results stay bit-identical to ``core/ref_bfs.py`` in
every mode (``eta=0`` with ``switching='on'`` forces queued every level;
tests/test_serve_switching.py pins all three against the oracle).

Per-lane state (either layout) also carries ``levels`` (n_ext, kappa)
int32 — *global* level stamps.  A lane stamps its discoveries with the
global level counter; extraction subtracts the lane's admission level
(tracked host-side per lane), so mid-flight admission needs no per-lane
loop skew handling.  Per-lane ``reach`` and the Eq.(7) ``far`` sum
(single-source closeness) are accumulated host-side in int64 from the
per-level new-vertex counts the level step already returns — the device
int32 would overflow on paper-scale graphs (cf. core/closeness.py), and a
device reach column would only mirror what the host tracks anyway.

Service API (DESIGN.md §12)
---------------------------
``submit()`` returns a :class:`Ticket` — an ``int`` (the request id, so
every pre-ticket call site keeps working) that doubles as a completion
handle: ``done()``, ``result()``, and submit/admit/complete timestamps
for latency accounting.  ``engine.step()`` advances **one scheduling
tick** and returns the newly completed tickets; submission is legal
between steps, so a caller can pump the engine inside its own event loop
(true online serving).  ``run()`` is now a thin drain loop over
``step()`` with unchanged results.

Per graph, the serving state that used to live in a monolithic drain
loop is a resumable :class:`_GraphSession` (lane set, runner, megatick
window state held across ticks), so multiple graphs are in flight
simultaneously; a round-robin scheduler (optionally weighted, see
``BfsEngine(scheduler=, weights=)``) interleaves their ticks,
eliminating the cross-graph head-of-line blocking of the PR 1 engine —
a backlog on one graph no longer starves a single query on another
(``benchmarks/serve_fairness.py`` measures exactly this).

What a lane computes is a :class:`repro.serve.workloads.Workload`
plugin (§12.3): ``bfs`` and ``closeness`` are plugins now, joined by
``distance`` (s→t point-to-point, the lane early-exits the tick its
target's bit lights up) and ``reach`` (count only, no level-array
transfer); ``BfsEngine.register_workload`` adds more.

Service hardening (DESIGN.md §14)
---------------------------------
Tickets carry an explicit lifecycle (``QUEUED ⇄ BUILDING → RUNNING →
DONE | REJECTED | FAILED``, §14.1) and the engine never blocks a
``step()`` on artifact construction: a cache-miss graph's build
(reorder + BVSS + probe, the Table 7 preprocessing cost) runs on
:class:`GraphCache`'s bounded background builder pool (§14.3), its
tickets sit in ``BUILDING``, and the session opens only once the
artifact lands — a slow or *failing* build never stalls another
graph's tick; build exceptions surface as per-ticket ``FAILED``
results instead of crashing the engine.  Admission is a policy
(§14.2): per-graph and global queue-depth caps shed load at
``submit()`` time (``overload='reject'`` → ``REJECTED`` tickets,
``'defer'`` → a holding queue promoted as capacity frees), and
per-tenant weights (``tenant_weights=``) give the per-graph queues
weighted-round-robin admission across tenants so a heavy tenant
cannot starve a light one of lane slots.  Timestamps come from an
injectable clock (``BfsEngine(clock=)``), so latency/SLO accounting
is testable without sleeps; ``benchmarks/serve_overload.py`` drives
the engine past capacity with Zipf-popularity traffic and measures
the p99 a capped queue buys.

Megatick traversal (DESIGN.md §11)
----------------------------------
``BfsEngine(megatick=T)`` with ``T > 1`` moves the per-graph level loop
on-device: up to ``T`` consecutive dense levels run inside one
``jax.lax.while_loop`` dispatch (pull + slot-table gather-OR on the
packed substrate, diff, level
stamps, per-lane reach, the Eq. (6) decision, and per-lane done flags all
stay resident), returning to host only when every active lane has
finished, when the policy picks a queued level (executed host-side with
the §10 bucketed machinery, then the loop re-enters), or when ``T`` ticks
elapse.  Scheduling is queue-aware: windows engage once a graph's queue
has drained; under backlog the engine keeps the per-level path so a freed
slot is refilled the very next level — continuous batching semantics are
those of ``T = 1`` exactly.  A lane finishing inside a window *parks*
(its empty frontier freezes its columns), and extraction at window end
reads what extraction at the finish tick would have.  ``megatick=1`` is
the legacy per-level engine, bit-identical results either way
(tests/test_megatick.py).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from collections import OrderedDict, deque
from concurrent.futures import (
    FIRST_COMPLETED, ThreadPoolExecutor, wait as _futures_wait)
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blest, reorder as reorder_mod
from repro.core import switching as switching_mod
from repro.core.blest import (
    UNREACHED, BvssDevice, bucket_size, expand_active_sets)
from repro.core.bvss import Bvss, BvssConfig, build_bvss
from repro.core.graph import Graph
from repro.core.msbfs_packed import frontier_planes, unpack_levels_check
from repro.kernels import ops
from repro.kernels import pull_mma_ms_packed as mma_mod
from repro.kernels.gather_or import gather_or, slot_table
from repro.kernels.pull_ms_packed import (
    lanes_of, pull_ms_packed_lanes, pull_ms_packed_ref)
from repro.kernels.pull_ms_packed_queued import (
    pull_ms_packed_queued, pull_ms_packed_queued_ref)
from repro.kernels.scatter_or import scatter_or, scatter_or_ref
from repro.serve import lifecycle as lifecycle_mod
from repro.serve import mesh as mesh_mod
from repro.serve import spans as spans_mod
from repro.serve import workloads as workloads_mod
from repro.serve.workloads import (  # re-exported: the request/result
    KIND_BFS, KIND_CLOSENESS, KIND_DISTANCE, KIND_REACH,  # noqa: F401
    KIND_CC, KIND_MIS, KIND_TPV,  # noqa: F401
    BfsQuery, BfsResult, Workload)

SWITCHING_MODES = ("auto", "on", "off")
SCHEDULERS = ("rr", "serial")
LAYOUTS = ("auto", "packed", "byteplane", "mma")
OVERLOAD_POLICIES = ("reject", "defer")
# the device -> host read-backs of the serving path, each with whether the
# read launches a gather program of its own (the others read the output of
# a level or a megatick window)
SYNC_SITES = {"active_mask": True, "new_lane": False, "megatick": False,
              "watch": True, "gather_cols": True}
# the host spans of the serving path (serve/spans.py); their self seconds
# are ``stats["host_s:<name>"]``
SPAN_NAMES = ("serve.step", "serve.housekeep", "serve.round", "serve.tick",
              "serve.admit", "serve.decide", "serve.expand", "serve.dispatch",
              "serve.hooks", "serve.finish",
              *("serve.sync." + s for s in SYNC_SITES))


# ---------------------------------------------------------------------------
# Tickets (requests/results live in serve/workloads.py, re-exported above)
# ---------------------------------------------------------------------------


class TicketState:
    """Ticket lifecycle (DESIGN.md §14.1, extended by §16)::

        QUEUED ⇄ BUILDING → RUNNING → DONE
           ↓                    ↓         (terminal)
        REJECTED / FAILED / EXPIRED / CANCELLED (terminal)

    ``QUEUED`` waits for a lane with the artifact resident; ``BUILDING``
    waits for the graph's background artifact build — the two swap
    whenever the artifact is evicted (build rescheduled) or lands (back
    to the lane queue).  ``RUNNING`` is seeded into a lane.  Terminal:
    ``DONE`` (result extracted), ``REJECTED`` (shed at submission by the
    §14.2 admission policy), ``FAILED`` (the artifact build raised;
    ``ticket.error`` carries the cause), ``EXPIRED`` (deadline passed or
    its violation was predicted, §16.1 — at submission, at lane seeding,
    or at a window boundary), ``CANCELLED`` (the caller's
    ``ticket.cancel()``, §16.2 — immediate while waiting, at the next
    window boundary once seeded)."""

    QUEUED = "QUEUED"
    BUILDING = "BUILDING"
    RUNNING = "RUNNING"
    DONE = "DONE"
    REJECTED = "REJECTED"
    FAILED = "FAILED"
    EXPIRED = "EXPIRED"
    CANCELLED = "CANCELLED"
    TERMINAL = frozenset({DONE, REJECTED, FAILED, EXPIRED, CANCELLED})


class TicketError(RuntimeError):
    """Base class of the terminal-failure errors ``Ticket.result`` raises."""


class TicketRejected(TicketError):
    """``result()`` of a ticket shed by admission control (§14.2)."""


class TicketFailed(TicketError):
    """``result()`` of a ticket whose graph's artifact build failed (§14.3)."""


class TicketExpired(TicketError):
    """``result()`` of a ticket shed or reclaimed by its deadline (§16.1)."""


class TicketCancelled(TicketError):
    """``result()`` of a ticket the caller cancelled (§16.2)."""


class Ticket(int):
    """``submit``'s return value: the request id as an ``int`` subclass —
    every pre-ticket call site (``results[rid]`` indexing, set/dict keys)
    keeps working — that doubles as a non-blocking completion handle
    (DESIGN.md §12.1).

    ``done()`` is an O(1) host check (any terminal §14.1 state);
    ``result()`` returns the :class:`BfsResult` (by default pumping
    ``engine.step()`` until this request reaches a terminal state —
    ``wait=False`` raises instead of pumping), or raises
    :class:`TicketRejected` / :class:`TicketFailed` for requests that
    terminated without a result.  ``state`` is the current §14.1
    lifecycle state; ``error`` the human-readable cause of a
    ``REJECTED``/``FAILED`` terminal.  Timestamps (engine-clock seconds,
    ``time.monotonic`` unless ``BfsEngine(clock=)`` injects a fake)
    support latency accounting: ``submitted_at`` is stamped at
    submission, ``admitted_at`` when the request is seeded into a lane
    (``queue_wait`` = admitted − submitted), ``completed_at`` at
    extraction — or rejection/failure — (``latency`` = completed −
    submitted).

    The engine holds the ticket only while the request is pending; once
    completed, the result lives on the ticket alone, so result lifetime is
    the caller's — dropping the ticket drops the result (no unbounded
    retention in a long-running service; cf. ``keep_results``)."""

    _engine: "BfsEngine"
    query: BfsQuery
    state: str
    error: str | None
    submitted_at: float
    admitted_at: float | None
    completed_at: float | None
    deadline: float | None
    deadline_at: float | None
    cancel_requested: bool
    _result: BfsResult | None

    def __new__(cls, rid: int, engine: "BfsEngine", query: BfsQuery,
                deadline: float | None = None):
        t = super().__new__(cls, rid)
        t._engine = engine
        t.query = query
        t.state = TicketState.QUEUED
        t.error = None
        t.submitted_at = engine._clock()
        t.admitted_at = None
        t.completed_at = None
        # SLO budget (§16.1): relative seconds granted at submission and
        # the absolute engine-clock instant the budget runs out
        t.deadline = deadline
        t.deadline_at = (None if deadline is None
                         else t.submitted_at + deadline)
        t.cancel_requested = False
        t._result = None
        return t

    def done(self) -> bool:
        return self.state in TicketState.TERMINAL

    def cancel(self) -> bool:
        """Withdraw this request (§16.2).  A waiting ticket
        (``QUEUED``/``BUILDING``/deferred) goes terminal ``CANCELLED``
        immediately and its queue slot is freed; a ``RUNNING`` one is
        flagged and its lane is reclaimed at the next megatick window
        boundary (the column is parked and wiped, the lane returns to
        the free set, the other lanes' bits are untouched).  Returns
        True when the request is or will be cancelled, False when it
        already reached a terminal state (including a prior
        cancellation) — cancel never un-completes anything.  The
        terminal notification is delivered through ``step()`` exactly
        once, like every other in-engine terminal."""
        return self._engine._cancel(self)

    def result(self, *, wait: bool = True) -> BfsResult:
        """The finished :class:`BfsResult`.  ``wait=True`` (default) pumps
        ``engine.step()`` until this request reaches a terminal state;
        ``wait=False`` raises RuntimeError when it has not completed yet.
        A ticket shed by admission control raises :class:`TicketRejected`;
        one whose graph's artifact build failed raises
        :class:`TicketFailed` — in both cases regardless of ``wait``.

        Other requests completing during the pump are re-queued onto the
        engine's completion stream (only this ticket's own notification
        is consumed), so a surrounding ``step()``/``run()`` loop still
        sees every completion exactly once."""
        if not self.done() and wait:
            eng = self._engine
            # foreign completions are parked locally during the pump (a
            # step()-returned ticket fed straight back into eng._completed
            # would be drained and re-parked on every remaining iteration)
            # and re-queued in one batch when the pump ends
            others: list[Ticket] = []
            while not self.done() and eng.has_work():
                stepped = eng.step()
                others.extend(t for t in stepped if t is not self)
                if not stepped:
                    eng._idle_wait()
            eng._completed.extend(others)
        if self.state == TicketState.REJECTED:
            raise TicketRejected(
                self.error or f"request {int(self)} was shed")
        if self.state == TicketState.FAILED:
            raise TicketFailed(
                self.error or f"request {int(self)} failed")
        if self.state == TicketState.EXPIRED:
            raise TicketExpired(
                self.error or f"request {int(self)} missed its deadline")
        if self.state == TicketState.CANCELLED:
            raise TicketCancelled(
                self.error or f"request {int(self)} was cancelled")
        if self._result is None:
            raise RuntimeError(f"request {int(self)} has not completed"
                               + ("" if wait else " (wait=False)"))
        return self._result

    @property
    def queue_wait(self) -> float | None:
        """Seconds from submission to lane admission (None while queued)."""
        if self.admitted_at is None:
            return None
        return self.admitted_at - self.submitted_at

    @property
    def latency(self) -> float | None:
        """Seconds from submission to completion (None until done)."""
        if self.completed_at is None:
            return None
        return self.completed_at - self.submitted_at


# ---------------------------------------------------------------------------
# Per-graph artifact cache (LRU by device bytes)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GraphArtifacts:
    """Everything needed to serve one graph: built once, cached, reused.

    Beyond the device substrate this carries the per-graph *policy* tuned at
    preprocessing time (DESIGN.md §10.3): the reordering dispatch verdict
    (``reorder``, from ``core/reorder.reorder``) and the switching probe
    verdict (``switching``, ``None`` unless the probe ran), so per-request
    traversals get the tuned policy for free on cache hits.
    """

    name: str
    graph: Graph
    bvss: Bvss
    bd: BvssDevice
    perm: np.ndarray        # old id -> new id (pi^{-1})
    reorder: reorder_mod.ReorderResult
    switching: switching_mod.SwitchingDecision | None
    device_bytes: int       # substrate arrays resident on the accelerator
    aux_bytes: int          # reorder/probe/MMA-tile artifacts alongside them
    # MMA-layout tile prep (DESIGN.md §13.1): int8 mask planes + padded
    # scatter metadata, built when the engine may route this graph through
    # the bit-MMA pull; its nbytes are in aux_bytes (the eviction budget
    # must see layout-auxiliary device arrays too, or the cache over-admits)
    mma: mma_mod.MmaTiles | None = None
    # §16.4 graceful degradation: a tile-prep exception does not fail the
    # build — the cause lands here and the engine quarantines the
    # (graph, 'mma') pair, serving the base layout instead
    degraded: str | None = None
    # §17 mesh serving: per-device replicas of ``bd`` (source-parallel),
    # or a row-sharded substrate spanning the group (graph-parallel);
    # ``placement`` pins the sessions to the group's device ids and
    # ``per_device_bytes`` is what each of those devices holds resident
    replicas: list | None = None
    sharded: "mesh_mod.ShardedGraph | None" = None
    placement: tuple = ()
    per_device_bytes: dict | None = None
    # set-up seconds on the host clock: reorder + BVSS + device transfer +
    # tile prep (build_s), and the switching probe's traversals (probe_s)
    build_s: float = 0.0
    probe_s: float = 0.0

    @property
    def total_bytes(self) -> int:
        """What this entry costs the cache budget (DESIGN.md §10.3)."""
        if self.per_device_bytes:
            return sum(self.per_device_bytes.values()) + self.aux_bytes
        return self.device_bytes + self.aux_bytes


# nominal footprint of a cached SwitchingDecision (three scalars + header);
# counted so probe artifacts are visible to the cache bound, per §10.3
_PROBE_DECISION_BYTES = 64


def build_artifacts(name: str, g: Graph, *, reorder: str | None = None,
                    config: BvssConfig | None = None,
                    probe: bool = False,
                    eta: float = switching_mod.ETA_DEFAULT,
                    probe_use_pallas: bool = False,
                    probe_runner=None,
                    mma_tiles: bool = False,
                    prebuilt: tuple | None = None) -> GraphArtifacts:
    """Preprocess ``g`` for serving: reorder -> BVSS -> device arrays, plus
    (``probe=True``) the paper's switching probe, whose verdict is cached
    in the artifact.  ``probe_runner`` (a ``bd -> runner`` factory, supplied
    by :class:`BfsEngine`) switches the probe from the single-source
    ``BucketedBfs`` proxy to the serve-aware variant that times the
    kappa-lane runner itself (DESIGN.md §11.3).

    ``mma_tiles=True`` additionally runs the §13.1 tile prep (int8 MMA
    mask planes, cached in ``art.mma`` and counted in ``aux_bytes``); the
    tiles are then handed to ``probe_runner`` as a second argument so the
    probe can time the bit-MMA dense path and record a ``dense_layout``
    verdict (§13.4) — factories taking one argument are only ever called
    when no tiles were requested."""
    config = config or BvssConfig()
    t_build = time.perf_counter()
    if prebuilt is not None:
        # §17: the mesh build path already ran reorder + BVSS on host (it
        # needed the byte projection before deciding how to place) — do
        # not redo the expensive preprocessing
        rr, b = prebuilt
    else:
        rr = reorder_mod.reorder(g, sigma=config.sigma, force=reorder)
        gp = g.permuted(rr.perm)
        b = build_bvss(gp, config)
    bd = blest.to_device(b)
    tiles, degraded = None, None
    if mma_tiles:
        # §16.4: the MMA tiles are a layout *accelerator*, not a
        # correctness requirement — a tile-prep exception degrades this
        # graph to the base substrate instead of failing every ticket
        try:
            tiles = mma_mod.prep_mma_tiles(bd)
        except Exception as e:  # noqa: BLE001 — any tile-prep error
            degraded = f"mma tile prep raised: {e!r}"
    build_s = time.perf_counter() - t_build
    t_probe = time.perf_counter()
    sw = None
    if probe:
        if probe_runner is not None:
            made = (probe_runner(bd, tiles) if tiles is not None
                    else probe_runner(bd))
            base, alt = (made if isinstance(made, tuple) else (made, None))
            sw = switching_mod.probe_switching_benefit_serve(
                base, g.n, eta=eta, mma_runner=alt)
        else:
            sw = switching_mod.probe_switching_benefit(
                bd, eta=eta, use_pallas=probe_use_pallas)
    probe_s = time.perf_counter() - t_probe
    arrays = [bd.masks, bd.row_ids, bd.v2r, bd.real_ptrs]
    if bd.masks_packed is not bd.masks:  # aliased when tau % 4 != 0
        arrays.append(bd.masks_packed)
    dev_bytes = sum(int(a.nbytes) for a in arrays)
    perm = np.asarray(rr.perm)
    # the O(n) permutation, the probe verdict, and the MMA tile prep live
    # for exactly as long as the entry does, so they count against the
    # eviction budget too — previously only the substrate arrays were
    # accounted
    aux_bytes = (int(perm.nbytes) + (_PROBE_DECISION_BYTES if sw else 0)
                 + (tiles.nbytes if tiles is not None else 0))
    return GraphArtifacts(name=name, graph=g, bvss=b, bd=bd, perm=perm,
                          reorder=rr, switching=sw,
                          device_bytes=dev_bytes, aux_bytes=aux_bytes,
                          mma=tiles, degraded=degraded,
                          build_s=build_s, probe_s=probe_s)


class GraphCache:
    """LRU cache of :class:`GraphArtifacts`, bounded by total device bytes.

    ``register`` records how to build a graph's artifacts (cheap); ``get``
    builds on first use and evicts least-recently-used entries until the
    byte budget holds.  The entry being returned is never evicted, so a
    budget smaller than a single graph still serves (with rebuild churn,
    visible in ``stats``).

    Builds can also run **asynchronously** (DESIGN.md §14.3):
    ``start_build`` schedules :func:`build_artifacts` on a bounded
    background pool (at most ``builders`` threads; further builds queue
    behind them) and ``poll_builds`` — called from the owner's thread —
    installs finished artifacts and reports failures.  The split keeps
    the threading contract trivial: worker threads only ever read the
    immutable ``_specs``; every ``_entries``/stats mutation happens on
    the polling thread.  ``fault_hook`` (a ``fn(name)`` called at the
    top of every build, sync or async) is the §14.3 fault-injection
    point — raising from it fails the build exactly like a real
    preprocessing error (:class:`repro.serve.lifecycle.ScriptedFaults`
    scripts flaky-then-succeed sequences through it).

    Build failures are classified (§16.3,
    :func:`repro.serve.lifecycle.classify_build_failure`): a transient
    failure earns up to ``build_retries`` further attempts under capped
    exponential backoff (``retry_backoff`` doubling up to
    ``retry_backoff_cap``, timed on the injectable ``clock``) before it
    is reported terminal; a permanent one is reported on the first.
    Synchronous ``get`` retries inline without backoff (the caller is
    already blocking).  Dispatch beyond the ``builders`` thread bound is
    a priority queue, not FIFO: ``build_priority`` (a ``name -> int``
    callable, read on the polling thread) picks the parked build with
    the highest score — the engine wires it to queued depth so the
    build unblocking the most tickets runs first (§16.5)."""

    def __init__(self, max_bytes: int | None = None,
                 config: BvssConfig | None = None, *,
                 probe: bool = False,
                 eta: float = switching_mod.ETA_DEFAULT,
                 probe_use_pallas: bool = False,
                 probe_runner=None,
                 mma_tiles: bool = False,
                 builders: int = 1,
                 fault_hook=None,
                 build_retries: int = 0,
                 retry_backoff: float = 0.05,
                 retry_backoff_cap: float = 2.0,
                 clock=None):
        if builders < 1:
            raise ValueError(f"builders must be >= 1, got {builders}")
        if build_retries < 0:
            raise ValueError(
                f"build_retries must be >= 0, got {build_retries}")
        if retry_backoff <= 0 or retry_backoff_cap < retry_backoff:
            raise ValueError(
                f"need 0 < retry_backoff <= retry_backoff_cap, got "
                f"{retry_backoff} / {retry_backoff_cap}")
        self.max_bytes = max_bytes
        self.config = config or BvssConfig()
        self.probe = probe
        self.eta = eta
        self.probe_use_pallas = probe_use_pallas
        self.probe_runner = probe_runner
        self.mma_tiles = mma_tiles
        self.builders = int(builders)
        self.fault_hook = fault_hook
        self.build_retries = int(build_retries)
        self.retry_backoff = float(retry_backoff)
        self.retry_backoff_cap = float(retry_backoff_cap)
        self._clock = time.monotonic if clock is None else clock
        # §16.5 dispatch priority: name -> int, higher first (None = FIFO)
        self.build_priority = None
        self._specs: dict[str, tuple[Graph, str | None]] = {}
        self._entries: OrderedDict[str, GraphArtifacts] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.retries = 0
        self._evict_listeners: list = []
        # in-flight background builds: name -> Future[GraphArtifacts].
        # The executor is created lazily and torn down whenever the build
        # set drains, so idle engines hold no threads.
        self._builds: dict = {}
        # accepted builds waiting for a worker slot (insertion-ordered;
        # _dispatch picks by build_priority) and §16.3 backoff state:
        # name -> (attempts so far, clock instant the retry is due)
        self._build_queue: OrderedDict[str, None] = OrderedDict()
        self._retry: dict[str, tuple[int, float]] = {}
        self._attempts: dict[str, int] = {}
        self._executor: ThreadPoolExecutor | None = None
        # §17.3 mesh hooks, set by the engine after construction: a
        # replacement build callable ``fn(name, graph, reorder) -> art``
        # (mesh-aware placement + sharding decisions live there), a
        # per-device byte bound, and the device every non-placed entry
        # is charged to.
        self.build_fn = None
        self.device_budget: int | None = None
        self.default_device_id = int(jax.devices()[0].id)

    def register(self, name: str, graph: Graph, *,
                 reorder: str | None = None) -> None:
        if name in self._specs:
            raise ValueError(f"graph {name!r} already registered")
        self._specs[name] = (graph, reorder)

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def registered(self) -> list[str]:
        return list(self._specs)

    def is_registered(self, name: str) -> bool:
        return name in self._specs

    @property
    def current_bytes(self) -> int:
        # total_bytes, not device_bytes: the perm / probe artifacts an entry
        # pins must count or the configured bound silently over-admits
        return sum(e.total_bytes for e in self._entries.values())

    def _devices_of(self, art: GraphArtifacts) -> dict[int, int]:
        """Device-id -> resident bytes for one entry (§17.3).  Placed
        entries carry their own ``per_device_bytes`` map (replicas or
        shards); everything else is charged whole to the default
        device.  ``aux_bytes`` (perm, probe state) lives on host but is
        charged to the entry's first device so the configured bound
        still covers it."""
        pdb = getattr(art, "per_device_bytes", None)
        if pdb:
            out = dict(pdb)
            first = next(iter(out))
            out[first] += art.aux_bytes
            return out
        return {self.default_device_id: art.total_bytes}

    def per_device(self) -> dict[int, int]:
        """Resident bytes per device id across all entries (§17.3) —
        the accounting surface behind per-device eviction and
        ``engine.health().device_bytes``."""
        out: dict[int, int] = {}
        for art in self._entries.values():
            for dev, nb in self._devices_of(art).items():
                out[dev] = out.get(dev, 0) + nb
        return out

    def peek(self, name: str) -> GraphArtifacts | None:
        """Resident entry without touching LRU order or hit stats (for
        introspection, e.g. printing probe verdicts in launchers)."""
        return self._entries.get(name)

    def on_evict(self, fn) -> None:
        """Register a callback fn(name) fired when an entry is evicted."""
        self._evict_listeners.append(fn)

    def graph(self, name: str) -> Graph:
        return self._specs[name][0]

    def get(self, name: str) -> GraphArtifacts:
        if name in self._entries:
            self.hits += 1
            self._entries.move_to_end(name)
            return self._entries[name]
        if name not in self._specs:
            raise KeyError(f"graph {name!r} not registered")
        if name in self._builds:
            # a synchronous build here would race the worker and install
            # the artifact twice; callers using the async path must
            # poll_builds()/wait_builds() until the in-flight build lands
            raise RuntimeError(
                f"artifact build for {name!r} is in flight on the "
                f"background builder; poll_builds() until it lands")
        self.misses += 1
        art = self._build_sync(name)
        self._install(name, art)
        return art

    def _build_sync(self, name: str) -> GraphArtifacts:
        """The synchronous miss path with §16.3 retries folded inline:
        transient failures are retried up to ``build_retries`` times
        immediately (the caller is already blocking — backoff belongs
        to the background path), permanent ones re-raise at once."""
        attempt = 1
        while True:
            try:
                return self._build(name)
            except Exception as exc:  # noqa: BLE001 — classified below
                if (attempt <= self.build_retries
                        and lifecycle_mod.classify_build_failure(exc)
                        == "transient"):
                    attempt += 1
                    self.retries += 1
                    continue
                raise

    def _build(self, name: str) -> GraphArtifacts:
        """One artifact build (fault hook, then the real preprocessing) —
        shared verbatim by the sync ``get`` path and the §14.3 worker
        threads, which only ever read ``_specs`` (immutable after
        ``register``)."""
        if self.fault_hook is not None:
            self.fault_hook(name)
        g, reorder = self._specs[name]
        if self.build_fn is not None:
            # §17.3: the engine routes builds through the mesh subsystem
            # (replication / row-sharding decided per graph at build time)
            return self.build_fn(name, g, reorder)
        return build_artifacts(name, g, reorder=reorder, config=self.config,
                               probe=self.probe, eta=self.eta,
                               probe_use_pallas=self.probe_use_pallas,
                               probe_runner=self.probe_runner,
                               mma_tiles=self.mma_tiles)

    def _install(self, name: str, art: GraphArtifacts) -> None:
        self._entries[name] = art
        self._entries.move_to_end(name)
        self._shrink()

    # ---- background builds (DESIGN.md §14.3, retries §16.3) ---------------
    def start_build(self, name: str) -> None:
        """Accept ``name``'s artifact build for the background pool.
        No-op when the entry is resident or its build is already pending
        (in flight, parked for a worker slot, or waiting out a backoff).
        Counts a miss — the build *is* the miss work, moved off-thread;
        installation into the LRU happens on the polling thread at the
        next :meth:`poll_builds`.  At most ``builders`` builds run at
        once; beyond that the build parks and :meth:`poll_builds`
        dispatches it by ``build_priority`` when a slot frees (§16.5)."""
        if name in self._entries or self.build_pending(name):
            return
        if name not in self._specs:
            raise KeyError(f"graph {name!r} not registered")
        self.misses += 1
        self._build_queue[name] = None
        self._dispatch()

    def _dispatch(self) -> None:
        """Move parked builds onto worker slots, highest
        ``build_priority`` first (insertion order when unset or tied —
        ``max`` keeps the first of equals)."""
        while self._build_queue and len(self._builds) < self.builders:
            if self.build_priority is None:
                name = next(iter(self._build_queue))
            else:
                name = max(self._build_queue, key=self.build_priority)
            del self._build_queue[name]
            if name in self._entries:  # became resident while parked
                continue
            if self._executor is None:
                self._executor = ThreadPoolExecutor(
                    max_workers=self.builders,
                    thread_name_prefix="artifact-build")
            self._attempts[name] = self._attempts.get(name, 0) + 1
            self._builds[name] = self._executor.submit(self._build, name)

    def _pump_retries(self) -> None:
        """Re-park retries whose §16.3 backoff has elapsed on the clock."""
        if not self._retry:
            return
        now = self._clock()
        for name, (_attempts, due) in list(self._retry.items()):
            if now >= due:
                del self._retry[name]
                if name not in self._entries:
                    self._build_queue[name] = None

    def poll_builds(self) -> list:
        """Collect finished background builds without blocking: install
        each success into the LRU (move-to-end + shrink, exactly like a
        sync miss) and return ``[(name, art_or_None, exc_or_None), ...]``
        for every build that reached a *terminal* outcome since the last
        poll.  A transient failure with retry budget left (§16.3) is not
        terminal: it is scheduled for a backoff retry and not reported.
        The artifact is returned *alongside* installation because a
        same-poll neighbour's install may immediately evict it (§14.3's
        pin-during-build) — the caller holds the reference, not the
        LRU."""
        self._pump_retries()
        finished = [n for n, f in self._builds.items() if f.done()]
        out = []
        for name in finished:
            fut = self._builds.pop(name)
            exc = fut.exception()
            art = None
            if exc is None:
                art = fut.result()
                self._attempts.pop(name, None)
                self._install(name, art)
            else:
                attempts = self._attempts.get(name, 1)
                if (attempts <= self.build_retries
                        and lifecycle_mod.classify_build_failure(exc)
                        == "transient"):
                    self.retries += 1
                    self._retry[name] = (attempts, self._clock()
                                         + lifecycle_mod.backoff_delay(
                                             attempts, self.retry_backoff,
                                             self.retry_backoff_cap))
                    continue
                self._attempts.pop(name, None)
            out.append((name, art, exc))
        self._dispatch()
        if (not self._builds and not self._build_queue
                and self._executor is not None):
            # build set drained: drop the pool so a fleet of engines in
            # one process doesn't accumulate idle threads; the next
            # dispatch lazily re-creates it
            self._executor.shutdown(wait=False)
            self._executor = None
        return out

    def wait_builds(self, timeout: float | None = None) -> bool:
        """Block until at least one in-flight build finishes (or
        ``timeout`` seconds elapse); False when none was in flight.
        Completions still need a :meth:`poll_builds` to install — this is
        the bounded sleep ``run()``-style drain loops use instead of
        spinning (``step()`` itself never calls it).  Event-driven: the
        wait is on the build futures, so it returns the moment one
        lands, not at the timeout."""
        if not self._builds:
            return False
        _futures_wait(list(self._builds.values()), timeout=timeout,
                      return_when=FIRST_COMPLETED)
        return True

    def next_retry_in(self) -> float | None:
        """Seconds (on the injectable clock) until the earliest §16.3
        backoff elapses; <= 0 when one is already due, None when no
        retry is pending.  Drain loops use this to sleep exactly as
        long as needed instead of spinning."""
        if not self._retry:
            return None
        return min(due for _a, due in self._retry.values()) - self._clock()

    def kick_retries(self) -> None:
        """Declare the earliest pending backoff elapsed and dispatch it
        now.  The escape hatch for blocking drains under an *injected*
        clock (§16.3): a drain loop that owns neither wall time nor the
        fake clock would otherwise wait forever on a backoff that only
        the test can advance.  ``step()``-driven pumping never calls
        this, so clock-driven tests see exact backoff gating."""
        if not self._retry:
            return
        name = min(self._retry, key=lambda n: self._retry[n][1])
        del self._retry[name]
        if name not in self._entries:
            self._build_queue[name] = None
        self._dispatch()

    @property
    def building(self) -> list[str]:
        """Names whose artifact build is committed to the background
        pool: in flight on a worker or parked for a slot (§16.5).
        Backoff waiters are *not* here — see :attr:`retry_pending`."""
        return list(self._builds) + list(self._build_queue)

    @property
    def retry_pending(self) -> list[str]:
        """Names waiting out a §16.3 backoff before their next attempt."""
        return list(self._retry)

    def build_in_flight(self, name: str) -> bool:
        return name in self._builds

    def build_pending(self, name: str) -> bool:
        """True while ``name``'s build is anywhere in the pipeline:
        running, parked for a worker slot, or waiting out a backoff."""
        return (name in self._builds or name in self._build_queue
                or name in self._retry)

    def evict(self, name: str) -> bool:
        """Force ``name`` out of the cache now (listeners fire, the
        eviction is counted); False when not resident.  Sessions serving
        the graph keep their pinned artifact reference (§12.2) — this
        only makes the next cold lookup rebuild."""
        if name not in self._entries:
            return False
        self._evict_entry(name)
        return True

    def _shrink(self) -> None:
        """Evict LRU entries until the budget holds.  The entry `get` is
        about to return was just move_to_end'd and the `len > 1` bound keeps
        at least one entry, so it is never the victim."""
        if self.max_bytes is not None:
            while (self.current_bytes > self.max_bytes
                   and len(self._entries) > 1):
                victim, _ = next(iter(self._entries.items()))
                self._evict_entry(victim)
        if self.device_budget is None:
            return
        # §17.3 per-device bound: evict the LRU entry touching any
        # over-budget device.  The MRU entry (the one being installed /
        # returned) is never the victim, so an entry that alone exceeds
        # the bound still serves — oversized *admission* is the mesh
        # build path's job, not eviction's.
        while len(self._entries) > 1:
            over = {d for d, nb in self.per_device().items()
                    if nb > self.device_budget}
            if not over:
                return
            names = list(self._entries)
            victim = next(
                (n for n in names[:-1]
                 if over & set(self._devices_of(self._entries[n]))), None)
            if victim is None:
                return
            self._evict_entry(victim)

    def _evict_entry(self, victim: str) -> None:
        self._entries.pop(victim)
        self.evictions += 1
        for fn in self._evict_listeners:
            fn(victim)


# ---------------------------------------------------------------------------
# Per-graph admission queues: FIFO within a tenant, weighted across them
# ---------------------------------------------------------------------------


class _TenantQueue:
    """One graph's admission queue (DESIGN.md §14.2): FIFO within a
    tenant, weighted round-robin *across* tenants at lane-refill time.

    Every query carries a ``tenant`` key (``"default"`` unless the
    caller sets one), so with a single tenant this degenerates to the
    plain FIFO deque the engine used before — same pop order, same
    ``len``/iteration surface.  With several, a tenant of weight ``k``
    (``BfsEngine(tenant_weights={...})``, default 1) is offered ``k``
    consecutive dequeues per rotation while it has queued work: free
    lanes are shared by weight, and a tenant flooding one graph's queue
    cannot starve another tenant's requests on that graph of lane slots.
    Tenants leave the rotation when drained and re-enter on their next
    append, so idle tenants cost nothing."""

    __slots__ = ("_weights", "_by_tenant", "_rotation", "_credit", "_len")

    def __init__(self, weights: dict[str, int] | None = None):
        self._weights = weights or {}
        self._by_tenant: OrderedDict[str, deque] = OrderedDict()
        self._rotation: deque[str] = deque()
        self._credit = 0
        self._len = 0

    def _weight(self, tenant: str) -> int:
        return int(self._weights.get(tenant, 1))

    def append(self, q: BfsQuery) -> None:
        d = self._by_tenant.get(q.tenant)
        if d is None:
            d = self._by_tenant[q.tenant] = deque()
            self._rotation.append(q.tenant)
            if len(self._rotation) == 1:
                self._credit = self._weight(q.tenant)
        d.append(q)
        self._len += 1

    def prepend(self, q: BfsQuery) -> None:
        """Re-queue ``q`` at the *front* of its tenant's deque — the
        §16.4 degradation path returns in-flight work to the queue
        without sending it to the back of the line."""
        d = self._by_tenant.get(q.tenant)
        if d is None:
            self.append(q)
            return
        d.appendleft(q)
        self._len += 1

    def popleft(self) -> BfsQuery:
        if not self._len:
            raise IndexError("pop from an empty _TenantQueue")
        rot = self._rotation
        while True:
            tenant = rot[0]
            d = self._by_tenant[tenant]
            if not d:
                # drained tenant retires from the rotation (it re-enters
                # on its next append); the new head starts a fresh quantum
                rot.popleft()
                del self._by_tenant[tenant]
                self._credit = self._weight(rot[0]) if rot else 0
                continue
            if self._credit <= 0:
                rot.rotate(-1)
                self._credit = self._weight(rot[0])
                continue
            self._credit -= 1
            self._len -= 1
            return d.popleft()

    def __len__(self) -> int:
        return self._len

    def __bool__(self) -> bool:
        return self._len > 0

    def __iter__(self):
        return itertools.chain.from_iterable(self._by_tenant.values())

    def remove_rid(self, rid: int) -> BfsQuery | None:
        """Withdraw the queued request with id ``rid`` (§16.2
        cancellation); None when not queued here.  O(queue length) — a
        cancel is rare next to the per-pop hot path, which stays O(1).
        A drained tenant's empty deque is left for ``popleft``'s
        existing retire-on-empty handling."""
        for d in self._by_tenant.values():
            for q in d:
                if q.rid == rid:
                    d.remove(q)
                    self._len -= 1
                    return q
        return None


# ---------------------------------------------------------------------------
# Lane runner: kappa concurrent lanes with independent lifecycles
# ---------------------------------------------------------------------------


class LaneState(NamedTuple):
    """Device arrays for kappa in-flight lanes (both layouts share this
    shape-polymorphic container; packed uses uint32 words, byteplane uint8
    columns).  Per-lane reach is *not* here: it is mirrored host-side from
    the per-level new counts (`reach_host` in ``_GraphSession``) and a
    device column would only be read back at extraction."""

    v: jax.Array        # (n_ext, kw) uint32 | (n_ext, kappa) uint8 visited
    f: jax.Array        # (num_sets_ext, sigma, width) frontier tiles
    levels: jax.Array   # (n_ext, kappa) int32 — global level stamps


class _GraphOperands(NamedTuple):
    """The per-graph device arrays a runner's jitted steps read.  They are
    passed as an argument: a jitted closure over them would embed them in
    every program as constants (see ``BvssDevice``)."""

    bd: BvssDevice
    tiles: "mma_mod.MmaTiles | None"
    # the slice-compacted byteplane substrate's slot list (§11.2)
    nz_mask: jax.Array | None
    nz_parent: jax.Array | None
    nz_rows: jax.Array | None
    # the packed dense level's destination-major slot table (§11.2)
    chunks: jax.Array | None
    chunk_rows: jax.Array | None


class _LaneRunner:
    """kappa MS-BFS lanes over one graph; jit-compiled level + reseed steps.

    The level step is the packed-word pipeline of
    :class:`repro.core.msbfs_packed.PackedMsBfs` extended with per-lane
    bookkeeping; the reseed step clears a set of lanes and seeds new sources
    into them without touching the other lanes' bits (bitwise lane
    independence makes this exact, not approximate).

    A replica's runner (§17.1) is given its chip as ``device``: every array
    it makes (slot table, slice list, tiles, empty state) is committed
    there, and its per-call operands are left on the host for jit to place
    beside them, so no level reads an operand across chips.  ``table`` is
    the graph's host slot table where one was built already (the replicas
    of a graph share one, ``self.table``).  A lone runner (``device=None``)
    keeps its arrays on the default device, uncommitted.
    """

    def __init__(self, bd: BvssDevice, kappa: int, *, layout: str = "auto",
                 use_pallas: bool | None = None,
                 mma_tiles: mma_mod.MmaTiles | None = None,
                 device=None, table=None):
        if kappa % 32 != 0:
            raise ValueError("kappa must be a multiple of 32 (packed words)")
        if layout == "auto":
            layout = "packed" if jax.default_backend() == "tpu" else "byteplane"
        if layout not in ("packed", "byteplane", "mma"):
            raise ValueError(layout)
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.bd = bd
        self.kappa = kappa
        self.kw = kappa // 32
        self.layout = layout
        self.device = device
        # the MMA layout changes only the *dense pull* (DESIGN.md §13.2);
        # state, reseed, and queued sweeps follow the substrate — packed
        # words when Pallas kernels drive the fused MMA scatter, the
        # slice-compacted byteplane (popcount fallback) on the jnp path
        self._mma = layout == "mma"
        self.substrate = (("packed" if use_pallas else "byteplane")
                          if self._mma else layout)
        self._tiles = (mma_tiles if mma_tiles is not None
                       else mma_mod.prep_mma_tiles(bd)) if self._mma else None
        if self._tiles is not None and device is not None:
            self._tiles = jax.device_put(self._tiles, device)
        self.use_pallas = use_pallas
        self._interpret = jax.default_backend() != "tpu"
        self._level_fn = jax.jit(self._level)
        # one jitted callable; XLA re-traces per distinct bucket size, and
        # power-of-two bucketing bounds that to O(log N_v) shapes (§2)
        self._level_queued_fn = jax.jit(self._level_queued)
        self._reseed_fn = jax.jit(self._reseed)
        self._active_fn = jax.jit(lambda f: (f != 0).any(axis=(1, 2)))
        self._real_ptrs = np.asarray(bd.real_ptrs)
        self._pad_vss = bd.num_vss  # a guaranteed padding VSS id
        self._compact = self.substrate == "byteplane" and not use_pallas
        nz = (None, None, None)
        if self._compact:
            # slice-compacted pulls (§11.2): the (num_vss_pad, tau) grid is
            # mostly padding (zero masks -> zero marks -> no-op scatter
            # rows); the nonzero-mask slot list is static per graph, so the
            # XLA path builds marks and scatters over S = num_slices rows
            # instead of num_vss_pad * tau.  The arrays stay ordered by
            # (vss, slot) and `_nz_ptrs` maps a VSS to its slice range, so
            # queued sweeps expand active VSSs to exactly their real
            # slices; entry S is a sentinel (zero mask, sentinel row) that
            # pads queued buckets.
            mask_np = np.asarray(bd.masks)
            nz_vss, nz_slot = np.nonzero(mask_np)
            self._nz_ptrs = np.zeros(bd.num_vss_pad + 1, np.int64)
            np.cumsum(np.bincount(nz_vss, minlength=bd.num_vss_pad),
                      out=self._nz_ptrs[1:])
            mask_c = np.append(mask_np[nz_vss, nz_slot], 0).astype(np.uint8)
            parent_c = np.append(np.asarray(bd.v2r)[nz_vss], bd.num_sets)
            rows_c = np.append(np.asarray(bd.row_ids)[nz_vss, nz_slot],
                               bd.n_pad)
            nz = (self.put(mask_c), self.put(parent_c.astype(np.int32)),
                  self.put(rows_c.astype(np.int32)))
            self._pad_slice = int(mask_c.size - 1)  # the sentinel entry
        # the packed dense level ORs marks into rows through a gather over
        # a static slot table (§11.2); entries gathered a level, for stats
        self.table, self.dense_gathered = None, 0
        dev_table = (None, None)
        if self.substrate == "packed" and not self._mma:
            self.table = table if table is not None else slot_table(
                np.asarray(bd.row_ids), np.asarray(bd.masks), bd.n_ext,
                self.kw)
            dev_table = (self.put(self.table.chunks),
                         self.put(self.table.rows))
            self.dense_gathered = self.table.entries
        self._ops = _GraphOperands(bd, self._tiles, *nz, *dev_table)
        # megatick residency (DESIGN.md §11.1): the bucket-guard threshold
        # (smallest |Q| whose padded bucket reaches the full sweep), and
        # jitted drivers per (T, policy) pair
        if bucket_size(1) >= bd.num_vss_pad:
            self._dense_guard = 0
        else:
            self._dense_guard = (1 << (bd.num_vss_pad - 1).bit_length()) // 2 + 1
        self._megatick_fns: dict[tuple[int, bool, float], object] = {}
        self._init_state: LaneState | None = None
        self._reach_zero = jnp.zeros(kappa, jnp.int32,  # policy-off filler
                                     device=device)
        # extraction gather: slice the finished lanes' level columns on
        # device before the host copy; re-traced per power-of-two bucket of
        # len(done), so at most log2(kappa)+1 shapes ever compile
        self._gather_cols_fn = jax.jit(
            lambda levels, idx: levels[: bd.n][:, idx])
        # watched-target gather (§12.3): one level stamp per lane — a
        # (kappa,) transfer per tick while any distance lane is in flight
        self._watch_fn = jax.jit(
            lambda levels, ids: levels[ids, jnp.arange(kappa)])

    # ---- placement ----------------------------------------------------------
    def put(self, x) -> jax.Array:
        """A host array as a device array of this runner: committed to its
        chip, or on the default device for a lone runner."""
        if self.device is None:
            return jnp.asarray(x)
        return jax.device_put(x, self.device)

    def _arg(self, x, dtype):
        """One per-call operand.  A replica's runner leaves a host value on
        the host (jit copies it to the chip of the committed operands); a
        lone runner's becomes an array on the default device."""
        if self.device is None:
            return jnp.asarray(x, dtype)
        return x if isinstance(x, jax.Array) else np.asarray(x, dtype)

    # ---- state ------------------------------------------------------------
    def init_state(self) -> LaneState:
        """The all-empty lane state.  Immutable device arrays, so the one
        instance is built lazily and shared by every batch session (a fresh
        build per drain was measurable host overhead)."""
        if self._init_state is None:
            bd, kappa, dev = self.bd, self.kappa, self.device
            if self.substrate == "packed":
                v = jnp.zeros((bd.n_ext, self.kw), jnp.uint32, device=dev)
            else:
                v = jnp.zeros((bd.n_ext, kappa), jnp.uint8, device=dev)
            self._init_state = LaneState(
                v=v,
                f=self._planes(v),
                levels=jnp.full((bd.n_ext, kappa), UNREACHED, jnp.int32,
                                device=dev),
            )
        return self._init_state

    def _planes(self, v_or_diff):
        """visited/diff rows -> (num_sets_ext, sigma, width) frontier tiles."""
        return frontier_planes(self.bd, v_or_diff)

    # ---- one level over all lanes -----------------------------------------
    def _pull_scatter(self, g: "_GraphOperands", v, f):
        bd = g.bd
        if self.substrate == "byteplane":
            if self._mma:
                # §13.3 AND-OR/popcount fallback: the dense pull over the
                # slice-compacted slots as one int8 counts matmul instead
                # of the sigma-pass OR ladder — marks are (counts > 0)
                ft = f[g.nz_parent]  # (S, sigma, kappa) uint8 planes
                marks = mma_mod.pull_mma_byteplane_ref(
                    g.tiles.nz_planes[:, None, :], ft)[:, 0]
                return v.at[g.nz_rows].max(marks)
            if self.use_pallas:
                marks = ops.pull_ms(bd.masks, f, bd.v2r, sigma=bd.sigma,
                                    use_pallas=True)
                return v.at[bd.row_ids.ravel()].max(
                    marks.reshape(-1, self.kappa))
            # slice-compacted bitwise OR-of-selected-planes pull (§11.2):
            # marks and scatter rows over the static nonzero-slice list
            # only — zero-mask slots could never contribute, and XLA CPU
            # scatter cost is linear in rows
            ft = f[g.nz_parent]  # (S, sigma, kappa) uint8 bit-planes
            marks = jnp.zeros((g.nz_mask.shape[0], self.kappa), jnp.uint8)
            for b in range(bd.sigma):
                sel = ((g.nz_mask >> b) & 1)[:, None]
                marks = marks | (sel * ft[:, b])
            return v.at[g.nz_rows].max(marks)
        if self._mma:
            # §13.2 fused MMA pull+scatter: each VSS's marks are one
            # (kappa, sigma) x (sigma, tau) binary product ORed into the
            # live visited words (kernel), or — jnp twin — one batched
            # counts matmul + duplicate-safe scatter-add
            t = g.tiles
            if self.use_pallas:
                return mma_mod.pull_scatter_mma_ms_packed(
                    v, t.a_planes, f, t.v2r, t.rows, sigma=bd.sigma,
                    interpret=self._interpret)
            return mma_mod.pull_scatter_mma_ms_packed_ref(
                v, t.a_planes, f, t.v2r, t.rows)
        # the dense pull's lane-dense marks, ORed into their rows by the
        # destination-major gather over the static slot table (§11.2)
        if self.use_pallas:
            marks = pull_ms_packed_lanes(bd.masks, f, bd.v2r, sigma=bd.sigma,
                                         interpret=self._interpret)
        else:
            marks = lanes_of(pull_ms_packed_ref(bd.masks, f[bd.v2r],
                                                sigma=bd.sigma))
        return v | gather_or(marks, g.chunks, g.chunk_rows, self.kw)

    def _pull_scatter_queued(self, g: "_GraphOperands", v, f, qids):
        """Frontier-compacted pull+scatter over the active list only
        (DESIGN.md §10.1): work ~ |Q| * tau instead of N_v * tau — or
        ~ |active slices| on the slice-compacted path, where ``qids`` are
        slice ids (``bucket_qids`` expands VSS ids through ``_nz_ptrs``).
        The MMA layout shares this path unchanged: queued sweeps are
        sparse gathers, which the bit-MMA formulation does not help
        (DESIGN.md §13.2)."""
        bd = g.bd
        if self.substrate == "byteplane":
            if self._compact:
                # slice-compacted queued pull (§11.2): gather the active
                # slices' mask bytes / parent tiles / rows directly
                mask_q = g.nz_mask[qids]            # (B,) uint8
                ft = f[g.nz_parent[qids]]           # (B, sigma, kappa)
                marks = jnp.zeros((qids.shape[0], self.kappa), jnp.uint8)
                for b in range(bd.sigma):
                    sel = ((mask_q >> b) & 1)[:, None]
                    marks = marks | (sel * ft[:, b])
                return v.at[g.nz_rows[qids]].max(marks)
            # XLA take-based queued path: gather the queued masks/rows/parent
            # tiles, then the same OR-of-selected-planes pull as dense.  (The
            # MXU byteplane kernel is deliberately not given a queued twin —
            # off-TPU the take-based path is the fast one, and on TPU the
            # packed substrate is the default.)
            masks_q = bd.masks[qids]            # (B, tau) uint8
            ft = f[bd.v2r[qids]]                # (B, sigma, kappa) uint8
            marks = jnp.zeros((qids.shape[0], bd.tau, self.kappa), jnp.uint8)
            for b in range(bd.sigma):
                sel = ((masks_q >> b) & 1)[:, :, None]
                marks = marks | (sel * ft[:, b][:, None, :])
            rows = bd.row_ids[qids]
            return v.at[rows.ravel()].max(marks.reshape(-1, self.kappa))
        rows = bd.row_ids[qids].reshape(-1)
        if self.use_pallas:
            marks = pull_ms_packed_queued(bd.masks, f, bd.v2r, qids,
                                          sigma=bd.sigma,
                                          interpret=self._interpret)
            return scatter_or(v, rows, marks.reshape(-1, self.kw),
                              interpret=self._interpret)
        marks = pull_ms_packed_queued_ref(bd.masks, f, bd.v2r, qids,
                                          sigma=bd.sigma)
        return scatter_or_ref(v, rows, marks.reshape(-1, self.kw))

    def _lane_bits(self, diff):
        """diff rows -> (n_ext, kappa) 0/1 int32 newly-visited matrix."""
        if self.substrate == "byteplane":
            return diff.astype(jnp.int32)
        return unpack_levels_check(diff, self.kappa).astype(jnp.int32)

    def _finish_level(self, state: LaneState, v_next, ell):
        """Shared tail of both sweeps: diff, level stamps, frontier tiles."""
        v = state.v
        diff = (v_next & ~v if self.substrate == "packed"
                else v_next & (1 - v))
        bits = self._lane_bits(diff)
        new_lane = bits.sum(axis=0)
        return LaneState(
            v=v_next,
            f=self._planes(diff),
            levels=jnp.where(bits == 1, ell, state.levels),
        ), new_lane

    def _level(self, g: "_GraphOperands", state: LaneState, ell):
        """Advance every lane one dense level; returns (state', new_per_lane)."""
        v_next = self._pull_scatter(g, state.v, state.f)
        return self._finish_level(state, v_next, ell)

    def _level_queued(self, g: "_GraphOperands", state: LaneState, ell, qids):
        """Advance every lane one queued level over the active VSSs only."""
        v_next = self._pull_scatter_queued(g, state.v, state.f, qids)
        return self._finish_level(state, v_next, ell)

    def level(self, state: LaneState, ell: int):
        return self._level_fn(self._ops, state, self._arg(ell, np.int32))

    def level_queued(self, state: LaneState, ell: int, qids: np.ndarray):
        return self._level_queued_fn(self._ops, state,
                                     self._arg(ell, np.int32),
                                     self._arg(qids, np.int32))

    def active_set_mask(self, f) -> np.ndarray:
        """Union frontier across lanes -> (num_sets,) bool on host.

        A slice set is active when *any* lane holds a frontier bit in it;
        its realPtrs range names every VSS that can produce marks this
        level, so queued sweeps over the expansion are exact (§10.2)."""
        return np.asarray(self._active_fn(f))[: self.bd.num_sets]

    def queue_len(self, active_mask: np.ndarray) -> int:
        """|Q| — total VSS count under the active sets, without
        materializing the id list (the dense branch never needs it)."""
        sets = np.nonzero(active_mask)[0]
        rp = self._real_ptrs
        return int((rp[sets + 1] - rp[sets]).sum())

    def active_vss(self, active_mask: np.ndarray) -> np.ndarray:
        """Expand the active sets into the VSS id list (queued branch only)."""
        return expand_active_sets(self._real_ptrs, active_mask)

    def bucket_qids(self, qids: np.ndarray) -> np.ndarray:
        """Pad the active list to a power-of-two bucket with padding ids
        (zero masks, sentinel rows), bounding jit re-traces.  On the
        slice-compacted substrate the VSS ids are first expanded to their
        real nonzero-slice ranges (``_nz_ptrs``), so queued work tracks
        the active slice count, not |Q| * tau."""
        pad = self._pad_vss
        if self._compact:
            starts = self._nz_ptrs[qids]
            counts = self._nz_ptrs[qids + 1] - starts
            total = int(counts.sum())
            if total:
                which = np.repeat(np.arange(qids.size), counts)
                offs = np.arange(total) - np.repeat(
                    np.cumsum(counts) - counts, counts)
                qids = (starts[which] + offs).astype(np.int32)
            else:
                qids = np.zeros(0, np.int32)
            pad = self._pad_slice
        bs = bucket_size(qids.size)
        padded = np.full(bs, pad, np.int32)
        padded[: qids.size] = qids
        return padded

    # ---- megatick: up to T fused dense levels per dispatch (§11.1) --------
    def megatick(self, state: LaneState, reach: np.ndarray, ell0: int,
                 active, admitted_at, eta: float,
                 *, ticks: int, policy_on: bool):
        """Run up to ``ticks`` consecutive dense levels in one
        ``lax.while_loop`` dispatch; returns ``(state', new_hist)`` where
        ``new_hist`` is (ticks, kappa) int32 per-level new-vertex counts
        with unexecuted rows left at -1 (the host derives the executed tick
        count from them — one transfer carries the whole window's
        bookkeeping).

        Exit conditions, beyond ``ticks`` elapsing: every active lane
        finishing (results are due); or, under an active policy, Eq. (6)
        picking a queued level — which the host executes with the §10
        bucketed machinery before re-entering.  The engine only opens a
        window when the graph's queue is empty, so a lane finishing early
        parks inside the window instead of forcing an exit: its frontier
        is empty so its levels column, reach, and far contributions are
        all frozen (every later ``new`` count is zero), and extraction at
        window end reads exactly what extraction at the finish tick would
        have.

        ``active``/``admitted_at`` may be device arrays (the engine caches
        them across windows — they only change at admission) and ``eta`` is
        a compile-time constant, so steady-state windows upload at most the
        policy's reach mirror.  ``reach`` is ignored unless ``policy_on``."""
        key = (int(ticks), bool(policy_on), float(eta))
        fn = self._megatick_fns.get(key)
        if fn is None:
            fn = jax.jit(functools.partial(
                self._megatick, T=int(ticks), policy_on=bool(policy_on),
                eta=float(eta)))
            self._megatick_fns[key] = fn
        reach_dev = (self._arg(reach, np.int32) if policy_on
                     else self._reach_zero)
        return fn(self._ops, state, reach_dev, self._arg(ell0, np.int32),
                  self._arg(active, bool),
                  self._arg(admitted_at, np.int32))

    def _megatick(self, g: "_GraphOperands", state: LaneState, reach, ell0,
                  active, admitted_at, *, T: int, policy_on: bool,
                  eta: float):
        bd = g.bd
        # per-set VSS counts for the on-device |Q|
        set_counts = bd.real_ptrs[1:] - bd.real_ptrs[:-1]

        def cond(carry):
            st, reach, tick, done, hist = carry
            live = active & ~done
            go = (tick < T) & live.any()
            if policy_on:
                # the §10.2 decision, fully on device: |Q| from the union
                # frontier through real_ptrs, #unvisited from the resident
                # per-lane reach.  Eq. (6) compares in float32 (the host
                # path uses Python floats); a flip at the exact boundary
                # changes the sweep shape only, never the results.  The
                # unvisited sum is accumulated in float32 too: it reaches
                # kappa*n, which would wrap an int32 at paper scale (the
                # host mirror is int64 for the same reason), while float32
                # merely rounds.
                af = self._active_fn(st.f)[: bd.num_sets]
                q_len = jnp.where(af, set_counts, 0).sum()
                unvisited = jnp.where(
                    active, (bd.n - reach).astype(jnp.float32), 0.0).sum()
                dense = unvisited < eta * q_len.astype(jnp.float32)
                dense = dense | (q_len >= self._dense_guard)  # bucket guard
                go = go & dense
            return go

        def body(carry):
            st, reach, tick, done, hist = carry
            ell = ell0 + tick + 1
            st, new_lane = self._level(g, st, ell)
            # new counts are monotone-absorbing at zero (an empty lane
            # frontier stays empty), so |= is exact
            done = done | (active & ((new_lane == 0)
                                     | (ell - admitted_at >= bd.n_ext)))
            return (st, reach + new_lane, tick + 1, done,
                    hist.at[tick].set(new_lane))

        hist0 = jnp.full((T, self.kappa), -1, jnp.int32)
        done0 = jnp.zeros(self.kappa, bool)
        state, _reach, _tick, _done, hist = jax.lax.while_loop(
            cond, body,
            (state, reach, jnp.int32(0), done0, hist0))
        return state, hist

    # ---- watched-target gather (§12.3) ------------------------------------
    def watch_levels(self, levels, ids_dev) -> np.ndarray:
        """Level stamps of one watched vertex per lane: (kappa,) int32 in
        a single tiny gather.  ``ids_dev`` is the host-clamped (>= 0)
        per-lane vertex id column; the caller masks unwatched lanes.
        Copied out of the device buffer: the session mutates its ``tl``
        mirror at admission, and ``np.asarray`` of a jax array is
        read-only."""
        return np.array(self._watch_fn(levels, ids_dev))

    # ---- extraction gather (§11.3) ----------------------------------------
    def gather_level_cols(self, levels, cols: list[int]) -> np.ndarray:
        """Finished lanes' level columns, sliced on device before the host
        copy: (n, len(cols)) int32.  The column list is padded to a
        power-of-two bucket (duplicates of the first id) so the jitted
        gather compiles at most log2(kappa)+1 shapes."""
        b = min(self.kappa, 1 << (len(cols) - 1).bit_length())
        idx = np.full(b, cols[0], np.int32)
        idx[: len(cols)] = cols
        out = np.asarray(self._gather_cols_fn(levels,
                                              self._arg(idx, np.int32)))
        return out[:, : len(cols)]

    # ---- clear + seed a subset of lanes -----------------------------------
    def _reseed(self, state: LaneState, clear, new_src, ell):
        """clear: (kappa,) bool — lanes to wipe; new_src: (kappa,) int32 —
        source to seed into a wiped lane, or -1 to leave it empty."""
        bd, kappa = self.bd, self.kappa
        lanes = jnp.arange(kappa)
        has = new_src >= 0
        src = jnp.where(has, new_src, 0)
        if self.substrate == "packed":
            # one uint32 per word with the cleared lanes' bits set
            word_mask = self._lane_word_mask(clear)
            v = state.v & ~word_mask[None, :]
            f = state.f & ~word_mask[None, None, :]
            seed_bits = (has.astype(jnp.uint32)
                         << (lanes % 32).astype(jnp.uint32))
            # cleared bits are 0 and lane bit positions are distinct, so
            # scatter-add == scatter-OR here
            v = v.at[src, lanes // 32].add(seed_bits)
            f = f.at[src // bd.sigma, src % bd.sigma, lanes // 32].add(
                seed_bits)
        else:
            keep = (1 - clear.astype(jnp.uint8))[None, :]
            v = state.v * keep
            f = state.f * keep[None]
            v = v.at[src, lanes].max(has.astype(jnp.uint8))
            f = f.at[src // bd.sigma, src % bd.sigma, lanes].max(
                has.astype(jnp.uint8))
        levels = jnp.where(clear[None, :], UNREACHED, state.levels)
        levels = levels.at[src, lanes].set(
            jnp.where(has, ell, levels[src, lanes]))
        return LaneState(v=v, f=f, levels=levels)

    def _lane_word_mask(self, clear):
        shifts = jnp.arange(32, dtype=jnp.uint32)
        bits = clear.astype(jnp.uint32).reshape(self.kw, 32) << shifts
        return bits.sum(axis=1).astype(jnp.uint32)  # distinct bits: sum == OR

    def reseed(self, state: LaneState, clear: np.ndarray, new_src: np.ndarray,
               ell: int) -> LaneState:
        return self._reseed_fn(state, self._arg(clear, bool),
                               self._arg(new_src, np.int32),
                               self._arg(ell, np.int32))


# ---------------------------------------------------------------------------
# Graph sessions: one resumable serving context per in-flight graph
# ---------------------------------------------------------------------------


# the BfsResult fields a Workload.extract override may set
_RESULT_FIELDS = frozenset(BfsResult.__dataclass_fields__)

# extract() override typing (§15.3): field name -> acceptable scalar types
# (None always allowed).  ``levels`` is shape-checked separately; ``extra``
# must be a dict.  A workload returning a malformed override corrupts every
# caller downstream of verify_result, so the engine rejects it loudly at
# extraction instead.
_INT_RESULT_FIELDS = frozenset({
    "far", "reach", "admitted_at_level", "distance", "component",
    "component_size", "mis_size", "triangles"})


def _check_extract_field(kind: str, field: str, value, n: int) -> None:
    if value is None:
        return
    if field == "levels":
        if (not isinstance(value, np.ndarray) or value.shape != (n,)
                or not np.issubdtype(value.dtype, np.integer)):
            raise ValueError(
                f"workload {kind!r} extract() returned a bad 'levels': "
                f"want an (n,)=({n},) integer ndarray, got "
                f"{type(value).__name__}"
                + (f" of shape {value.shape}, dtype {value.dtype}"
                   if isinstance(value, np.ndarray) else ""))
    elif field in _INT_RESULT_FIELDS:
        if isinstance(value, bool) or not isinstance(value,
                                                     (int, np.integer)):
            raise ValueError(
                f"workload {kind!r} extract() returned a non-int "
                f"{field!r}: {value!r}")
    elif field == "in_mis":
        if not isinstance(value, (bool, np.bool_)):
            raise ValueError(
                f"workload {kind!r} extract() returned a non-bool "
                f"'in_mis': {value!r}")
    elif field == "closeness":
        if not isinstance(value, (float, np.floating)):
            raise ValueError(
                f"workload {kind!r} extract() returned a non-float "
                f"'closeness': {value!r}")
    elif field == "extra":
        if not isinstance(value, dict):
            raise ValueError(
                f"workload {kind!r} extract() returned a non-dict "
                f"'extra': {value!r}")


class _GraphSession:
    """Resumable per-graph serving state (DESIGN.md §12.2).

    PR 1's engine drained one graph to completion inside a monolithic
    ``_drain_graph`` loop; everything that loop kept in locals — the lane
    set, the host mirrors (far/reach), the megatick window caches — now
    lives here, so a session advances **one tick at a time** and the
    scheduler can interleave many graphs.  One tick is one iteration of
    the old loop: admission refill, then either one megatick window or
    one (dense | queued) level, then per-lane early exit.

    The session pins ``art``/``runner`` for its lifetime, so a graph
    evicted from the cache mid-service keeps serving correctly: the cache
    drops the entry (and a *re-opened* session will schedule a rebuild)
    but in-flight lanes never see the substrate swap out from under them.
    The artifact arrives prebuilt from the engine (resident cache entry,
    or the §14.3 held reference when eviction raced the build landing) —
    a session never builds anything itself, so opening one is always
    cheap and ``step()`` stays non-blocking.
    """

    def __init__(self, engine: "BfsEngine", name: str,
                 queue: "_TenantQueue", art: GraphArtifacts, runner=None,
                 replica: int = 0):
        self.engine = engine
        self.name = name
        self.queue = queue
        self.art = art
        # §17.1: a mesh session group hands each replica its own runner
        # and its index in the group; single-device sessions resolve
        # through the engine as before, and are replica 0
        self.runner = runner if runner is not None else engine._runner_for(art)
        self._levels_key = f"levels:replica{replica}"
        kappa = engine.kappa
        self.lanes: list[BfsQuery | None] = [None] * kappa
        self.wl: list[Workload | None] = [None] * kappa
        self.accs: list[workloads_mod.LaneAccum | None] = [None] * kappa
        self.admitted_at = np.zeros(kappa, np.int32)
        # Eq.(7) far accumulated host-side in int64: the device int32 lane
        # accumulator would overflow on paper-scale graphs (sum of
        # distances from one source can exceed 2^31; cf. core/closeness.py,
        # which widens to int64 on host for the same reason).
        self.far64 = np.zeros(kappa, np.int64)
        # per-lane visited counts mirrored host-side: the Eq. (6) unvisited
        # term aggregated over in-flight lanes, without a device round-trip
        self.reach_host = np.zeros(kappa, np.int64)
        # watched-target machinery (§12.3): permuted target id per lane
        # (-1 = not watching), the cached clamped device column, and the
        # stamps from the latest watch gather
        self.watch_ids = np.full(kappa, -1, np.int64)
        self.watch_dev = None
        self.tl = np.full(kappa, UNREACHED, np.int64)
        # sharded runners run policy-off (§17.2): Eq. 6's queued sweep has
        # no row-sharded formulation, so the dense path is always taken
        self.policy_on = (engine._policy_active(art)
                          and getattr(self.runner, "supports_policy", True))
        # session-held workload graph state (§15.2): populated from the
        # engine memo at first use, kept here so eviction mid-service
        # never forces a rebuild (the same pinning rule as art/runner)
        self.graph_states: dict[str, object] = {}
        self.state = self.runner.init_state()
        self.ell = 0
        # device copies of the lane metadata the megatick window reads;
        # rebuilt only when the lane set changes (admission / extraction)
        self.meta_dev = None
        # queued-streak guard: after a window exits on a queued verdict,
        # stay on the per-level path until the policy picks dense again —
        # otherwise a queued-dominant traversal would pay a no-op window
        # dispatch plus a history transfer on every single level
        self.prefer_host = False
        engine.stats["batches"] += 1

    @property
    def idle(self) -> bool:
        return not self.queue and all(q is None for q in self.lanes)

    @property
    def in_flight(self) -> int:
        return sum(q is not None for q in self.lanes)

    # ---- cancel / deadline reclamation at window boundaries (§16.2) -------
    def _reclaim_lanes(self) -> None:
        """Free lanes whose request was cancelled or whose deadline
        passed, at a megatick window boundary (= between ticks — a
        window is one tick, so this is exactly the §11.1 boundary).
        The lane's column is wiped via the reseed clear (bitwise lane
        independence keeps the other lanes exact) and the lane returns
        to the free set for this very tick's admission refill."""
        eng = self.engine
        kappa = eng.kappa
        stale: list[int] = []
        now = None
        for i, q in enumerate(self.lanes):
            if q is None:
                continue
            t = eng._tickets.get(q.rid)
            if t is None:
                continue
            if t.cancel_requested:
                eng._finish_cancel(t)
                stale.append(i)
            elif t.deadline_at is not None:
                if now is None:
                    now = eng._clock()
                if now > t.deadline_at:
                    eng._tickets.pop(q.rid, None)
                    eng._shed_expired(t, now, where="window boundary",
                                      deliver=True)
                    stale.append(i)
        if not stale:
            return
        for i in stale:
            self.lanes[i] = None
            self.wl[i] = None
            self.accs[i] = None
            self.watch_ids[i] = -1
        self.meta_dev = None
        self.watch_dev = None
        clear = np.zeros(kappa, bool)
        clear[stale] = True
        self.state = self.runner.reseed(
            self.state, clear, np.full(kappa, -1, np.int32), self.ell)
        eng.stats["dispatches"] += 1

    # ---- device -> host read-backs ----------------------------------------
    def _read(self, site: str, fn, *args) -> np.ndarray:
        """``fn(*args)``, one device -> host read-back of the serving path
        (a site of ``SYNC_SITES``): timed as the span ``serve.sync.<site>``
        and counted in ``syncs:<site>`` and ``host_syncs``, so that
        ``host_syncs`` is always the sum of the site counts."""
        stats = self.engine.stats
        with self.engine._spans("serve.sync." + site):
            out = fn(*args)
        stats["syncs:" + site] += 1
        stats["host_syncs"] += 1
        if SYNC_SITES[site]:
            stats["dispatches"] += 1
        return out

    # ---- one scheduling tick ----------------------------------------------
    def tick(self) -> None:
        """One level or one megatick window for every lane, as the span
        ``serve.tick`` (its arguments: graph, level, mode): the issue half,
        then the collect half of the level it put in flight."""
        with self.engine._spans("serve.tick", graph=self.name,
                                level=self.ell) as ann:
            mode, new_lane = self.issue()
            if new_lane is not None:
                self.collect(new_lane)
            ann.set_metadata(mode=mode)

    def _count_levels(self, n: int) -> None:
        self.engine.stats["levels"] += n
        self.engine.stats[self._levels_key] += n

    def issue(self) -> tuple[str | None, jax.Array | None]:
        """Admission, the mode decision, the queued expansion and the
        level's dispatch: everything up to the level's result, which stays
        in flight.  Returns the mode of the level (``dense``, ``queued`` or
        ``megatick``; None with no lane) and its per-lane new counts for
        :meth:`collect`, or None where nothing is left to collect.  A
        megatick window runs whole in here, read-backs and all (§17.1)."""
        eng = self.engine
        runner, art, kappa = self.runner, self.art, eng.kappa
        # the sharded runner keeps no slot table
        gathered = getattr(runner, "dense_gathered", 0)
        queue, lanes = self.queue, self.lanes
        span = eng._spans
        with span("serve.admit"):
            self._reclaim_lanes()
            # ---- admission: refill free lanes from the queue -------------
            free = [i for i in range(kappa) if lanes[i] is None]
            if free and queue:
                self.meta_dev = None
                self.watch_dev = None
                clear = np.zeros(kappa, bool)
                new_src = np.full(kappa, -1, np.int32)
                now = eng._clock()
                for i in free:
                    q = None
                    # §16.1 seeding-time check: pop until a request that
                    # can still make its deadline (expired ones shed here)
                    while queue:
                        cand = queue.popleft()
                        if eng._seed_ok(cand, now):
                            q = cand
                            break
                    if q is None:
                        break
                    wl = eng._workloads[q.kind]
                    lanes[i] = q
                    self.wl[i] = wl
                    self.accs[i] = (workloads_mod.LaneAccum()
                                    if wl.has_accumulate else None)
                    self.admitted_at[i] = self.ell
                    self.far64[i] = 0
                    self.reach_host[i] = 1  # the seeded source is visited
                    self.watch_ids[i] = (art.perm[q.target]
                                         if wl.watches_target else -1)
                    self.tl[i] = UNREACHED
                    clear[i] = True
                    new_src[i] = art.perm[q.source]
                    eng._lane_admitted(q, now)
                    if self.ell > 0:
                        eng.stats["admissions_midflight"] += 1
                self.state = runner.reseed(self.state, clear, new_src,
                                           self.ell)
                eng.stats["dispatches"] += 1
        if all(q is None for q in lanes):
            return None, None
        active_arr = np.fromiter((q is not None for q in lanes), bool, kappa)
        # ---- megatick window: up to T fused dense levels (§11.1) ---------
        # windows run when this graph's queue is drained; under backlog
        # the per-level path keeps admission immediate (a window exiting
        # on every lane-finish to admit degenerates to per-level ticks
        # that still pay the window overhead)
        if eng.megatick > 1 and not queue and not self.prefer_host:
            if self.meta_dev is None:
                self.meta_dev = (runner.put(active_arr),
                                 runner.put(self.admitted_at))
            with span("serve.dispatch"):
                self.state, hist = runner.megatick(
                    self.state, self.reach_host.astype(np.int32), self.ell,
                    self.meta_dev[0], self.meta_dev[1], eng.eta,
                    ticks=eng.megatick, policy_on=self.policy_on)
            eng.stats["dispatches"] += 1
            hist = self._read("megatick", np.asarray, hist)
            # unexecuted rows stay -1: the one transfer above carries
            # both the executed tick count and every level's counts
            ticks = int((hist[:, 0] >= 0).sum())
            if ticks:
                eng.stats["megaticks"] += 1
                self._count_levels(ticks)
                eng.stats["levels_dense"] += ticks
                eng.stats["dense_gathered"] += ticks * gathered
                w = hist[:ticks].astype(np.int64)
                ells = self.ell + 1 + np.arange(ticks, dtype=np.int64)
                self.reach_host += w.sum(axis=0)
                self.far64 += ((ells[:, None] - self.admitted_at[None, :])
                               * w).sum(axis=0)
                self.ell += ticks
                self._run_hooks(w, ells)
                tl = self._watch_tick()
                # lane new counts are monotone-absorbing at zero, so the
                # last row flags every lane that finished anywhere in the
                # window
                if self._finish_tick(hist[ticks - 1], tl):
                    self.meta_dev = None
                    return "megatick", None  # freed lanes: admit first
                if ticks == eng.megatick:
                    # window exhausted, every lane active
                    return "megatick", None
            # the window stopped short of T with no lane finished: the
            # on-device Eq. (6) verdict was queued — run that one level
            # host-side with the §10 bucketed machinery, and stay on
            # the per-level path while the verdict keeps being queued
            mode = "queued"
            self.prefer_host = True
            with span("serve.decide"):
                active_mask = self._read("active_mask", runner.active_set_mask,
                                         self.state.f)
        else:
            # ---- mode decision over the aggregate frontier (§10.2) -------
            # counts first, ids later: the decision needs only |Q|; the
            # id list is expanded on the queued branch alone, so dense
            # levels under a policy skip the O(|Q|) host expansion
            mode = "dense"
            active_mask = None
            if self.policy_on:
                with span("serve.decide"):
                    active_mask = self._read(
                        "active_mask", runner.active_set_mask, self.state.f)
                    q_len = runner.queue_len(active_mask)
                    unvisited = int(np.where(active_arr,
                                             art.graph.n - self.reach_host,
                                             0).sum())
                    mode = switching_mod.decide_mode(unvisited, q_len,
                                                     eng.eta)
                    # bucket guard: a padded queue as large as the full
                    # VSS sweep can only lose to dense (gather overhead,
                    # no savings)
                    if bucket_size(q_len) >= art.bd.num_vss_pad:
                        mode = "dense"
            if mode == "dense":
                self.prefer_host = False  # dense again: windows may resume
        # ---- one level for every lane ------------------------------------
        self.ell += 1
        if mode == "queued":
            with span("serve.expand"):
                qids = runner.active_vss(active_mask)
                rows = runner.bucket_qids(qids)
            eng.stats["queued_vss"] += qids.size
            eng.stats["queued_rows"] += rows.size
            with span("serve.dispatch"):
                self.state, new_lane = runner.level_queued(
                    self.state, self.ell, rows)
            eng.stats["levels_queued"] += 1
        else:
            with span("serve.dispatch"):
                self.state, new_lane = runner.level(self.state, self.ell)
            eng.stats["levels_dense"] += 1
            eng.stats["dense_gathered"] += gathered
        self._count_levels(1)
        eng.stats["dispatches"] += 1
        return mode, new_lane

    def collect(self, new_lane: jax.Array) -> None:
        """Wait for the level :meth:`issue` put in flight, and fold its
        per-lane new counts in: the host mirrors, the workload hooks, the
        watched targets, and the extraction of every finished lane."""
        nl = self._read("new_lane", np.asarray, new_lane)
        self.reach_host += nl
        self.far64 += (self.ell - self.admitted_at).astype(np.int64) * nl
        self._run_hooks(nl[None, :].astype(np.int64),
                        np.array([self.ell], dtype=np.int64))
        tl = self._watch_tick()
        if self._finish_tick(nl, tl):
            self.meta_dev = None

    # ---- per-level workload hooks (§12.3) ---------------------------------
    def _run_hooks(self, counts: np.ndarray, ells: np.ndarray) -> None:
        """Call overridden ``Workload.accumulate`` hooks for the executed
        levels: ``counts`` is (T, kappa) new-vertex counts at global
        levels ``ells``.  Lanes of hook-less workloads (all built-ins)
        never enter the loop, so the hot path stays vectorized."""
        with self.engine._spans("serve.hooks"):
            if not any(a is not None for a in self.accs):
                return
            for i in range(self.engine.kappa):
                acc = self.accs[i]
                if acc is None or self.lanes[i] is None:
                    continue
                wl, a0 = self.wl[i], int(self.admitted_at[i])
                for t in range(counts.shape[0]):
                    wl.accumulate(acc, int(ells[t]) - a0, int(counts[t, i]))

    # ---- watched targets (§12.3) ------------------------------------------
    def _watch_tick(self) -> np.ndarray | None:
        """Watched targets' level stamps after a level/window: one tiny
        (kappa,) gather, skipped entirely unless a watcher lane is in
        flight — bfs/closeness/reach streams never pay it."""
        if not ((self.watch_ids >= 0)
                & np.fromiter((q is not None for q in self.lanes), bool,
                              self.engine.kappa)).any():
            return None
        if self.watch_dev is None:
            self.watch_dev = self.runner.put(
                np.maximum(self.watch_ids, 0).astype(np.int32))
        self.tl = self._read("watch", self.runner.watch_levels,
                             self.state.levels, self.watch_dev)
        return self.tl

    # ---- per-lane early exit ----------------------------------------------
    def _finish_tick(self, nl: np.ndarray, tl: np.ndarray | None) -> bool:
        """Extract and free every finished lane after a level (or megatick
        window): frontier empty, diameter bound hit, or — distance lanes —
        the watched target's bit lit (§12.3); True iff any lane freed."""
        eng, art = self.engine, self.art
        with eng._spans("serve.finish"):
            done = [i for i in range(eng.kappa) if self.lanes[i] is not None
                    and (nl[i] == 0
                         or self.ell - self.admitted_at[i] >= art.bd.n_ext
                         or (tl is not None and self.watch_ids[i] >= 0
                             and tl[i] != UNREACHED))]
            if not done:
                return False
            self._extract(done)
            for i in done:
                self.lanes[i] = None
                self.wl[i] = None
                self.accs[i] = None
                self.watch_ids[i] = -1
            self.watch_dev = None
            # a lane freed with a non-empty frontier (watched-target early
            # exit; in principle the diameter bound too) would keep
            # traversing in its column and feed the dead frontier into the
            # Eq. (6) aggregate / queued expansions until re-seeded — wipe
            # it now (reseed with src=-1 clears without seeding); the
            # common frontier-empty exit (nl == 0) skips the extra dispatch
            live = [i for i in done if nl[i] != 0]
            if live:
                clear = np.zeros(eng.kappa, bool)
                clear[live] = True
                self.state = self.runner.reseed(
                    self.state, clear, np.full(eng.kappa, -1, np.int32),
                    self.ell)
                eng.stats["dispatches"] += 1
            return True

    def _extract(self, done: list[int]) -> None:
        eng, art = self.engine, self.art
        n = art.graph.n
        # the done columns are sliced on device (bucketed static-shape
        # gather, §11.3) so the host copy is (n, |done|), not the full
        # (n_ext, kappa) levels array — and only for workloads that ship
        # level arrays at all (needs_levels): a closeness/distance/reach
        # batch transfers nothing here
        lv_done = [i for i in done if self.wl[i].needs_levels]
        cols = {}
        if lv_done:
            arr = self._read("gather_cols", self.runner.gather_level_cols,
                             self.state.levels, lv_done)
            # one vectorized admission-offset subtraction + permutation for
            # every finished column (a per-lane loop here was measurable)
            lv = np.where(arr != UNREACHED,
                          arr - self.admitted_at[lv_done][None, :],
                          UNREACHED).astype(np.int32)[art.perm]
            cols = {i: lv[:, k] for k, i in enumerate(lv_done)}
        for i in done:
            q: BfsQuery = self.lanes[i]
            wl: Workload = self.wl[i]
            target_level = None
            if (wl.watches_target and self.watch_ids[i] >= 0
                    and self.tl[i] != UNREACHED):
                target_level = int(self.tl[i] - self.admitted_at[i])
            gstate = None
            if wl.has_graph_state:
                if q.kind not in self.graph_states:
                    self.graph_states[q.kind] = eng._workload_graph_state(
                        self.name, wl, art.graph)
                gstate = self.graph_states[q.kind]
            view = workloads_mod.LaneView(
                query=q, n=n, admitted_at_level=int(self.admitted_at[i]),
                far=int(self.far64[i]), reach=int(self.reach_host[i]),
                levels=cols.get(i), target_level=target_level,
                acc=self.accs[i], graph_state=gstate)
            res = BfsResult(
                rid=q.rid, graph=q.graph, source=q.source, kind=q.kind,
                levels=None, far=view.far, reach=view.reach, closeness=None,
                admitted_at_level=view.admitted_at_level)
            out = wl.extract(view)
            if out is None:
                out = {}
            if not isinstance(out, dict):
                raise ValueError(
                    f"workload {wl.kind!r} extract() must return a dict of "
                    f"BfsResult field overrides, got {type(out).__name__}")
            for field, value in out.items():
                if field not in _RESULT_FIELDS:
                    raise ValueError(
                        f"workload {wl.kind!r} extract() returned unknown "
                        f"BfsResult field {field!r}")
                _check_extract_field(wl.kind, field, value, n)
                setattr(res, field, value)
            eng._lane_completed(q, res)


# ---------------------------------------------------------------------------
# The engine: admission queue + fair scheduler over per-graph sessions
# ---------------------------------------------------------------------------


class BfsEngine:
    """Continuous-batching graph-query engine with a ticket-based
    non-blocking service API (DESIGN.md §6, §12).

    Usage::

        eng = BfsEngine(kappa=32, cache_bytes=64 << 20)
        eng.register_graph("social", g1)
        eng.register_graph("road", g2)
        t1 = eng.submit("social", source=17)                 # BFS levels
        t2 = eng.submit("road", source=3, kind="closeness")
        results = eng.run()     # {rid: BfsResult}; tickets are ints

        # ... or pump incrementally (§12.1) — submission is legal between
        # steps, and lands in the graph's live session mid-flight:
        t3 = eng.submit("road", 9, kind="distance", target=41)
        while not t3.done():
            for t in eng.step():          # one scheduling tick
                print(int(t), t.latency, t.result())

    Scheduling policy (§12.2): each ``step()`` opens a session for every
    graph with queued work and gives **one tick** — one traversal level,
    or one megatick window — to the next session in round-robin order
    (``weights={name: k}`` grants a graph ``k`` consecutive ticks per
    rotation).  Requests on one graph are FIFO; across graphs the
    round-robin interleaves sessions, so a deep backlog on one graph
    cannot head-of-line-block another's single query.
    ``scheduler="serial"`` restores the PR 1 graph-at-a-time drain (the
    ``benchmarks/serve_fairness.py`` baseline).  ``run()`` is a thin
    drain loop over ``step()`` with unchanged results.

    What a lane computes is a :class:`repro.serve.workloads.Workload`
    plugin (§12.3): ``bfs``/``closeness``/``distance``/``reach`` by
    default, ``register_workload`` for more.

    Overload behaviour (§14): a cache-miss graph's artifact builds on a
    background pool (``build_workers``; ``0`` restores the legacy
    synchronous build on the submitting thread), so ``submit()`` and
    ``step()`` never block on preprocessing and a failed build yields
    per-ticket ``FAILED`` results instead of an engine crash.
    ``max_queue`` / ``max_queue_total`` cap per-graph / engine-wide
    queue depth: beyond them ``submit()`` sheds the request —
    ``overload='reject'`` returns a terminal ``REJECTED`` ticket,
    ``'defer'`` parks it in a holding queue promoted as capacity frees.
    ``tenant_weights`` shares each graph's lane admission across
    ``submit(..., tenant=)`` keys by weighted round-robin; ``clock``
    (default ``time.monotonic``) stamps every ticket timestamp, so SLO
    accounting is deterministic under test; ``build_fault_hook`` is the
    §14.3 fault-injection point, called at the top of every artifact
    build.
    """

    def __init__(self, *, kappa: int = 32, cache_bytes: int | None = None,
                 layout: str = "auto", use_pallas: bool | None = None,
                 config: BvssConfig | None = None,
                 reorder: str | None = None, keep_results: bool = False,
                 switching: str = "auto",
                 eta: float = switching_mod.ETA_DEFAULT,
                 megatick: int = 1,
                 scheduler: str = "rr",
                 weights: dict[str, int] | None = None,
                 workloads: dict[str, Workload] | None = None,
                 build_workers: int = 1,
                 max_queue: int | None = None,
                 max_queue_total: int | None = None,
                 overload: str = "reject",
                 tenant_weights: dict[str, int] | None = None,
                 build_fault_hook=None,
                 clock=None,
                 build_retries: int = 0,
                 build_backoff: float = 0.05,
                 build_backoff_cap: float = 2.0,
                 mesh: "mesh_mod.EngineMesh | int | None" = None,
                 device_budget: int | None = None):
        if kappa % 32 != 0 or kappa <= 0:
            raise ValueError("kappa must be a positive multiple of 32")
        if device_budget is not None and device_budget < 1:
            raise ValueError(
                f"device_budget must be >= 1 byte, got {device_budget}")
        if layout not in LAYOUTS:
            raise ValueError(
                f"layout must be one of {LAYOUTS}, got {layout!r}")
        if switching not in SWITCHING_MODES:
            raise ValueError(
                f"switching must be one of {SWITCHING_MODES}, got {switching!r}")
        if eta < 0:
            raise ValueError(f"eta must be >= 0, got {eta}")
        if megatick < 1:
            raise ValueError(f"megatick must be >= 1, got {megatick}")
        if scheduler not in SCHEDULERS:
            raise ValueError(
                f"scheduler must be one of {SCHEDULERS}, got {scheduler!r}")
        if weights and any(int(w) < 1 for w in weights.values()):
            raise ValueError(f"weights must be >= 1, got {weights}")
        if build_workers < 0:
            raise ValueError(
                f"build_workers must be >= 0, got {build_workers}")
        if overload not in OVERLOAD_POLICIES:
            raise ValueError(
                f"overload must be one of {OVERLOAD_POLICIES}, got {overload!r}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if max_queue_total is not None and max_queue_total < 1:
            raise ValueError(
                f"max_queue_total must be >= 1, got {max_queue_total}")
        if tenant_weights and any(int(w) < 1
                                  for w in tenant_weights.values()):
            raise ValueError(
                f"tenant_weights must be >= 1, got {tenant_weights}")
        self.kappa = kappa
        self.layout = layout
        self.use_pallas = use_pallas
        self.default_reorder = reorder
        self.switching = switching
        self.eta = float(eta)
        self.megatick = int(megatick)
        self.scheduler = scheduler
        self.weights = ({k: int(v) for k, v in weights.items()}
                        if weights else None)
        self.build_workers = int(build_workers)
        self.max_queue = max_queue
        self.max_queue_total = max_queue_total
        self.overload = overload
        self.tenant_weights = ({k: int(v) for k, v in tenant_weights.items()}
                               if tenant_weights else None)
        # injectable clock (§14): every ticket timestamp and queue-wait
        # stat flows through this, so tests pin exact latency values.
        # _wall_clock gates the §16.3 drain-loop sleeps: under an
        # injected clock the engine never wall-sleeps on its behalf.
        self._clock = time.monotonic if clock is None else clock
        self._wall_clock = clock is None
        # §16.1 EWMA service-time model behind submit(deadline=)'s
        # predicted-violation shedding, and the §16.4 degradation
        # registry: (graph, layout) -> quarantine cause
        self._slo = lifecycle_mod.ServiceTimeModel()
        self._quarantine: dict[tuple[str, str], str] = {}
        # per-engine snapshot of the workload registry: register_workload
        # extends this engine alone, workloads.register the module default
        self._workloads = (dict(workloads) if workloads is not None
                           else workloads_mod.default_registry())
        # probe timings in Pallas interpret mode are meaningless (see
        # benchmarks/common.py), so the probe only uses Pallas on real TPUs
        self._probe_pallas = (jax.default_backend() == "tpu"
                              and use_pallas is not False)
        self._probe_runners_last: tuple | None = None
        # MMA tile prep runs when the graph may be served through the
        # bit-MMA layout: forced (layout='mma'), or probe-selectable
        # (layout='auto' with the switching probe on, DESIGN.md §13.4 —
        # the probe then times the MMA runner and 'auto' adopts its
        # dense_layout verdict per graph)
        self._mma_tiles = (layout == "mma"
                           or (layout == "auto" and switching == "auto"))
        # serve-aware probe (DESIGN.md §11.3): time the engine's own lane
        # runner dense vs policy, not the single-source BucketedBfs proxy
        self.cache = GraphCache(max_bytes=cache_bytes, config=config,
                                probe=(switching == "auto"), eta=self.eta,
                                probe_use_pallas=self._probe_pallas,
                                probe_runner=self._make_probe_runner,
                                mma_tiles=self._mma_tiles,
                                builders=max(1, self.build_workers),
                                fault_hook=build_fault_hook,
                                build_retries=build_retries,
                                retry_backoff=build_backoff,
                                retry_backoff_cap=build_backoff_cap,
                                clock=self._clock)
        self.cache.on_evict(self._drop_runner)
        # §17 mesh serving: device groups for source-parallel replication
        # and the per-device byte bound that triggers row-sharded builds
        # (§17.2) and per-device eviction (§17.3); an int takes the first
        # that many devices (EngineMesh.of)
        mesh = mesh_mod.EngineMesh.of(mesh)
        self.mesh = mesh
        self.device_budget = device_budget
        self._mesh_runners: dict[str, list] = {}
        self.cache.device_budget = device_budget
        if mesh is not None or device_budget is not None:
            self.cache.build_fn = self._mesh_build
        # §16.5: dispatch parked builds by queued depth, not FIFO — the
        # build that unblocks the most waiting tickets runs first
        self.cache.build_priority = (
            lambda name: len(self._queues.get(name) or ()))
        self._runners: dict[str, _LaneRunner] = {}
        # per-graph workload state (DESIGN.md §15.2): graph name ->
        # {kind: Workload.graph_state(graph)}, built lazily on the first
        # finished lane of that kind and dropped with the cache entry
        # (live sessions hold their own reference, like the substrate)
        self._wl_state: dict[str, dict[str, object]] = {}
        self._queues: OrderedDict[str, _TenantQueue] = OrderedDict()
        # artifacts whose build landed but whose session has not opened
        # yet: held by reference so cache pressure between install and
        # session open cannot force a synchronous rebuild (§14.3)
        self._built: dict[str, GraphArtifacts] = {}
        # overload='defer' holding queue, promoted each step while the
        # §14.2 caps allow (counts as neither queue depth nor a lane)
        self._deferred: deque[BfsQuery] = deque()
        self._rids = itertools.count()
        # scheduler state (§12.2): live sessions, their round-robin
        # rotation, and the tick quantum left for the rotation head
        self._sessions: dict[str, _GraphSession] = {}
        self._rotation: deque[str] = deque()
        self._quantum_left = 0
        self._last_scheduled: str | None = None
        # pending tickets (popped at completion — result lifetime is the
        # caller's ticket, not the engine) and the tickets completed since
        # the last step() returned
        self._tickets: dict[int, Ticket] = {}
        self._completed: list[Ticket] = []
        # opt-in: retaining every result (full level arrays) would be an
        # unbounded memory leak in a long-running service
        self.keep_results = keep_results
        self.results: dict[int, BfsResult] = {}
        self.stats = {
            "queries": 0, "batches": 0, "levels": 0,
            "admissions_midflight": 0,
            "levels_dense": 0, "levels_queued": 0,
            "megaticks": 0, "host_syncs": 0,
            "ticks": 0, "session_switches": 0, "max_live_sessions": 0,
            "builds": 0, "build_failures": 0,
            "rejected": 0, "deferred": 0,
            "expired": 0, "cancelled": 0,
            "deadline_misses": 0, "degraded": 0,
            # device programs launched while serving; the active VSSs of
            # queued levels and the bucket rows dispatched for them
            "dispatches": 0, "queued_vss": 0, "queued_rows": 0,
            # slot-table entries the packed dense levels gathered (§11.2)
            "dense_gathered": 0,
            # §17.1: the mesh's devices (0 without one), the session
            # groups' rounds that ran a level, and the levels of each
            # replica index of a group (a lone session is replica 0)
            "mesh_devices": mesh.n_devices if mesh is not None else 0,
            "rounds": 0,
            **{f"levels:replica{k}": 0
               for k in range(mesh.group_size if mesh is not None else 1)},
            **{"syncs:" + site: 0 for site in SYNC_SITES},
        }
        self._spans = spans_mod.Spans(self.stats, SPAN_NAMES)

    # ---- registration / admission -----------------------------------------
    def register_graph(self, name: str, graph: Graph, *,
                       reorder: str | None = None) -> None:
        self.cache.register(name, graph,
                            reorder=reorder or self.default_reorder)
        # per-graph queue-wait accounting (seconds spent submitted but not
        # yet seeded into a lane) and shed counts, keyed into stats so
        # launchers/benchmarks report them without extra plumbing
        self.stats[f"queue_wait_s:{name}"] = 0.0
        self.stats[f"rejected:{name}"] = 0

    def register_workload(self, workload: Workload, *,
                          replace: bool = False) -> None:
        """Register a workload plugin on this engine alone (module-wide
        default for engines built later: ``repro.serve.workloads.register``).
        Duplicate kinds raise unless ``replace=True`` — silently shadowing
        a built-in would change the semantics of every subsequent submit
        of that kind (§15.3)."""
        if not workload.kind:
            raise ValueError("workload must set a non-empty kind")
        if not replace and workload.kind in self._workloads:
            raise ValueError(
                f"workload kind {workload.kind!r} already registered on "
                f"this engine (pass replace=True to override)")
        self._workloads[workload.kind] = workload
        # a replaced workload's memoized per-graph state is stale
        for per in self._wl_state.values():
            per.pop(workload.kind, None)

    @property
    def workload_kinds(self) -> list[str]:
        return sorted(self._workloads)

    def submit(self, graph: str, source: int, kind: str = KIND_BFS,
               *, target: int | None = None,
               tenant: str = "default",
               deadline: float | None = None) -> Ticket:
        """Enqueue one request; returns a :class:`Ticket` (int-compatible
        request id + completion handle).  Legal at any time — between
        ``step()`` calls the request joins the graph's live session
        mid-flight, exactly like PR 1's mid-flight admission.

        Never blocks on artifact construction (§14.3): a cache miss
        schedules a background build and the ticket waits in
        ``BUILDING``.  Over the §14.2 queue-depth caps the request is
        shed instead of queued — a terminal ``REJECTED`` ticket under
        ``overload='reject'`` (the engine forgets it immediately), or a
        deferred one promoted later under ``'defer'``.

        ``deadline`` (relative seconds, §16.1) makes shedding SLO-aware
        instead of purely depth-based: when the EWMA service model
        predicts this request cannot complete inside its budget given
        the backlog ahead of it, it is shed *now* as a terminal
        ``EXPIRED`` ticket (like ``REJECTED``, never delivered through
        ``step()``) — shedding the predicted violator at submission is
        strictly cheaper than queueing it to miss.  The deadline is
        re-checked at lane seeding and at every window boundary; a cold
        model always admits."""
        if not self.cache.is_registered(graph):
            raise KeyError(f"graph {graph!r} not registered")
        wl = self._workloads.get(kind)
        if wl is None:
            raise ValueError(f"unknown query kind {kind!r}; registered "
                             f"workloads: {self.workload_kinds}")
        g = self.cache.graph(graph)
        if not 0 <= source < g.n:
            raise ValueError(f"source {source} out of range for {graph!r}")
        if deadline is not None:
            deadline = float(deadline)
            if deadline <= 0:
                raise ValueError(f"deadline must be > 0 s, got {deadline}")
        rid = next(self._rids)
        q = BfsQuery(rid=rid, graph=graph, source=int(source), kind=kind,
                     target=None if target is None else int(target),
                     tenant=str(tenant))
        wl.validate(q, g)
        ticket = Ticket(rid, self, q, deadline)
        self.stats["queries"] += 1
        if ticket.deadline_at is not None:
            depth = len(self._queues.get(graph) or ())
            # §16.1: deferred arrivals wait in line too — they promote
            # into this graph's queue ahead of the new request, so
            # leaving them out of the queueing term under-predicts wait
            # exactly when overload='defer' is shedding-relevant
            depth += sum(1 for d in self._deferred if d.graph == graph)
            pred = self._slo.predict_latency(graph, kind, depth, self.kappa)
            if (pred is not None
                    and ticket.submitted_at + pred > ticket.deadline_at):
                self._shed_expired(
                    ticket, ticket.submitted_at, where="admission",
                    deliver=False,
                    cause=(f"predicted latency {pred:.4f}s exceeds the "
                           f"{deadline}s deadline at queue depth {depth}"))
                return ticket
        if self._over_capacity(graph):
            if self.overload == "reject":
                ticket.state = TicketState.REJECTED
                ticket.error = (
                    f"queue for graph {graph!r} at capacity "
                    f"(max_queue={self.max_queue}, "
                    f"max_queue_total={self.max_queue_total})")
                ticket.completed_at = ticket.submitted_at
                self.stats["rejected"] += 1
                key = f"rejected:{graph}"
                self.stats[key] = self.stats.get(key, 0) + 1
                key = f"shed_tenant:{q.tenant}"
                self.stats[key] = self.stats.get(key, 0) + 1
                return ticket
            self._tickets[rid] = ticket
            self._deferred.append(q)
            self.stats["deferred"] += 1
            return ticket
        self._tickets[rid] = ticket
        self._enqueue(q, ticket)
        return ticket

    @property
    def pending(self) -> int:
        """Requests submitted but not yet seeded into a lane (deferred
        arrivals included)."""
        return (sum(len(q) for q in self._queues.values())
                + len(self._deferred))

    @property
    def in_flight(self) -> int:
        """Requests currently occupying a lane in some live session."""
        return sum(s.in_flight for s in self._sessions.values())

    # ---- admission control / build plumbing (§14) -------------------------
    def _over_capacity(self, graph: str) -> bool:
        """The §14.2 queue-depth check: counts requests waiting for a
        lane (in-flight lanes and deferred arrivals are not depth — the
        caps bound *waiting* work, which is what latency tails see)."""
        if self.max_queue is not None:
            q = self._queues.get(graph)
            if q is not None and len(q) >= self.max_queue:
                return True
        if self.max_queue_total is not None:
            if sum(len(q) for q in self._queues.values()) >= \
                    self.max_queue_total:
                return True
        return False

    def _enqueue(self, q: BfsQuery, ticket: Ticket | None) -> None:
        queue = self._queues.get(q.graph)
        if queue is None:
            queue = self._queues[q.graph] = _TenantQueue(self.tenant_weights)
        queue.append(q)
        self._ensure_build(q.graph, ticket)

    def _ensure_build(self, name: str, ticket: Ticket | None = None) -> None:
        """Make sure ``name``'s artifact is resident or on its way:
        schedules a background build on a miss (§14.3) and keeps the
        affected tickets' lifecycle state honest.  ``build_workers=0``
        is the legacy synchronous path — the build runs inline (the
        submitting thread pays for it), with failures still surfacing as
        ``FAILED`` tickets rather than an engine crash."""
        if name in self.cache or name in self._built:
            return
        if self.build_workers == 0:
            try:
                self.cache.get(name)
            except KeyError:
                raise
            except Exception as e:  # noqa: BLE001 — any build error
                self._fail_graph(name, e)
            return
        if not self.cache.build_pending(name):
            self.cache.start_build(name)
            self.stats["builds"] += 1
            for pending_q in self._queues.get(name) or ():
                t = self._tickets.get(pending_q.rid)
                if t is not None and t.state == TicketState.QUEUED:
                    t.state = TicketState.BUILDING
        elif ticket is not None:
            ticket.state = TicketState.BUILDING

    def _poll_builds(self) -> None:
        """Collect finished background builds (non-blocking).  Successes
        move their tickets ``BUILDING → QUEUED``; the artifact reference
        is held in ``_built`` until the session opens, so an eviction
        racing the install (a same-poll neighbour became MRU under a
        tight budget) cannot force a synchronous rebuild.  Failures fan
        out to the graph's tickets as ``FAILED`` (§14.3)."""
        for name, art, exc in self.cache.poll_builds():
            if exc is not None:
                self._fail_graph(name, exc)
                continue
            if self._queues.get(name):
                self._built[name] = art
                for q in self._queues[name]:
                    t = self._tickets.get(q.rid)
                    if t is not None and t.state == TicketState.BUILDING:
                        t.state = TicketState.QUEUED

    def _promote_deferred(self) -> None:
        """Re-admit deferred arrivals (overload='defer') while the §14.2
        caps allow — earliest deadline first (§16.1 EDF), submission
        order among deadline-free requests (the sort is stable, so the
        pre-§16 FIFO behaviour is unchanged when nobody sets
        deadlines).  Deferred requests whose deadline has already
        passed are shed here instead of promoted — the window-boundary
        check for work that never reached a queue."""
        if not self._deferred:
            return
        now = self._clock()

        def urgency(q: BfsQuery) -> float:
            t = self._tickets.get(q.rid)
            if t is None or t.deadline_at is None:
                return float("inf")
            return t.deadline_at

        held: deque[BfsQuery] = deque()
        for q in sorted(self._deferred, key=urgency):
            t = self._tickets.get(q.rid)
            if t is None:
                continue  # cancelled under us; already terminal
            if t.deadline_at is not None and now > t.deadline_at:
                self._tickets.pop(q.rid, None)
                self._shed_expired(t, now, where="deferred promotion",
                                  deliver=True)
                continue
            if self._over_capacity(q.graph):
                held.append(q)
                continue
            self._enqueue(q, t)
        self._deferred = held

    def _fail_graph(self, name: str, exc: BaseException) -> None:
        """Terminate every request waiting on ``name`` with a ``FAILED``
        ticket (§14.3): the queue and any deferred arrivals drain, other
        graphs' sessions never notice, and a later submit retries the
        build from scratch."""
        self.stats["build_failures"] += 1
        msg = f"artifact build for graph {name!r} failed: {exc!r}"
        victims: list[BfsQuery] = []
        queue = self._queues.pop(name, None)
        if queue is not None:
            victims.extend(queue)
        if self._deferred:
            victims.extend(q for q in self._deferred if q.graph == name)
            self._deferred = deque(
                q for q in self._deferred if q.graph != name)
        now = self._clock()
        for q in victims:
            t = self._tickets.pop(q.rid, None)
            if t is None:
                continue
            t.state = TicketState.FAILED
            t.error = msg
            t.completed_at = now
            self._completed.append(t)

    # ---- per-graph graceful degradation (§16.4) ----------------------------
    def _quarantine_pair(self, name: str, layout: str, why: str) -> None:
        """Record one (graph, layout) quarantine: ``_resolve_layout``
        falls back to the base layout for the pair from now on."""
        if (name, layout) not in self._quarantine:
            self._quarantine[(name, layout)] = why
            self.stats["degraded"] += 1

    def _note_degraded(self, art: GraphArtifacts) -> None:
        """Adopt a build-time degradation (§16.4): MMA tile prep raised
        inside ``build_artifacts``, so the artifact landed without tiles —
        quarantine (graph, 'mma') so health() shows it and a forced
        ``layout='mma'`` engine serves the base layout instead of
        crashing the session open."""
        if art.degraded:
            self._quarantine_pair(art.name, "mma", art.degraded)

    def _handle_session_fault(self, name: str, sess: "_GraphSession",
                              exc: BaseException) -> None:
        """A session tick raised (§16.4).  On a non-base layout:
        quarantine (graph, layout), drop the compiled runner, and put the
        in-flight requests back at the *front* of the graph's queue — a
        fresh session re-opens on the base layout next step and re-runs
        them from scratch (lanes restart, results stay oracle-exact), so
        no ticket fails.  Base-layout faults never reach here: the
        caller re-raises them — there is nothing left to fall back to,
        and §15.3 extract validation must stay loud."""
        self._sessions.pop(name, None)
        was_head = self._rotation and self._rotation[0] == name
        if name in self._rotation:
            self._rotation.remove(name)
        if was_head and self._rotation:
            self._quantum_left = self._weight(self._rotation[0])
        in_flight = [q for q in sess.lanes if q is not None]
        lay = self._resolve_layout(sess.art)
        self._drop_runner(name)
        self._quarantine_pair(name, lay, f"session tick raised: {exc!r}")
        queue = self._queues.get(name)
        if queue is None:
            queue = self._queues[name] = _TenantQueue(self.tenant_weights)
        for q in reversed(in_flight):
            t = self._tickets.get(q.rid)
            if t is None:
                continue
            if t.cancel_requested:
                self._finish_cancel(t)
                continue
            t.state = TicketState.QUEUED
            t.admitted_at = None
            queue.prepend(q)

    def _idle_wait(self, timeout: float = 0.05) -> None:
        """Bounded wait when a drain loop (``run()`` /
        ``Ticket.result()``) has nothing else to do — ``step()`` itself
        never calls this, so pumping stays non-blocking.  Event- and
        clock-driven, never a fixed sleep (the pre-§16 version
        wall-blocked a hard-coded 0.05 s even under a fake clock):

        * a build in flight → wait on its future (returns the moment it
          lands, ``timeout`` cap);
        * only a §16.3 backoff pending → wall clocks sleep exactly
          ``min(remaining, timeout)``; injected clocks *kick* the retry
          instead (a blocking drain can advance neither wall time nor a
          fake clock, so the backoff is declared elapsed) and return
          immediately — fake-clock drains never wall-block;
        * nothing pending → return immediately."""
        if self._sessions or self._completed:
            return
        if self.cache.wait_builds(timeout=timeout):
            return
        self._retry_nap(timeout)

    def _retry_nap(self, cap: float) -> None:
        """Wait out (wall clock) or kick (injected clock, §16.3) the
        earliest pending build retry; no-op when none is pending."""
        due_in = self.cache.next_retry_in()
        if due_in is None or due_in <= 0:
            return
        if self._wall_clock:
            time.sleep(min(due_in, cap))
        else:
            self.cache.kick_retries()

    def _await_builds(self) -> None:
        """Block until no *queued* graph's artifact build is pending —
        ``run()``'s pre-pass.  ``run()`` drains everything anyway (it was
        the synchronous-build path before §14), so waiting here restores
        its deterministic all-ready drain — every queued graph's session
        opens on the first step — without touching the non-blocking
        ``step()`` contract.  Builds for graphs nothing is queued on are
        not waited for; §16.3 backoff waits are slept out (wall clock)
        or kicked (injected clock) like ``_idle_wait``."""
        while True:
            self._poll_builds()
            self._promote_deferred()
            waiting = [n for n, q in self._queues.items()
                       if q and n not in self.cache and n not in self._built]
            for n in waiting:
                self._ensure_build(n)
            if not any(self.cache.build_pending(n) for n in waiting):
                return
            if not self.cache.wait_builds(timeout=0.2):
                self._retry_nap(0.2)

    # ---- serving ----------------------------------------------------------
    def step(self) -> list[Ticket]:
        """Advance one scheduling tick (§12.1): collect finished
        background builds and promote deferred arrivals (§14), open
        sessions for graphs whose artifacts are ready, give the next
        session in rotation one tick (one traversal level or one
        megatick window), close it if it went idle, and return the
        tickets that reached a terminal state — possibly empty, also
        when nothing is pending at all.  Non-blocking in the service
        sense and now also in the *build* sense: one bounded slice of
        work per call, never a synchronous artifact build (§14.3), so a
        caller can interleave submission and pumping in its own loop."""
        with self._spans("serve.step"):
            with self._spans("serve.housekeep"):
                self._poll_builds()
                self._promote_deferred()
                self._open_sessions()
            if self._sessions:
                name = self._schedule()
                sess = self._sessions[name]
                try:
                    sess.tick()
                except Exception as exc:  # noqa: BLE001 — §16.4 degradation
                    if self._resolve_layout(sess.art) == self._base_layout():
                        raise  # nothing to fall back to; stay loud (§15.3)
                    self._handle_session_fault(name, sess, exc)
                else:
                    self.stats["ticks"] += 1
                    if (self._last_scheduled not in (None, name)
                            and len(self._sessions) > 1):
                        self.stats["session_switches"] += 1
                    self._last_scheduled = name
                    if sess.idle:
                        self._close_session(name)
            done, self._completed = self._completed, []
        return done

    def run(self) -> dict[int, BfsResult]:
        """Drain every pending request; returns {rid: result} for the ones
        completed by this call (also recorded in ``self.results`` when the
        engine was built with ``keep_results=True``).

        Scheduling is the documented §12.2 policy — FIFO within a graph,
        round-robin across graph sessions — not the graph-serial drain of
        PR 1 (whose docstring claimed a per-request FIFO it did not
        implement); ``BfsEngine(scheduler="serial")`` restores the old
        graph-at-a-time behaviour.

        Requests that terminated without a result (``REJECTED`` tickets
        are never the engine's to drain; ``FAILED`` ones surface through
        their tickets / ``step()``) do not appear in the dict — check
        ``ticket.state`` or ``stats['build_failures']``."""
        out: dict[int, BfsResult] = {}
        self._await_builds()
        while self.has_work():
            stepped = self.step()
            for t in stepped:
                if t._result is not None:
                    out[int(t)] = t._result
            if not stepped:
                self._idle_wait()
        return out

    def has_work(self) -> bool:
        """True while any request is queued (deferred included), any
        session is live, any artifact build is in flight for queued
        work, or a completion awaits delivery by the next ``step()`` (a
        ticket re-queued by another ticket's ``result()`` pump) — the
        public pump predicate (``while eng.has_work(): eng.step()``)."""
        return (bool(self._sessions) or bool(self._completed)
                or bool(self._deferred) or any(self._queues.values()))

    # ---- scheduler (§12.2) ------------------------------------------------
    def _open_sessions(self) -> None:
        ready: list[str] = []
        # snapshot: a failed sync build inside _ensure_build pops the
        # graph's queue (_fail_graph) mid-iteration
        for name, q in list(self._queues.items()):
            if not q or name in self._sessions:
                continue
            if name in self.cache or name in self._built:
                ready.append(name)
            else:
                # queued work on a non-resident graph (evicted since, or
                # never built): (re)schedule the background build; the
                # session opens once it lands.  The synchronous path
                # (build_workers=0) lands immediately, so it keeps PR 5's
                # same-step session-open behaviour.
                self._ensure_build(name)
                if name in self.cache:
                    ready.append(name)
        if self.scheduler == "serial":
            # PR 1 semantics: one graph at a time, in queue-insertion
            # order among the graphs whose artifacts are ready — a graph
            # mid-build never blocks a ready neighbour's session
            if not self._sessions and ready:
                self._open_session(ready[0])
            return
        for name in ready:
            self._open_session(name)

    def _open_session(self, name: str) -> None:
        # prefer the resident entry (LRU touch + hit accounting); fall
        # back to the §14.3 held reference when eviction raced the build
        held = self._built.pop(name, None)
        art = self.cache.get(name) if name in self.cache else held
        if art is None:
            # evicted between the ready scan and the open: a sync inline
            # build for a neighbouring graph inside _open_sessions can
            # shrink the cache mid-scan.  Reschedule (sync rebuilds
            # inline; async opens once the fresh build lands) instead of
            # opening a session on a missing artifact.
            self._ensure_build(name)
            if name not in self.cache:
                return
            art = self.cache.get(name)
        self._note_degraded(art)
        try:
            sess = self._new_session(name, art)
        except Exception as exc:  # noqa: BLE001 — §16.4 degradation
            lay = self._resolve_layout(art)
            if lay == self._base_layout():
                raise  # nothing to fall back to; stay loud
            self._quarantine_pair(name, lay,
                                  f"session open raised: {exc!r}")
            self._drop_runner(name)
            sess = self._new_session(name, art)
        self._sessions[name] = sess
        self._rotation.append(name)
        if len(self._rotation) == 1:
            self._quantum_left = self._weight(name)
        self.stats["max_live_sessions"] = max(
            self.stats["max_live_sessions"], len(self._sessions))

    def _new_session(self, name: str, art: GraphArtifacts):
        """One serving session for ``art``: a §17.1 mesh group (one
        replica sub-session per device, kappa lanes each) when the
        artifact was replicated across a device group, else the plain
        single-runner session.  Sharded artifacts (§17.2) run as one
        session whose runner dispatches over the whole group."""
        if getattr(art, "replicas", None):
            return mesh_mod._MeshSessionGroup(self, name,
                                              self._queues[name], art)
        return _GraphSession(self, name, self._queues[name], art)

    def _close_session(self, name: str) -> None:
        sess = self._sessions.pop(name)
        was_head = self._rotation and self._rotation[0] == name
        self._rotation.remove(name)
        if was_head and self._rotation:
            self._quantum_left = self._weight(self._rotation[0])
        # drop the graph's (empty) queue object so a later submit starts a
        # fresh one; guard against it having been replaced meanwhile
        if not sess.queue and self._queues.get(name) is sess.queue:
            self._queues.pop(name)

    def _schedule(self) -> str:
        """Pick this tick's session: serve the rotation head until its
        quantum (its weight, default 1) is spent, then rotate."""
        rot = self._rotation
        name = rot[0]
        self._quantum_left -= 1
        if self._quantum_left <= 0:
            rot.rotate(-1)
            self._quantum_left = self._weight(rot[0])
        return name

    def _weight(self, name: str) -> int:
        return self.weights.get(name, 1) if self.weights else 1

    # ---- ticket bookkeeping -----------------------------------------------
    def _lane_admitted(self, q: BfsQuery, now: float) -> None:
        t = self._tickets.get(q.rid)
        if t is not None:
            t.admitted_at = now
            t.state = TicketState.RUNNING
            key = f"queue_wait_s:{q.graph}"
            self.stats[key] = (self.stats.get(key, 0.0)
                               + (now - t.submitted_at))

    def _lane_completed(self, q: BfsQuery, res: BfsResult) -> None:
        t = self._tickets.pop(q.rid, None)
        if t is not None:
            t._result = res
            t.state = TicketState.DONE
            t.completed_at = self._clock()
            if t.admitted_at is not None:
                # §16.1: feed the EWMA predictor the lane service time
                # (admission -> completion; queue wait excluded)
                self._slo.observe(q.graph, q.kind,
                                  t.completed_at - t.admitted_at)
            if t.deadline_at is not None and t.completed_at > t.deadline_at:
                self.stats["deadline_misses"] += 1
            self._completed.append(t)
        if self.keep_results:
            self.results[q.rid] = res

    # ---- deadline / cancellation lifecycle (§16.1, §16.2) ------------------
    def _shed_expired(self, t: Ticket, now: float, *, where: str,
                      deliver: bool, cause: str | None = None) -> None:
        """Move ``t`` to terminal ``EXPIRED``.  ``deliver=False`` is the
        submission-time shed (the ticket never entered the engine, so —
        like ``REJECTED`` — it is not delivered through ``step()``);
        later sheds deliver exactly once."""
        t.state = TicketState.EXPIRED
        t.error = (cause or
                   f"deadline of {t.deadline}s exceeded") + f" ({where})"
        t.completed_at = now
        self.stats["expired"] += 1
        key = f"shed_tenant:{t.query.tenant}"
        self.stats[key] = self.stats.get(key, 0) + 1
        if deliver:
            self._completed.append(t)

    def _seed_ok(self, q: BfsQuery, now: float) -> bool:
        """The §16.1 lane-seeding check: False sheds the request instead
        of seeding it — its deadline has already passed, or the EWMA
        service estimate says the lane cannot finish inside it (the
        queueing term is gone here; only service time remains)."""
        t = self._tickets.get(q.rid)
        if t is None:
            return False  # defensively skip a ghost entry
        if t.deadline_at is None:
            return True
        srv = self._slo.service(q.graph, q.kind)
        if now > t.deadline_at or (srv is not None
                                   and now + srv > t.deadline_at):
            self._tickets.pop(q.rid, None)
            self._shed_expired(t, now, where="lane seeding", deliver=True)
            return False
        return True

    def _cancel(self, t: Ticket) -> bool:
        """``Ticket.cancel``'s engine side (§16.2)."""
        if t.done():
            return False
        if t.cancel_requested:
            return True  # idempotent: already headed for CANCELLED
        q = t.query
        if t.state == TicketState.RUNNING:
            # in a lane: reclaimed at the session's next window boundary
            # (_GraphSession._reclaim_lanes); a megatick window in
            # progress is never interrupted mid-dispatch
            t.cancel_requested = True
            return True
        # waiting (QUEUED/BUILDING, queued or deferred): free it now
        queue = self._queues.get(q.graph)
        removed = queue.remove_rid(q.rid) if queue is not None else None
        if removed is None:
            for d in self._deferred:
                if d.rid == q.rid:
                    self._deferred.remove(d)
                    break
        self._tickets.pop(q.rid, None)
        # an emptied queue with no live session would linger (sessions
        # normally own queue teardown); drop it so state stays tidy
        if (queue is not None and not queue
                and q.graph not in self._sessions
                and self._queues.get(q.graph) is queue):
            self._queues.pop(q.graph, None)
        self._finish_cancel(t)
        return True

    def _finish_cancel(self, t: Ticket) -> None:
        """Terminal-ize a cancellation: CANCELLED, delivered exactly
        once through ``step()`` like every in-engine terminal."""
        self._tickets.pop(t.query.rid, None)
        t.state = TicketState.CANCELLED
        t.error = f"request {int(t)} cancelled by caller"
        t.completed_at = self._clock()
        self.stats["cancelled"] += 1
        self._completed.append(t)

    # ---- health snapshot (§16.4) -------------------------------------------
    def health(self) -> lifecycle_mod.EngineHealth:
        """One self-contained operator snapshot of the lifecycle layer:
        queue depths, deferred/in-flight occupancy, builds in every
        pipeline stage, shed/expiry/cancel/miss counters, the §16.4
        degradation registry, and the EWMA service-time estimates."""
        return lifecycle_mod.EngineHealth(
            queue_depths={n: len(qq) for n, qq in self._queues.items()
                          if len(qq)},
            deferred=len(self._deferred),
            in_flight=self.in_flight,
            live_sessions=list(self._sessions),
            building=self.cache.building,
            retry_pending=self.cache.retry_pending,
            build_retries=self.cache.retries,
            build_failures=self.stats["build_failures"],
            rejected=self.stats["rejected"],
            expired=self.stats["expired"],
            cancelled=self.stats["cancelled"],
            deadline_misses=self.stats["deadline_misses"],
            degraded={f"{n}:{lay}": why
                      for (n, lay), why in sorted(self._quarantine.items())},
            tenant_shed={k.split(":", 1)[1]: v
                         for k, v in sorted(self.stats.items())
                         if k.startswith("shed_tenant:")},
            service_times=self._slo.snapshot(),
            device_bytes=self.cache.per_device(),
            device_queue_depth=self._device_queue_depth(),
        )

    # ---- per-graph runners / probe adoption --------------------------------
    def _base_layout(self) -> str:
        """The backend-default substrate every graph can always fall back
        to (§16.4): packed uint32 on TPU, uint8 byteplanes elsewhere —
        the layouts with no per-graph prep step that can fail."""
        return "packed" if jax.default_backend() == "tpu" else "byteplane"

    def _resolve_layout(self, art: GraphArtifacts) -> str:
        """The layout this graph is actually served with: forced layouts
        pass through; 'auto' consults the probe's ``dense_layout`` verdict
        (§13.4) when tiles were probed, else the backend default.  A
        (graph, layout) pair quarantined by §16.4 degradation resolves to
        the base layout instead — bit-identical results, no fast path."""
        base = self._base_layout()
        if self.layout != "auto":
            lay = self.layout
        else:
            sw = art.switching
            if (sw is not None and sw.dense_layout == "mma"
                    and art.mma is not None):
                lay = "mma"
            else:
                lay = base
        if lay != base and (art.name, lay) in self._quarantine:
            return base
        return lay

    def _make_probe_runner(self, bd: BvssDevice, tiles=None):
        """Probe-runner factory handed to :class:`GraphCache`: the base
        runner in the engine's (resolved) layout, plus — when tile prep
        ran and the layout is probe-selectable 'auto' — the MMA alternate
        the probe times against it (§13.4).  Returns the pair when the
        alternate exists, the base runner alone otherwise."""
        base_layout = self.layout
        if base_layout == "auto":
            base_layout = ("packed" if jax.default_backend() == "tpu"
                           else "byteplane")
        base = _LaneRunner(bd, self.kappa, layout=base_layout,
                           use_pallas=self._probe_pallas,
                           mma_tiles=tiles if base_layout == "mma" else None)
        alt = None
        if tiles is not None and self.layout == "auto":
            alt = _LaneRunner(bd, self.kappa, layout="mma",
                              use_pallas=self._probe_pallas, mma_tiles=tiles)
        self._probe_runners_last = (base, alt)
        return (base, alt) if alt is not None else base

    def _adopt_probe_runner(self, bd: BvssDevice,
                            want_layout: str) -> _LaneRunner | None:
        """The probe's runners are jit-warm for every per-level shape of
        this graph; adopt the one matching the resolved layout/kernel
        config for serving instead of compiling a twin."""
        made, self._probe_runners_last = self._probe_runners_last, None
        if made is None:
            return None
        want_pallas = self.use_pallas
        if want_pallas is None:
            want_pallas = jax.default_backend() == "tpu"
        for r in made:
            if (r is not None and r.bd is bd and r.layout == want_layout
                    and r.use_pallas == want_pallas):
                return r
        return None

    def _runner_for(self, art: GraphArtifacts) -> _LaneRunner:
        if art.replicas:
            return self._mesh_runners_for(art)[0]  # §17.1: replica 0's
        name, bd = art.name, art.bd
        r = self._runners.get(name)
        if getattr(art, "sharded", None) is not None:
            # §17.2 graph-parallel: one runner spanning the whole group
            if not isinstance(r, mesh_mod.ShardedLaneRunner) or r.bd is not bd:
                r = mesh_mod.ShardedLaneRunner(
                    art.sharded, bd, self.kappa,
                    layout=self._resolve_layout(art))
                self._runners[name] = r
            return r
        if r is None or r.bd is not bd:
            layout = self._resolve_layout(art)
            r = (self._adopt_probe_runner(bd, layout)
                 or _LaneRunner(bd, self.kappa, layout=layout,
                                use_pallas=self.use_pallas,
                                mma_tiles=art.mma))
            self._runners[name] = r
        return r

    def _mesh_runners_for(self, art: GraphArtifacts) -> list[_LaneRunner]:
        """Per-replica runners for a §17.1 source-parallel artifact, one
        per device in its placement group, each committed to its replica's
        chip and all sharing one host slot table; cached per graph (the jit
        caches inside a runner are per-shape and expensive to rebuild)."""
        name = art.name
        group = self._mesh_runners.get(name)
        if group is None or group[0].bd is not art.replicas[0]:
            layout = self._resolve_layout(art)
            # the probe's runners (on the default device) serve no replica
            self._probe_runners_last = None
            group, table = [], None
            for bd_k in art.replicas:
                (dev,) = bd_k.row_ids.devices()
                group.append(_LaneRunner(
                    bd_k, self.kappa, layout=layout,
                    use_pallas=self.use_pallas, mma_tiles=art.mma,
                    device=dev, table=table))
                table = group[-1].table
            self._mesh_runners[name] = group
            # keep the single-runner registry pointing at replica 0 so
            # layout introspection (tests, launchers) sees the mesh graph
            self._runners[name] = group[0]
        return group

    def _drop_runner(self, name: str) -> None:
        self._runners.pop(name, None)
        self._mesh_runners.pop(name, None)
        self._wl_state.pop(name, None)

    # ---- mesh placement (§17) ----------------------------------------------
    def _mesh_build(self, name: str, g: Graph,
                    reorder: str | None) -> GraphArtifacts:
        """The cache's ``build_fn`` when mesh serving or a per-device
        byte budget is configured: route the build through
        :func:`repro.serve.mesh.build_mesh_artifacts`, placing the graph
        on the least-loaded device group (§17.3)."""
        group = self._pick_group() if self.mesh is not None else None
        return mesh_mod.build_mesh_artifacts(
            name, g, group=group, reorder=reorder,
            config=self.cache.config, probe=self.cache.probe,
            eta=self.cache.eta,
            probe_use_pallas=self.cache.probe_use_pallas,
            probe_runner=self.cache.probe_runner,
            device_budget=self.device_budget,
            fault_hook=self.cache.fault_hook)

    def _pick_group(self):
        """Least-loaded placement (§17.3): the device group carrying the
        fewest resident cache bytes takes the next build.  Reads only
        the cache's entry map, so the §14.3 worker thread may call it."""
        groups = self.mesh.groups
        if len(groups) == 1:
            return groups[0]
        per = self.cache.per_device()
        return min(groups, key=lambda grp: sum(per.get(int(d.id), 0)
                                               for d in grp))

    def _placement_of(self, name: str) -> tuple:
        """Device ids serving ``name`` right now: the pinned session
        artifact if live, else the resident/held entry; empty when the
        graph has no placed artifact (single-device default)."""
        sess = self._sessions.get(name)
        if sess is not None:
            return getattr(sess.art, "placement", ())
        art = self.cache.peek(name) or self._built.get(name)
        return getattr(art, "placement", ()) if art is not None else ()

    def _device_queue_depth(self) -> dict[int, int]:
        """Queued requests per device id (§17.3): each graph's queue
        depth lands on every device in its placement (lanes will open
        there), or the default device when unplaced."""
        out: dict[int, int] = {}
        default = self.cache.default_device_id
        for name, qq in self._queues.items():
            depth = len(qq)
            if not depth:
                continue
            for dev in (self._placement_of(name) or (default,)):
                out[dev] = out.get(dev, 0) + depth
        return out

    def _workload_graph_state(self, name: str, wl: Workload, graph) -> object:
        """Memoized ``Workload.graph_state`` for ``graph`` (§15.2): shared
        across sessions while the cache entry lives, rebuilt lazily after
        eviction (a live session keeps its own reference, see
        ``_GraphSession.graph_states``)."""
        per = self._wl_state.setdefault(name, {})
        if wl.kind not in per:
            per[wl.kind] = wl.graph_state(graph)
        return per[wl.kind]

    def _policy_active(self, art: GraphArtifacts) -> bool:
        """Resolve the per-graph mode policy (DESIGN.md §10.3): 'off' forces
        dense, 'on' forces the Eq. (6) policy, 'auto' defers to the cached
        probe verdict (policy applied when no verdict is available)."""
        if self.switching == "off":
            return False
        if self.switching == "on":
            return True
        sw = art.switching
        return True if sw is None else bool(sw.enabled)
