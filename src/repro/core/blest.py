"""BLEST single-source BFS pipelines (paper Algs. 2 & 3) in JAX.

Two drivers:

* :func:`bfs_fused` — the persistent-kernel analogue: one ``lax.while_loop``
  holds the whole level loop on-device (GRIDSYNC == loop-carried dataflow; no
  host round-trips).  Work per level is dense over all VSSs, with inactive
  VSSs neutralized by an all-zero frontier word (the queue is implicit).
* :func:`bfs_bucketed` — per-level host loop with *real* frontier-compacted
  scheduling: active VSS ids are gathered into power-of-two padded buckets
  (bounded recompiles), matching the paper's work-queue semantics where work
  is proportional to |Q|*tau rather than N_v*tau.  Eq. (6) switching between
  queued top-down and dense bottom-up lives here (core/switching.py).

Update mechanics:
* ``lazy=True``  (Alg. 3): Stage 1 marks V_next unconditionally (scatter-max,
  the REDG analogue), Stage 2 is the fused frontier sweep.
* ``lazy=False`` (Alg. 2): the eager variant gathers V[row_ids] and filters
  marks before scattering — the extra random gather is the ATOMG-cost
  analogue and is what the lazy scheme removes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bvss import Bvss
from repro.kernels import ops

UNREACHED = np.iinfo(np.int32).max
VSS_PAD = 8  # N_v padded to a multiple of this (and >= 1 extra padding row)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["masks", "masks_packed", "row_ids", "v2r", "real_ptrs"],
    meta_fields=["n", "n_pad", "n_ext", "num_sets", "num_sets_ext",
                 "num_vss", "num_vss_pad", "sigma", "tau"])
@dataclasses.dataclass(frozen=True)
class BvssDevice:
    """BVSS moved to device, padded for tiling.

    Sentinels: padding VSS rows have ``v2r == num_sets`` (an extra, always
    inactive slice set) and ``row_ids == n_pad`` (an extra, ignored vertex
    slot).  V/level arrays are sized ``n_ext = n_pad + sigma`` so sentinel
    scatters land in-bounds but outside the reported range.

    A pytree (the sizes are static): jitted code takes it as an argument.
    A jitted closure over it would embed every array in the program as a
    constant — at scale 20 that is ~100 MB per program, minutes of
    compilation, and a device copy per executable.
    """

    n: int
    n_pad: int
    n_ext: int
    num_sets: int          # real slice sets (n_pad // sigma)
    num_sets_ext: int      # + 1 sentinel set
    num_vss: int           # real VSS count
    num_vss_pad: int
    sigma: int
    tau: int
    masks: jax.Array          # (num_vss_pad, tau) uint8
    masks_packed: jax.Array   # (num_vss_pad, tau//4) uint32
    row_ids: jax.Array        # (num_vss_pad, tau) int32
    v2r: jax.Array            # (num_vss_pad,) int32
    real_ptrs: jax.Array      # (num_sets + 1,) int32


def to_device(b: Bvss) -> BvssDevice:
    sigma, tau = b.config.sigma, b.config.tau
    num_vss_pad = ((b.num_vss + VSS_PAD) // VSS_PAD) * VSS_PAD  # >=1 pad row
    pad = num_vss_pad - b.num_vss
    masks = np.concatenate([b.masks[: b.num_vss],
                            np.zeros((pad, tau), np.uint8)])
    row_ids = np.concatenate([b.row_ids[: b.num_vss],
                              np.full((pad, tau), b.n_pad, np.int32)])
    v2r = np.concatenate([b.virtual_to_real,
                          np.full(pad, b.num_sets, np.int32)]).astype(np.int32)
    masks_j = jnp.asarray(masks)
    return BvssDevice(
        n=b.n,
        n_pad=b.n_pad,
        n_ext=b.n_pad + sigma,
        num_sets=b.num_sets,
        num_sets_ext=b.num_sets + 1,
        num_vss=b.num_vss,
        num_vss_pad=num_vss_pad,
        sigma=sigma,
        tau=tau,
        masks=masks_j,
        masks_packed=ops.pack_masks(masks_j) if tau % 4 == 0 else masks_j,
        row_ids=jnp.asarray(row_ids),
        v2r=jnp.asarray(v2r),
        real_ptrs=jnp.asarray(b.real_ptrs),
    )


class BfsState(NamedTuple):
    v: jax.Array        # (n_ext,) uint8 visited
    level: jax.Array    # (n_ext,) int32
    f_words: jax.Array  # (num_sets_ext,) uint8 — current frontier words
    ell: jax.Array      # int32 — next level to assign


def init_state(bd: BvssDevice, src) -> BfsState:
    src = jnp.asarray(src, jnp.int32)
    v = jnp.zeros(bd.n_ext, jnp.uint8).at[src].set(1)
    level = jnp.full(bd.n_ext, UNREACHED, jnp.int32).at[src].set(0)
    f_words = jnp.zeros(bd.num_sets_ext, jnp.uint8).at[src // bd.sigma].set(
        (jnp.uint8(1) << (src % bd.sigma).astype(jnp.uint8))
    )
    return BfsState(v, level, f_words, jnp.int32(1))


def _stage1_marks(bd: BvssDevice, masks, alphas, *, use_pallas, packed):
    if packed:
        mp = ops.pull_ss_packed(masks, alphas, use_pallas=use_pallas)
        return ops.unpack_marks(mp)
    return ops.pull_ss(masks, alphas, use_pallas=use_pallas)


def _level_dense(bd: BvssDevice, state: BfsState, *, lazy: bool,
                 use_pallas: bool, packed: bool) -> BfsState:
    """One BFS level over all VSSs (queue implicit via zero frontier words)."""
    masks = bd.masks_packed if packed else bd.masks
    alphas = state.f_words[bd.v2r]
    marks = _stage1_marks(bd, masks, alphas, use_pallas=use_pallas,
                          packed=packed)
    return _scatter_and_sweep(bd, state, marks, bd.row_ids, lazy=lazy,
                              use_pallas=use_pallas)


def _scatter_and_sweep(bd: BvssDevice, state: BfsState, marks, row_ids, *,
                       lazy: bool, use_pallas: bool) -> BfsState:
    rows = row_ids.ravel()
    m = marks.ravel()
    if not lazy:
        # Alg. 2 eager mechanics: check visited before updating (ATOMG
        # analogue: the gather stalls on V's previous value).
        m = m & (1 - state.v[rows])
    v_next = state.v.at[rows].max(m)
    v_new, level_new, f_words, _active = ops.frontier_sweep(
        state.v, v_next, state.level, state.ell, sigma=bd.sigma,
        use_pallas=use_pallas)
    # sentinel slice set's word must stay zero: it is the last sigma slots of
    # n_ext, never written by real slices; padding slices write zeros only.
    return BfsState(v_new, level_new, f_words, state.ell + 1)


@functools.partial(jax.jit, static_argnames=("lazy", "use_pallas", "packed",
                                             "max_levels"))
def bfs_fused(
    bd: BvssDevice,
    src,
    *,
    lazy: bool = True,
    use_pallas: bool = True,
    packed: bool = True,
    max_levels: int | None = None,
) -> jax.Array:
    """Fully on-device BFS; returns the level array (n,) int32.

    The whole level loop is one XLA program — the analogue of the paper's
    fused persistent kernel (contribution 1, bullet "kernel fusion").
    """
    max_levels = bd.n_ext if max_levels is None else max_levels

    def cond(state: BfsState):
        return jnp.logical_and((state.f_words != 0).any(),
                               state.ell <= max_levels)

    def body(state: BfsState):
        return _level_dense(bd, state, lazy=lazy, use_pallas=use_pallas,
                            packed=packed)

    final = jax.lax.while_loop(cond, body, init_state(bd, src))
    return final.level[: bd.n]


@dataclasses.dataclass
class FusedBfs:
    """Fused BFS bound to one graph (source is a runtime arg); compiles once
    per graph shape and flags."""

    bd: BvssDevice
    lazy: bool = True
    use_pallas: bool = True
    packed: bool = True

    def __call__(self, src) -> jax.Array:
        return bfs_fused(self.bd, jnp.asarray(src, jnp.int32), lazy=self.lazy,
                         use_pallas=self.use_pallas, packed=self.packed)


# --------------------------------------------------------------------------
# Bucketed (host-driven) driver with real frontier-compacted scheduling.
# --------------------------------------------------------------------------


def bucket_size(k: int) -> int:
    """Round queue length up to a power of two (bounded recompiles).
    Shared by :class:`BucketedBfs` and the serve engine's queued sweeps
    (DESIGN.md §10.2)."""
    return max(VSS_PAD, 1 << (max(k, 1) - 1).bit_length())


_bucket_size = bucket_size  # historical internal alias


def expand_active_sets(real_ptrs: np.ndarray,
                       active_sets: np.ndarray) -> np.ndarray:
    """Active slice sets -> VSS id list (realPtrs range expansion).

    ``real_ptrs`` must be a host numpy copy of ``bd.real_ptrs``;
    ``active_sets`` a (num_sets,) bool mask.  Shared by the bucketed
    single-source driver and the serve engine's queued mode."""
    sets = np.nonzero(active_sets)[0]
    if sets.size == 0:
        return np.zeros(0, np.int32)
    starts = real_ptrs[sets]
    ends = real_ptrs[sets + 1]
    counts = ends - starts
    total = int(counts.sum())
    out = np.empty(total, np.int32)
    off = 0
    for s, c in zip(starts, counts):
        out[off : off + c] = np.arange(s, s + c, dtype=np.int32)
        off += c
    return out


@dataclasses.dataclass
class BucketedBfs:
    """Per-level host loop; work per level ~ |Q|·tau.

    ``eta`` enables Eq.(6) switching to the dense (bottom-up analogue) level
    when the frontier is crowded; see core/switching.py for the policy.
    """

    bd: BvssDevice
    lazy: bool = True
    use_pallas: bool = True
    packed: bool = True
    eta: float | None = 10.0  # None disables switching
    instrument: bool = False

    def __post_init__(self):
        bd = self.bd
        self.trace: list[dict] = []

        # the graph is an argument, not a closure (see BvssDevice)
        def dense_level(bd: BvssDevice, state: BfsState) -> BfsState:
            return _level_dense(bd, state, lazy=self.lazy,
                                use_pallas=self.use_pallas, packed=self.packed)

        def queued_level(bd: BvssDevice, state: BfsState,
                         qids: jax.Array) -> BfsState:
            masks = (bd.masks_packed if self.packed else bd.masks)[qids]
            rows = bd.row_ids[qids]
            alphas = state.f_words[bd.v2r[qids]]
            marks = _stage1_marks(bd, masks, alphas,
                                  use_pallas=self.use_pallas,
                                  packed=self.packed)
            return _scatter_and_sweep(bd, state, marks, rows, lazy=self.lazy,
                                      use_pallas=self.use_pallas)

        self._dense_level = functools.partial(jax.jit(dense_level), bd)
        self._queued_level = functools.partial(jax.jit(queued_level), bd)
        # host-side copies for queue expansion
        self._real_ptrs = np.asarray(bd.real_ptrs)
        self._pad_vss = bd.num_vss  # a guaranteed padding VSS id

    def _expand_queue(self, active_sets: np.ndarray) -> np.ndarray:
        return expand_active_sets(self._real_ptrs, active_sets)

    def __call__(self, src) -> jax.Array:
        import time

        bd = self.bd
        self.trace = []
        state = init_state(bd, src)
        n_visited = 1
        while True:
            f_words = np.asarray(state.f_words)
            active_sets = f_words[: bd.num_sets] != 0
            qids = self._expand_queue(active_sets)
            if qids.size == 0:
                break
            unvisited = bd.n - n_visited
            use_dense = (
                self.eta is not None and unvisited < self.eta * qids.size
            ) or qids.size >= bd.num_vss
            t0 = time.perf_counter()
            if use_dense:
                state = self._dense_level(state)
            else:
                bs = _bucket_size(qids.size)
                padded = np.full(bs, self._pad_vss, np.int32)
                padded[: qids.size] = qids
                state = self._queued_level(state, jnp.asarray(padded))
            if self.instrument:
                jax.block_until_ready(state.v)
                self.trace.append({
                    "level": int(state.ell) - 1,
                    "mode": "dense" if use_dense else "queued",
                    "queue": int(qids.size),
                    "unvisited": int(unvisited),
                    "time_s": time.perf_counter() - t0,
                })
            n_visited = int(np.asarray(state.v[: bd.n_pad]).sum())
        return state.level[: bd.n]
