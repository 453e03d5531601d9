"""Multi-pod distribution of BLEST workloads (paper §7's 100-GPU closeness
run, re-expressed with shard_map on a (pod, data, model) TPU mesh).

Three modes:

* **Source-parallel closeness** (paper-faithful): the ceil(n/kappa) source
  batches are partitioned over the ('pod','data') axes — exactly the MPI
  partitioning of the paper's com-Friendster run — each shard runs MS-BFS on
  its (replicated) BVSS copy, and the per-vertex ``far`` partial sums are
  reduced once at the end (`psum`).  Embarrassingly parallel; one all-reduce
  of n int32 words total.

* **Graph-parallel BFS, replicated-V** (baseline): VSS ranges sharded over
  'model'; every device scatters into a replicated visited vector and the
  per-level frontier is combined with an OR-all-reduce (`pmax` over {0,1}
  bytes, ~2n bytes/device/level on a ring).  Simple, but collective-bound.

* **Graph-parallel BFS, row-partitioned** (beyond-paper, §Perf): slices are
  partitioned by *row range*, so every scatter is shard-local and the only
  exchange is an all-gather of the sigma-bit frontier words — n/8 bytes per
  level, a 16x collective-payload reduction over the replicated-V baseline.
  This exploits a BVSS property the paper doesn't use: a vertex's frontier
  bit lives in slice set u//sigma, so a row range *is* a slice-set range,
  and the stage-2 sweep already produces the packed words the collective
  needs — the all-gather payload is literally the F_curr^sigma array.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import msbfs
from repro.core.bvss import Bvss
from repro.core.blest import BvssDevice, UNREACHED, init_state
from repro.kernels import ops


# ---------------------------------------------------------------------------
# Source-parallel exact closeness (paper-faithful distribution)
# ---------------------------------------------------------------------------


def closeness_source_parallel(
    bd: BvssDevice,
    mesh: Mesh,
    source_axes: tuple[str, ...] = ("data",),
    kappa: int = 128,
    sources: np.ndarray | None = None,
    use_pallas: bool = True,
):
    """Exact closeness with sources partitioned over ``source_axes``.

    Returns (far, reach) as host int64 arrays of length bd.n.
    """
    n_shards = int(np.prod([mesh.shape[a] for a in source_axes]))
    if sources is None:
        sources = np.arange(bd.n, dtype=np.int32)
    per_shard = -(-len(sources) // n_shards)
    per_shard = -(-per_shard // kappa) * kappa  # round to whole kappa batches
    padded = np.full(n_shards * per_shard, -1, np.int32)
    padded[: len(sources)] = sources

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(source_axes),), out_specs=(P(), P()),
        check_vma=False,
    )
    def run(srcs_shard):
        far = jnp.zeros(bd.n_ext, jnp.int32)
        reach = jnp.zeros(bd.n_ext, jnp.int32)

        def batch_body(i, acc):
            far, reach = acc
            batch = jax.lax.dynamic_slice(srcs_shard, (i * kappa,), (kappa,))
            st = msbfs.msbfs_fused(bd, batch, use_pallas=use_pallas)
            return far + st.far, reach + st.reach

        far, reach = jax.lax.fori_loop(
            0, per_shard // kappa, batch_body, (far, reach))
        # the paper's final MPI reduction == one psum over the source axes
        return (jax.lax.psum(far, source_axes),
                jax.lax.psum(reach, source_axes))

    far, reach = run(jnp.asarray(padded))
    return (np.asarray(far)[: bd.n].astype(np.int64),
            np.asarray(reach)[: bd.n].astype(np.int64))


def closeness_from_far(n: int, far: np.ndarray, reach: np.ndarray,
                       normalize: str = "classic") -> np.ndarray:
    with np.errstate(divide="ignore", invalid="ignore"):
        if normalize == "component":
            return np.where(far > 0, (reach - 1) ** 2 / ((n - 1) * far), 0.0)
        return np.where(far > 0, (n - 1) / far, 0.0)


# ---------------------------------------------------------------------------
# Graph-parallel BFS — replicated-V baseline (OR-all-reduce of visited bytes)
# ---------------------------------------------------------------------------


def _pad_vss_dim(bd: BvssDevice, n_shards: int):
    nv = bd.num_vss_pad
    target = -(-nv // n_shards) * n_shards
    pad = target - nv
    masks = jnp.pad(bd.masks, ((0, pad), (0, 0)))
    row_ids = jnp.pad(bd.row_ids, ((0, pad), (0, 0)),
                      constant_values=bd.n_pad)
    v2r = jnp.pad(bd.v2r, (0, pad), constant_values=bd.num_sets)
    return masks, row_ids, v2r


def bfs_graph_parallel(
    bd: BvssDevice,
    src: int,
    mesh: Mesh,
    axis: str = "model",
    use_pallas: bool = True,
    max_levels: int | None = None,
) -> np.ndarray:
    """Replicated-V graph-parallel BFS: per level, each shard pulls marks for
    its VSS shard, scatters into its visited replica, and the replicas are
    OR-combined with pmax over {0,1} bytes (correct: max == OR elementwise).
    """
    n_shards = mesh.shape[axis]
    masks, row_ids, v2r = _pad_vss_dim(bd, n_shards)
    max_lv = bd.n_ext if max_levels is None else max_levels

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    def run(masks_l, rows_l, v2r_l, src_arr):
        state = init_state(bd, src_arr[0])

        def cond(state):
            return jnp.logical_and((state.f_words != 0).any(),
                                   state.ell <= max_lv)

        def body(state):
            alphas = state.f_words[v2r_l]
            marks = ops.pull_ss(masks_l, alphas, use_pallas=use_pallas)
            v_next = state.v.at[rows_l.ravel()].max(marks.ravel())
            # frontier exchange: elementwise OR across shards (bytes in {0,1})
            v_next = jax.lax.pmax(v_next, axis)
            v_new, level_new, f_words, _ = ops.frontier_sweep(
                state.v, v_next, state.level, state.ell, sigma=bd.sigma,
                use_pallas=use_pallas)
            return type(state)(v_new, level_new, f_words, state.ell + 1)

        final = jax.lax.while_loop(cond, body, state)
        return final.level[: bd.n]

    return np.asarray(run(masks, row_ids, v2r,
                          jnp.asarray([src], jnp.int32)))


# ---------------------------------------------------------------------------
# Graph-parallel BFS — row-partitioned (all-gather of frontier words only)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RowShardedBvss:
    """Per-shard sub-BVSS: shard k owns slices whose row id falls in
    [k*rows_per, (k+1)*rows_per).  Scatters are shard-local; the frontier
    words are the only cross-shard state."""

    n: int
    n_pad: int            # global padded vertex count, divisible by P*sigma
    rows_per: int         # vertices per shard
    num_sets: int         # global slice sets (n_pad // sigma)
    sets_per: int         # slice sets per shard (rows_per // sigma)
    nv_max: int           # per-shard VSS count (padded to the max shard)
    sigma: int
    tau: int
    masks: jax.Array      # (P, nv_max, tau) uint8
    row_ids: jax.Array    # (P, nv_max, tau) int32 — LOCAL row ids
    v2r: jax.Array        # (P, nv_max) int32 — GLOBAL slice-set ids
    n_shards: int

    @property
    def shard_bytes(self) -> int:
        """Substrate bytes **one** shard holds resident (its slice of
        masks/row_ids/v2r) — what mesh serving charges that shard's
        device in the per-device cache accounting (DESIGN.md §17.3).
        Shards are padded to the largest one (``nv_max``), so this is
        exact for every shard, not an average."""
        per = self.nv_max * self.tau        # masks uint8
        per += self.nv_max * self.tau * 4   # row_ids int32
        per += self.nv_max * 4              # v2r int32
        return int(per)


def build_row_sharded(b: Bvss, n_shards: int) -> RowShardedBvss:
    """Host-side re-bucketing of BVSS slices by row range."""
    sigma, tau = b.config.sigma, b.config.tau
    n_pad = -(-b.n_pad // (n_shards * sigma)) * (n_shards * sigma)
    rows_per = n_pad // n_shards
    num_sets = n_pad // sigma

    # flatten real slices
    nz = b.masks[: b.num_vss] != 0
    sets = np.repeat(b.virtual_to_real, tau).reshape(b.num_vss, tau)[nz]
    masks = b.masks[: b.num_vss][nz]
    rows = b.row_ids[: b.num_vss][nz]
    shard = rows // rows_per

    per_shard_arrays = []
    nvs = []
    for k in range(n_shards):
        sel = shard == k
        s_k, m_k, r_k = sets[sel], masks[sel], rows[sel] - k * rows_per
        # regroup into VSSs of tau slices per (global) slice set
        order = np.argsort(s_k, kind="stable")
        s_k, m_k, r_k = s_k[order], m_k[order], r_k[order]
        counts = np.bincount(s_k, minlength=num_sets)
        vss_per = (counts + tau - 1) // tau
        rp = np.zeros(num_sets + 1, np.int64)
        np.cumsum(vss_per, out=rp[1:])
        nv = int(rp[-1])
        mk = np.zeros((max(nv, 1), tau), np.uint8)
        rk = np.full((max(nv, 1), tau), rows_per, np.int32)  # local sentinel
        v2r = np.repeat(np.arange(num_sets, dtype=np.int32), vss_per)
        starts = np.zeros(num_sets + 1, np.int64)
        np.cumsum(counts, out=starts[1:])
        pos = np.arange(len(s_k)) - starts[s_k]
        vi = rp[s_k] + pos // tau
        sl = pos % tau
        mk[vi, sl] = m_k
        rk[vi, sl] = r_k
        per_shard_arrays.append((mk, rk, v2r))
        nvs.append(max(nv, 1))

    nv_max = max(max(nvs), 1)
    M = np.zeros((n_shards, nv_max, tau), np.uint8)
    R = np.full((n_shards, nv_max, tau), rows_per, np.int32)
    V = np.full((n_shards, nv_max), num_sets, np.int32)  # sentinel set
    for k, (mk, rk, v2r) in enumerate(per_shard_arrays):
        M[k, : mk.shape[0]] = mk
        R[k, : rk.shape[0]] = rk
        V[k, : v2r.shape[0]] = v2r
    return RowShardedBvss(
        n=b.n, n_pad=n_pad, rows_per=rows_per, num_sets=num_sets,
        sets_per=rows_per // sigma, nv_max=nv_max, sigma=sigma, tau=tau,
        masks=jnp.asarray(M), row_ids=jnp.asarray(R), v2r=jnp.asarray(V),
        n_shards=n_shards,
    )


def bfs_row_parallel(
    rs: RowShardedBvss,
    src: int,
    mesh: Mesh,
    axis: str = "model",
    use_pallas: bool = True,
    max_levels: int | None = None,
) -> np.ndarray:
    """Row-partitioned BFS: the only per-level collective is an all-gather of
    the sigma-bit frontier words (n/8 bytes globally).  Visited state and
    level arrays never leave their shard."""
    sigma = rs.sigma
    max_lv = rs.n_pad + 1 if max_levels is None else max_levels
    n_local = rs.rows_per + sigma  # + sentinel slot range

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=P(axis),
        check_vma=False,
    )
    def run(masks_s, rows_s, v2r_s, src_arr):
        masks_l = masks_s[0]
        rows_l = rows_s[0]
        v2r_l = v2r_s[0]
        src = src_arr[0]
        k = jax.lax.axis_index(axis)
        row0 = k * rs.rows_per
        local_src = src - row0
        own = jnp.logical_and(local_src >= 0, local_src < rs.rows_per)
        safe = jnp.where(own, local_src, rs.rows_per)  # sentinel slot
        v = jnp.zeros(n_local, jnp.uint8).at[safe].set(
            own.astype(jnp.uint8))
        level = jnp.full(n_local, UNREACHED, jnp.int32).at[safe].set(
            jnp.where(own, 0, UNREACHED))
        # global frontier words: every shard derives them identically
        f_all = jnp.zeros(rs.num_sets + 1, jnp.uint8).at[src // sigma].set(
            jnp.uint8(1) << (src % sigma).astype(jnp.uint8))

        def cond(carry):
            v, level, f_all, ell = carry
            return jnp.logical_and((f_all != 0).any(), ell <= max_lv)

        def body(carry):
            v, level, f_all, ell = carry
            alphas = f_all[v2r_l]
            marks = ops.pull_ss(masks_l, alphas, use_pallas=use_pallas)
            v_next = v.at[rows_l.ravel()].max(marks.ravel())
            v_new, level_new, f_local, _ = ops.frontier_sweep(
                v, v_next, level, ell, sigma=sigma, use_pallas=use_pallas)
            f_mine = f_local[: rs.sets_per]  # drop the sentinel-slot words
            # THE collective: n/8 bytes of frontier words, concatenated in
            # shard order == global slice-set order.
            f_gathered = jax.lax.all_gather(f_mine, axis, tiled=True)
            f_next = jnp.concatenate(
                [f_gathered, jnp.zeros(1, jnp.uint8)])  # sentinel set word
            return v_new, level_new, f_next, ell + 1

        v, level, f_all, ell = jax.lax.while_loop(
            cond, body, (v, level, f_all, jnp.int32(1)))
        return level[: rs.rows_per]

    lv = run(rs.masks, rs.row_ids, rs.v2r, jnp.asarray([src], jnp.int32))
    return np.asarray(lv)[: rs.n]
