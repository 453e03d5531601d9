"""Mesh serving (DESIGN.md §17): placement and sharding under the engine.

The single-device engine tops out at one device's FLOPs and bytes; the
paper's headline run is exact closeness on a 3.6B-edge graph across 100
GPUs.  This module is the placement-and-sharding layer that closes that
gap for the serving path, in two modes selected *per graph* at build
time:

* **Source-parallel** (§17.1): a graph whose artifact fits one device is
  replicated across a device group, and the engine runs one
  :class:`~repro.serve.bfs_engine._GraphSession` per replica off the
  shared queue — ``kappa x n_devices`` lanes in flight per graph.  Lanes
  never interact across replicas (bitwise lane independence holds per
  device), so early-exit, cancellation reclaim, and watched-target
  machinery all run unchanged per replica, and window results merge on
  the engine thread simply by each replica extracting its own lanes.

* **Graph-parallel** (§17.2): a graph whose projected artifact exceeds
  the per-device byte budget is admitted anyway, by building a
  row-range-sharded VSS artifact (``core/distributed.build_row_sharded``
  — scatters are shard-local by construction) and running every dense
  sweep as one ``shard_map`` dispatch over the group.  The only
  cross-shard state is the sigma-bit frontier planes: each level
  all-gathers ``diff`` tiles (shard order == global slice-set order) and
  ``psum``s the per-lane new counts, so the engine-facing contract —
  ``(state', new_per_lane)`` — is identical to the single-device runner.
  Megatick windows run the whole ``lax.while_loop`` *inside* the
  ``shard_map`` body: the loop condition depends only on replicated
  values (psum'd counts), so every shard takes identical trips and the
  window is one dispatch.  Sharded sessions force the Eq. (6) policy off
  (``supports_policy = False``): the queued sweep's bucketed host
  machinery is per-device by design and dense sweeps are the regime
  sharding targets.

The cache/scheduler integration (§17.3) lives in ``bfs_engine``:
``BfsEngine(mesh=EngineMesh(...), device_budget=...)`` routes builds
through :func:`build_mesh_artifacts`, pins sessions to the placement
recorded in the artifact, accounts cache bytes per device, and reports
per-device queue depth and byte occupancy through ``engine.health()``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import blest, reorder as reorder_mod
from repro.core.blest import UNREACHED
from repro.core.bvss import Bvss, BvssConfig, build_bvss
from repro.core.distributed import RowShardedBvss, build_row_sharded
from repro.core.msbfs_packed import unpack_levels_check
from repro.kernels.pull_scatter_ms_packed import pull_scatter_ms_packed_ref
from repro.serve import lifecycle as lifecycle_mod

AXIS = "d"  # the one mesh axis mesh serving shards over


class OversizedGraphError(lifecycle_mod.PermanentBuildError):
    """The graph's projected artifact exceeds the per-device byte budget
    and no device group is available to shard it over.  Permanent: an
    identical retry cannot help, so tickets FAIL fast (§16.3)."""


# ---------------------------------------------------------------------------
# Device groups
# ---------------------------------------------------------------------------


class EngineMesh:
    """A set of devices partitioned into equal placement groups.

    ``group_size`` defaults to all devices: one group, every graph
    either replicated across it (source-parallel) or sharded over it
    (graph-parallel).  Smaller groups let the engine place different
    graphs on disjoint device sets (§17.3 least-loaded placement)."""

    def __init__(self, devices=None, group_size: int | None = None):
        self.devices = tuple(devices) if devices is not None \
            else tuple(jax.devices())
        if not self.devices:
            raise ValueError("EngineMesh needs at least one device")
        gs = len(self.devices) if group_size is None else int(group_size)
        if gs < 1 or len(self.devices) % gs != 0:
            raise ValueError(
                f"group_size {gs} must divide the device count "
                f"{len(self.devices)}")
        self.group_size = gs
        self.groups = tuple(tuple(self.devices[i:i + gs])
                            for i in range(0, len(self.devices), gs))

    @classmethod
    def of(cls, mesh: "EngineMesh | int | None") -> "EngineMesh | None":
        """The engine's ``mesh`` argument as a mesh: ``None`` and an
        ``EngineMesh`` as given, an int ``n`` as one group of the first
        ``n`` visible devices, or of all of them where fewer are visible
        (one device is the degenerate mesh: it serves as a single device).
        ``launch/serve_bfs.py --mesh --devices N`` resolves through here."""
        if mesh is None or isinstance(mesh, cls):
            return mesh
        if isinstance(mesh, bool) or not isinstance(mesh, int) or mesh < 1:
            raise ValueError(
                f"mesh must be an EngineMesh or a device count >= 1, "
                f"got {mesh!r}")
        return cls(jax.devices()[:mesh])

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def device_ids(self) -> list[int]:
        return [int(d.id) for d in self.devices]

    def __repr__(self):
        return (f"EngineMesh({self.n_devices} devices, "
                f"{len(self.groups)} group(s) of {self.group_size})")


# ---------------------------------------------------------------------------
# Byte projection + artifact builds
# ---------------------------------------------------------------------------


def projected_device_bytes(b: Bvss) -> int:
    """What ``blest.to_device(b)`` will put on one device, computed on
    host *before* any transfer — the §17.2 admission decision must not
    allocate the thing it is deciding whether to allocate."""
    sigma, tau = b.config.sigma, b.config.tau
    del sigma
    nvp = ((b.num_vss + blest.VSS_PAD) // blest.VSS_PAD) * blest.VSS_PAD
    total = nvp * tau          # masks uint8
    total += nvp * tau * 4     # row_ids int32
    total += nvp * 4           # v2r int32
    total += (b.num_sets + 1) * 4  # real_ptrs int32
    if tau % 4 == 0:
        total += nvp * tau     # masks_packed uint32: nvp * (tau//4) * 4
    return int(total)


def _replicate_bd(bd: blest.BvssDevice, device) -> blest.BvssDevice:
    """One replica of the device substrate on ``device``: ``bd`` itself
    where it already lives there (no second copy), else a copy whose
    masks/masks_packed aliasing (tau % 4 != 0) is preserved so the
    replica costs what the original did."""
    if bd.row_ids.devices() == {device}:
        return bd
    masks = jax.device_put(bd.masks, device)
    return dataclasses.replace(
        bd,
        masks=masks,
        masks_packed=(masks if bd.masks_packed is bd.masks
                      else jax.device_put(bd.masks_packed, device)),
        row_ids=jax.device_put(bd.row_ids, device),
        v2r=jax.device_put(bd.v2r, device),
        real_ptrs=jax.device_put(bd.real_ptrs, device),
    )


@dataclasses.dataclass(frozen=True)
class ShardBd:
    """The scalar face of a sharded substrate: what sessions and the
    engine read off ``art.bd`` (``n_ext`` bounds the level loop, the
    rest is bookkeeping).  The arrays live in :class:`ShardedGraph`."""

    n: int
    n_pad: int
    n_ext: int
    num_sets: int
    num_sets_ext: int
    num_vss: int
    num_vss_pad: int
    sigma: int
    tau: int


@dataclasses.dataclass(frozen=True)
class ShardedGraph:
    """Row-range-sharded substrate placed on a device group: the
    :class:`RowShardedBvss` arrays carry a ``NamedSharding`` over the
    group's one-axis mesh, so every ``shard_map`` dispatch runs without
    input resharding."""

    rs: RowShardedBvss
    mesh: Mesh

    @property
    def n_shards(self) -> int:
        return self.rs.n_shards


def _shard_sharded_arrays(rs: RowShardedBvss, mesh: Mesh) -> RowShardedBvss:
    spec = NamedSharding(mesh, PartitionSpec(AXIS))
    return dataclasses.replace(
        rs,
        masks=jax.device_put(rs.masks, spec),
        row_ids=jax.device_put(rs.row_ids, spec),
        v2r=jax.device_put(rs.v2r, spec),
    )


def build_mesh_artifacts(name, g, *, group=None, reorder=None, config=None,
                         probe=False, eta=None, probe_use_pallas=False,
                         probe_runner=None, device_budget=None,
                         fault_hook=None):
    """Mesh-aware artifact build (§17.1/§17.2): project the device bytes
    on host, then either build a plain artifact (optionally replicated
    across ``group`` for source-parallel serving) or — over
    ``device_budget`` — a row-sharded one spanning the group.  With no
    group to shard over, an over-budget graph raises
    :class:`OversizedGraphError` (a permanent build failure: the
    single-device engine must reject what it cannot hold).

    ``fault_hook`` is called once per shard/replica with
    ``"{name}#shard{k}"`` / ``"{name}#replica{k}"`` so the §14 injection
    harness and §16.3 retry/quarantine machinery cover per-shard build
    failures (a transient fault in one shard retries the whole placement
    — shards of one graph are never mixed across build attempts)."""
    from repro.serve import bfs_engine as eng_mod

    config = config or BvssConfig()
    t0 = time.perf_counter()
    rr = reorder_mod.reorder(g, sigma=config.sigma, force=reorder)
    gp = g.permuted(rr.perm)
    b = build_bvss(gp, config)
    projected = projected_device_bytes(b)
    host_s = time.perf_counter() - t0  # build_s counts it, as off the mesh

    if device_budget is not None and projected > device_budget:
        if group is None or len(group) < 2:
            raise OversizedGraphError(
                f"graph {name!r}: projected artifact {projected} B exceeds "
                f"the per-device byte budget {device_budget} B and no "
                f"device group is available to shard it over")
        art = _build_sharded(eng_mod, name, g, b, rr, group, fault_hook)
        art.build_s = time.perf_counter() - t0
        return art

    kw = dict(reorder=reorder, config=config, probe=probe,
              probe_use_pallas=probe_use_pallas, probe_runner=probe_runner,
              prebuilt=(rr, b))
    if eta is not None:
        kw["eta"] = eta
    art = eng_mod.build_artifacts(name, g, **kw)
    t0 = time.perf_counter()
    if group is not None and len(group) > 1:
        replicas = []
        for k, dev in enumerate(group):
            if fault_hook is not None:
                fault_hook(f"{name}#replica{k}")
            replicas.append(_replicate_bd(art.bd, dev))
        art.replicas = replicas
        art.placement = tuple(int(d.id) for d in group)
        art.per_device_bytes = {int(d.id): art.device_bytes for d in group}
    art.build_s += host_s + time.perf_counter() - t0
    return art


def _build_sharded(eng_mod, name, g, b, rr, group, fault_hook):
    n_shards = len(group)
    for k in range(n_shards):
        if fault_hook is not None:
            fault_hook(f"{name}#shard{k}")
    rs = build_row_sharded(b, n_shards)
    mesh = Mesh(np.array(group), (AXIS,))
    rs = _shard_sharded_arrays(rs, mesh)
    per_shard = rs.shard_bytes
    perm = np.asarray(rr.perm)
    bd = ShardBd(
        n=b.n, n_pad=rs.n_pad, n_ext=rs.n_pad + rs.sigma,
        num_sets=rs.num_sets, num_sets_ext=rs.num_sets + 1,
        num_vss=b.num_vss, num_vss_pad=rs.nv_max * n_shards,
        sigma=rs.sigma, tau=rs.tau)
    return eng_mod.GraphArtifacts(
        name=name, graph=g, bvss=b, bd=bd, perm=perm, reorder=rr,
        switching=None,  # sharded sessions run policy-off (§17.2)
        device_bytes=per_shard * n_shards, aux_bytes=int(perm.nbytes),
        sharded=ShardedGraph(rs=rs, mesh=mesh),
        placement=tuple(int(d.id) for d in group),
        per_device_bytes={int(d.id): per_shard for d in group})


# ---------------------------------------------------------------------------
# Graph-parallel lane runner: one shard_map dispatch per level / window
# ---------------------------------------------------------------------------


class ShardLaneState(NamedTuple):
    """Sharded mirror of ``LaneState``: ``v``/``levels`` carry a leading
    shard axis (shard-local rows + the per-shard sentinel slot range);
    ``f`` is the replicated global frontier-plane array — the only
    cross-shard state, exactly the §8 row-partitioned property."""

    v: jax.Array       # (P, rows_per + sigma, kw|kappa) visited
    f: jax.Array       # (num_sets + 1, sigma, kw|kappa) frontier planes
    levels: jax.Array  # (P, rows_per + sigma, kappa) int32


class ShardedLaneRunner:
    """kappa MS-BFS lanes over a row-sharded substrate; drop-in for
    :class:`~repro.serve.bfs_engine._LaneRunner` on the dense path.

    Every step is one jitted ``shard_map`` dispatch over the group's
    mesh.  Per level each shard pulls marks from its local VSSs against
    the replicated frontier planes, scatters shard-locally (the §8
    row-range property: a slice's rows never leave its shard), stamps
    its local level rows, then contributes ``diff`` tiles to the
    all-gather that rebuilds the global planes and a ``psum`` that
    rebuilds the per-lane new counts.  ``reseed`` masks the seed scatter
    by row ownership so exactly one shard seeds each lane's source while
    every shard derives the identical replicated frontier.

    The Eq. (6) queued machinery is host-bucketed and per-device by
    design, so sharded sessions run policy-off (``supports_policy``
    gates it in ``_GraphSession``)."""

    supports_policy = False
    use_pallas = False
    _tiles = None

    def __init__(self, sg: ShardedGraph, bd: ShardBd, kappa: int, *,
                 layout: str = "auto"):
        if kappa % 32 != 0:
            raise ValueError("kappa must be a multiple of 32 (packed words)")
        if layout == "auto":
            layout = "packed" if jax.default_backend() == "tpu" \
                else "byteplane"
        if layout not in ("packed", "byteplane"):
            raise ValueError(
                f"sharded serving runs on the base substrates "
                f"(packed/byteplane), not {layout!r}")
        self.sg = sg
        self.rs = sg.rs
        self.mesh = sg.mesh
        self.bd = bd
        self.kappa = kappa
        self.kw = kappa // 32
        self.layout = layout
        self.substrate = layout
        self._packed = layout == "packed"
        self._width = self.kw if self._packed else kappa
        self._n_local = self.rs.rows_per + self.rs.sigma
        self._init_state: ShardLaneState | None = None
        self._mega_fns: dict[int, object] = {}

        shard = PartitionSpec(AXIS)
        repl = PartitionSpec()
        sm = functools.partial(jax.shard_map, mesh=self.mesh, check_vma=False)
        self._level_fn = jax.jit(sm(
            self._level_shard,
            in_specs=(shard, repl, shard, shard, shard, shard, repl),
            out_specs=(shard, repl, shard, repl)))
        self._reseed_fn = jax.jit(sm(
            self._reseed_shard,
            in_specs=(shard, repl, shard, repl, repl, repl),
            out_specs=(shard, repl, shard)))

    def put(self, x) -> jax.Array:
        """A small host array for the session (the state spans the group)."""
        return jnp.asarray(x)

    # ---- state ------------------------------------------------------------
    def init_state(self) -> ShardLaneState:
        if self._init_state is None:
            rs = self.rs
            shard = NamedSharding(self.mesh, PartitionSpec(AXIS))
            repl = NamedSharding(self.mesh, PartitionSpec())
            dt = np.uint32 if self._packed else np.uint8
            v = np.zeros((rs.n_shards, self._n_local, self._width), dt)
            f = np.zeros((rs.num_sets + 1, rs.sigma, self._width), dt)
            levels = np.full((rs.n_shards, self._n_local, self.kappa),
                             UNREACHED, np.int32)
            self._init_state = ShardLaneState(
                v=jax.device_put(v, shard),
                f=jax.device_put(f, repl),
                levels=jax.device_put(levels, shard))
        return self._init_state

    # ---- one level, per shard ---------------------------------------------
    def _pull_local(self, v_l, f, masks_l, rows_l, v2r_l):
        """Shard-local pull+scatter against the replicated planes.  The
        global-set ``v2r`` sentinel (num_sets) indexes the zero sentinel
        planes; the local row sentinel (rows_per) lands in the sentinel
        slot range of ``v_l`` — both exactly the single-device idiom."""
        rs = self.rs
        if self._packed:
            return pull_scatter_ms_packed_ref(
                v_l, masks_l, f, v2r_l, rows_l.reshape(-1), sigma=rs.sigma)
        ft = f[v2r_l]  # (nv, sigma, kappa) uint8 planes
        marks = jnp.zeros((masks_l.shape[0], rs.tau, self.kappa), jnp.uint8)
        for b in range(rs.sigma):
            sel = ((masks_l >> b) & 1)[:, :, None]
            marks = marks | (sel * ft[:, b][:, None, :])
        return v_l.at[rows_l.reshape(-1)].max(marks.reshape(-1, self.kappa))

    def _level_local(self, v_l, f, lv_l, masks_l, rows_l, v2r_l, ell):
        """One dense level on one shard: local pull/scatter/stamp, then
        the two collectives (frontier all-gather + new-count psum)."""
        rs = self.rs
        v_next = self._pull_local(v_l, f, masks_l, rows_l, v2r_l)
        diff = (v_next & ~v_l) if self._packed else (v_next & (1 - v_l))
        if self._packed:
            bits = unpack_levels_check(diff, self.kappa).astype(jnp.int32)
        else:
            bits = diff.astype(jnp.int32)
        new_lane = jax.lax.psum(bits[: rs.rows_per].sum(axis=0), AXIS)
        lv_next = jnp.where(bits == 1, ell, lv_l)
        # THE collective (§8): shard order == global slice-set order, so
        # the tiled all-gather of diff tiles is the global plane array
        f_mine = diff[: rs.rows_per].reshape(rs.sets_per, rs.sigma, -1)
        f_all = jax.lax.all_gather(f_mine, AXIS, tiled=True)
        f_next = jnp.concatenate(
            [f_all, jnp.zeros((1,) + f_all.shape[1:], f_all.dtype)])
        return v_next, f_next, lv_next, new_lane

    def _level_shard(self, v, f, levels, masks, rows, v2r, ell):
        v_next, f_next, lv_next, new_lane = self._level_local(
            v[0], f, levels[0], masks[0], rows[0], v2r[0], ell)
        return v_next[None], f_next, lv_next[None], new_lane

    def level(self, state: ShardLaneState, ell: int):
        rs = self.rs
        v, f, lv, new_lane = self._level_fn(
            state.v, state.f, state.levels,
            rs.masks, rs.row_ids, rs.v2r, jnp.int32(ell))
        return ShardLaneState(v=v, f=f, levels=lv), new_lane

    # ---- megatick: the whole window inside one shard_map body (§17.2) -----
    def megatick(self, state: ShardLaneState, reach, ell0: int,
                 active, admitted_at, eta: float, *, ticks: int,
                 policy_on: bool):
        """Up to ``ticks`` fused dense levels in one dispatch; same
        contract as the single-device runner (hist rows of -1 mark
        unexecuted ticks).  ``reach``/``eta``/``policy_on`` are unused:
        sharded sessions run policy-off, so the loop condition depends
        only on replicated values and every shard takes identical
        trips."""
        del reach, eta, policy_on
        fn = self._mega_fns.get(int(ticks))
        if fn is None:
            shard = PartitionSpec(AXIS)
            repl = PartitionSpec()
            fn = jax.jit(functools.partial(
                jax.shard_map, mesh=self.mesh, check_vma=False)(
                functools.partial(self._megatick_shard, T=int(ticks)),
                in_specs=(shard, repl, shard, shard, shard, shard,
                          repl, repl, repl),
                out_specs=(shard, repl, shard, repl)))
            self._mega_fns[int(ticks)] = fn
        rs = self.rs
        v, f, lv, hist = fn(state.v, state.f, state.levels,
                            rs.masks, rs.row_ids, rs.v2r, jnp.int32(ell0),
                            jnp.asarray(active, bool),
                            jnp.asarray(admitted_at, jnp.int32))
        return ShardLaneState(v=v, f=f, levels=lv), hist

    def _megatick_shard(self, v, f, levels, masks, rows, v2r, ell0,
                        active, admitted_at, *, T: int):
        masks_l, rows_l, v2r_l = masks[0], rows[0], v2r[0]
        n_ext = self.bd.n_ext

        def cond(carry):
            _v, _f, _lv, tick, done, _hist = carry
            return (tick < T) & (active & ~done).any()

        def body(carry):
            v_l, f, lv_l, tick, done, hist = carry
            ell = ell0 + tick + 1
            v_l, f, lv_l, new_lane = self._level_local(
                v_l, f, lv_l, masks_l, rows_l, v2r_l, ell)
            done = done | (active & ((new_lane == 0)
                                     | (ell - admitted_at >= n_ext)))
            return (v_l, f, lv_l, tick + 1, done,
                    hist.at[tick].set(new_lane))

        hist0 = jnp.full((T, self.kappa), -1, jnp.int32)
        done0 = jnp.zeros(self.kappa, bool)
        v_l, f, lv_l, _t, _d, hist = jax.lax.while_loop(
            cond, body, (v[0], f, levels[0], jnp.int32(0), done0, hist0))
        return v_l[None], f, lv_l[None], hist

    # ---- clear + seed a subset of lanes ------------------------------------
    def _reseed_shard(self, v, f, levels, clear, new_src, ell):
        """Ownership-masked reseed: the shard owning ``src``'s row seeds
        its visited/level slot (others write the sentinel slot with a
        zero/identity value); the replicated frontier planes are seeded
        identically on every shard from the global source id."""
        rs, kappa = self.rs, self.kappa
        v_l, lv_l = v[0], levels[0]
        row0 = jax.lax.axis_index(AXIS) * rs.rows_per
        lanes = jnp.arange(kappa)
        has = new_src >= 0
        src = jnp.where(has, new_src, 0)
        lsrc = src - row0
        own = has & (lsrc >= 0) & (lsrc < rs.rows_per)
        safe = jnp.where(own, lsrc, rs.rows_per)  # per-shard sentinel slot
        if self._packed:
            word_mask = _lane_word_mask(clear, self.kw)
            v_l = v_l & ~word_mask[None, :]
            f = f & ~word_mask[None, None, :]
            shift = (lanes % 32).astype(jnp.uint32)
            # cleared bits are 0 and lane bit positions are distinct, so
            # scatter-add == scatter-OR (the single-device reseed idiom)
            v_l = v_l.at[safe, lanes // 32].add(own.astype(jnp.uint32)
                                                << shift)
            f = f.at[src // rs.sigma, src % rs.sigma, lanes // 32].add(
                has.astype(jnp.uint32) << shift)
        else:
            keep = (1 - clear.astype(jnp.uint8))[None, :]
            v_l = v_l * keep
            f = f * keep[None]
            v_l = v_l.at[safe, lanes].max(own.astype(jnp.uint8))
            f = f.at[src // rs.sigma, src % rs.sigma, lanes].max(
                has.astype(jnp.uint8))
        lv_l = jnp.where(clear[None, :], UNREACHED, lv_l)
        lv_l = lv_l.at[safe, lanes].set(
            jnp.where(own, ell, lv_l[safe, lanes]))
        return v_l[None], f, lv_l[None]

    def reseed(self, state: ShardLaneState, clear, new_src, ell):
        v, f, lv = self._reseed_fn(
            state.v, state.f, state.levels, jnp.asarray(clear, bool),
            jnp.asarray(new_src, jnp.int32), jnp.int32(ell))
        return ShardLaneState(v=v, f=f, levels=lv)

    # ---- host-facing gathers ----------------------------------------------
    def active_set_mask(self, f) -> np.ndarray:
        return np.asarray((np.asarray(f) != 0).any(axis=(1, 2)))[
            : self.rs.num_sets]

    def queue_len(self, active_mask):
        raise NotImplementedError("sharded sessions run policy-off (§17.2)")

    def active_vss(self, active_mask):
        raise NotImplementedError("sharded sessions run policy-off (§17.2)")

    def bucket_qids(self, qids):
        raise NotImplementedError("sharded sessions run policy-off (§17.2)")

    def watch_levels(self, levels, ids_dev) -> np.ndarray:
        ids = np.asarray(ids_dev)
        arr = np.asarray(levels)
        return arr[ids // self.rs.rows_per, ids % self.rs.rows_per,
                   np.arange(self.kappa)]

    def gather_level_cols(self, levels, cols) -> np.ndarray:
        arr = np.asarray(levels)[:, : self.rs.rows_per, :]
        arr = arr.reshape(-1, self.kappa)  # shard-major == global row order
        return arr[: self.bd.n][:, list(cols)]


def _lane_word_mask(clear, kw):
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = clear.astype(jnp.uint32).reshape(kw, 32) << shifts
    return bits.sum(axis=1).astype(jnp.uint32)  # distinct bits: sum == OR


# ---------------------------------------------------------------------------
# Source-parallel session group
# ---------------------------------------------------------------------------


class _MeshSessionGroup:
    """kappa x n_devices lanes per graph (§17.1): one per-replica
    ``_GraphSession`` per device in the placement group, all fed from
    the shared tenant queue.  Presents the session surface the engine
    touches (``tick``/``idle``/``in_flight``/``lanes``/``art``/
    ``queue``), merging nothing: replica lanes are disjoint, each
    session extracts and delivers its own at its own window boundaries
    on the engine thread."""

    def __init__(self, engine, name, queue, art):
        from repro.serve.bfs_engine import _GraphSession

        self.engine = engine
        self.name = name
        self.queue = queue
        self.art = art
        runners = engine._mesh_runners_for(art)
        self.replicas = [_GraphSession(engine, name, queue, art, runner=r,
                                       replica=k)
                         for k, r in enumerate(runners)]

    @property
    def lanes(self):
        return [q for s in self.replicas for q in s.lanes]

    @property
    def idle(self) -> bool:
        return not self.queue and all(s.in_flight == 0
                                      for s in self.replicas)

    @property
    def in_flight(self) -> int:
        return sum(s.in_flight for s in self.replicas)

    def tick(self) -> None:
        """One round, as the span ``serve.round``: every replica's issue
        half, in replica order, then the collect half of every level put in
        flight, in the same order, so that the replicas' levels run on
        their chips together.  Admission order is deterministic (replica 0
        fills first); a replica with no lanes in flight and nothing left to
        admit is skipped, so idle replicas cost nothing per round."""
        eng = self.engine
        spans, levels0 = eng._spans, eng.stats["levels"]
        with spans("serve.round", graph=self.name) as ann:
            issued = []
            for k, s in enumerate(self.replicas):
                if not (s.in_flight or self.queue):
                    continue
                with spans("serve.tick", graph=self.name, level=s.ell,
                           replica=k, half="issue") as a:
                    mode, new_lane = s.issue()
                    a.set_metadata(mode=mode)
                if new_lane is not None:
                    issued.append((k, s, new_lane))
            for k, s, new_lane in issued:
                with spans("serve.tick", graph=self.name, level=s.ell,
                           replica=k, half="collect"):
                    s.collect(new_lane)
            ann.set_metadata(replicas=len(issued))
        if eng.stats["levels"] > levels0:
            eng.stats["rounds"] += 1
