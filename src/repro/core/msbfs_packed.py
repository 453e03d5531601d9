"""Packed-word MS-BFS — the paper's kappa-bit state layout, end-to-end.

The byte-plane MS-BFS (core/msbfs.py) spends 8x the unavoidable visited-state
bytes because XLA scatter cannot OR packed words.  With the two Pallas
primitives

    kernels/pull_ms_packed.py   (pull straight from packed frontier words)
    kernels/scatter_or.py       (duplicate-safe OR-scatter of packed marks)

the whole pipeline stays packed: V_curr/V_next are (n_ext, kappa/32) uint32,
Stage-2 sweeps use ``lax.population_count`` for the Eq.(7) far counts, and
the per-level state traffic drops from ~4*n*kappa bytes to ~(3/8)*n*kappa —
§Perf cell-1 iteration 4.

Level loop is host-driven (the Pallas scatter's grid depends only on static
shapes, so it could equally sit in a while_loop; host-driven keeps parity
with the bucketed driver and simplifies instrumentation).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blest import BvssDevice
from repro.kernels import pull_mma_ms_packed as mma
from repro.kernels.pull_ms_packed import pull_ms_packed
from repro.kernels.scatter_or import scatter_or


@dataclasses.dataclass
class PackedMsBfs:
    bd: BvssDevice
    interpret: bool | None = None
    # 'gather' — blocked selective-OR pull (kernels/pull_ms_packed);
    # 'mma'    — blocked binary-MMA pull (kernels/pull_mma_ms_packed,
    #            DESIGN.md §13): same marks, computed as bit-matrix products
    kernel: str = "gather"

    def __post_init__(self):
        if self.interpret is None:
            self.interpret = jax.default_backend() != "tpu"
        if self.kernel not in ("gather", "mma"):
            raise ValueError(f"unknown kernel {self.kernel!r}")
        self._mma_tiles = (mma.prep_mma_tiles(self.bd)
                           if self.kernel == "mma" else None)

    def run(self, sources: np.ndarray, max_levels: int | None = None):
        """Returns (v_curr packed (n_ext, kw) uint32, far (n_ext,) int32,
        reach (n_ext,) int32)."""
        bd = self.bd
        kappa = len(sources)
        assert kappa % 32 == 0, "packed layout needs kappa % 32 == 0"
        kw = kappa // 32
        max_levels = bd.n_ext if max_levels is None else max_levels
        interp = self.interpret

        sources = np.asarray(sources)
        v = np.zeros((bd.n_ext, kw), np.uint32)
        valid = sources >= 0
        idx = np.nonzero(valid)[0]
        v[sources[idx], idx // 32] |= np.uint32(1) << (idx % 32).astype(
            np.uint32)
        v = jnp.asarray(v)
        f = self._planes(v)
        far = jnp.zeros(bd.n_ext, jnp.int32)
        reach = jax.lax.population_count(v).sum(axis=1).astype(jnp.int32)

        tiles = self._mma_tiles

        @jax.jit
        def level(v, f, far, reach, ell):
            if tiles is not None:
                # MMA path: marks over the padded VSS list; the sentinel
                # rows of the pad tiles scatter into the scratch zone
                marks = mma.pull_mma_ms_packed(
                    tiles.a_planes, f, tiles.v2r, sigma=bd.sigma,
                    block=tiles.block, interpret=interp)
                rows = tiles.rows
            else:
                marks = pull_ms_packed(bd.masks, f, bd.v2r, sigma=bd.sigma,
                                       interpret=interp)
                rows = bd.row_ids.reshape(-1)
            v_next = scatter_or(v, rows, marks.reshape(-1, kw),
                                interpret=interp)
            diff = v_next & ~v
            new = jax.lax.population_count(diff).sum(axis=1).astype(jnp.int32)
            far = far + ell * new
            reach = reach + new
            f = self._planes(diff)
            return v_next, f, far, reach

        ell = 1
        while ell <= max_levels:
            v_new, f, far, reach = level(v, f, far, reach, jnp.int32(ell))
            if not bool((np.asarray(f) != 0).any()):
                v = v_new
                break
            v = v_new
            ell += 1
        return v, far, reach

    def _planes(self, v_or_diff):
        return frontier_planes(self.bd, v_or_diff)


def frontier_planes(bd: BvssDevice, v_or_diff):
    """(n_ext, width) visited/diff rows -> (num_sets_ext, sigma, width)
    frontier tiles with the sentinel slice set appended (dtype-generic;
    shared by PackedMsBfs and serve/bfs_engine)."""
    f = v_or_diff[: bd.n_pad].reshape(bd.num_sets, bd.sigma, -1)
    return jnp.concatenate(
        [f, jnp.zeros((1, bd.sigma, f.shape[2]), f.dtype)], axis=0)


def unpack_levels_check(v_packed, kappa: int):
    """(n, kw) uint32 -> (n, kappa) uint8 visited bytes (testing)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (v_packed[:, :, None] >> shifts) & jnp.uint32(1)
    return bits.astype(jnp.uint8).reshape(v_packed.shape[0], kappa)
