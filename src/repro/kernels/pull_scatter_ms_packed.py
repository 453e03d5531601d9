"""Fused packed pull + scatter-OR (DESIGN.md §11.2).

No engine path runs this kernel any more: the packed dense level gathers
over a static slot table instead (``kernels/gather_or.py``), about three
times faster on a v5e because this kernel's scalar scatter loop walks
every slot.  Its jnp twin stays the reference of the dense level and the
row-sharded mesh runner's pull.

The dense packed level was two kernels with an HBM round-trip between them:
``pull_ms_packed`` materializes ``marks (N_q, tau, kw)`` uint32, then
``scatter_or`` re-reads every one of those ``N_q*tau`` rows to OR them into
the visited words.  At ``kw = kappa/32`` words per lane row that is
``2 * N_q * tau * kw * 4`` bytes of marks traffic per level that exists only
to connect the two grids.

This kernel fuses them over one grid of VSS blocks with the visited words
resident in VMEM (the :mod:`kernels.scatter_or` machinery): step 0 loads
``v``; every step computes its block's marks with the selective-OR pull
(slots on the lanes, :func:`~repro.kernels.pull_ms_packed.packed_marks`),
moves them to SMEM, and ORs each nonzero mark word into
``out[row_ids[q, j]]`` — the marks array never reaches HBM.  Grid steps
execute sequentially on a core, so duplicate destination rows
read-modify-write in a well-defined order.

The jnp twin composes the two kernels' references bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pull_ms_packed import (frontier_tiles, packed_marks,
                                          pull_ms_packed_ref)
from repro.kernels.scatter_or import (copy_in_first_step, from_lane_rows,
                                      pad_blocks, resident_scatter_call,
                                      scatter_block, scatter_or_ref,
                                      to_lane_rows)


def _pull_scatter_kernel(v_hbm, masks_ref, ft_ref, rows_ref, out_ref,
                         marks_v, marks_s, *, sigma, kw):
    copy_in_first_step(v_hbm, out_ref)
    tau = masks_ref.shape[1]
    words = packed_marks(masks_ref[...].astype(jnp.int32), ft_ref[...],
                         sigma=sigma, kw=kw)
    for w, acc in enumerate(words):
        marks_v[:, w * tau:(w + 1) * tau] = acc
    pltpu.sync_copy(marks_v, marks_s)
    scatter_block(out_ref, rows_ref, marks_s, kw=kw, width=tau)


@functools.partial(jax.jit, static_argnames=("sigma", "interpret"))
def pull_scatter_ms_packed(
    v: jax.Array,          # (n_rows, kw) uint32 visited words
    masks: jax.Array,      # (N_q, tau) uint8
    f_packed: jax.Array,   # (num_sets_ext, sigma, kw) uint32 frontier words
    v2r: jax.Array,        # (N_q,) int32
    rows: jax.Array,       # (N_q*tau,) int32 — row_ids flattened
    *,
    sigma: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Returns ``v`` with the dense pull's marks OR-scattered in, without
    materializing the marks array (duplicate-safe)."""
    kw = v.shape[1]
    n_q, tau = masks.shape
    assert f_packed.shape[1:] == (sigma, kw)
    assert rows.shape[0] == n_q * tau
    blk, (masks, ft, rows2) = pad_blocks(
        n_q, masks, frontier_tiles(f_packed, v2r), rows.reshape(n_q, tau))
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    out = resident_scatter_call(
        functools.partial(_pull_scatter_kernel, sigma=sigma, kw=kw),
        to_lane_rows(v), (masks, ft, rows2),
        [pl.BlockSpec((blk, tau), lambda i: (i, 0)),
         pl.BlockSpec((blk, kw * sigma), lambda i: (i, 0)),
         smem((blk, tau), lambda i: (i, 0))],
        grid=masks.shape[0] // blk,
        scratch_shapes=[pltpu.VMEM((blk, kw * tau), jnp.int32),
                        pltpu.SMEM((blk, kw * tau), jnp.int32)],
        interpret=interpret)
    return from_lane_rows(out, v.shape)


def pull_scatter_ms_packed_ref(v, masks, f_packed, v2r, rows, sigma: int = 8):
    """Oracle: the unfused pipeline — packed pull reference composed with the
    bit-plane scatter-OR reference (bit-identical to the fused kernel)."""
    marks = pull_ms_packed_ref(masks, f_packed[v2r], sigma=sigma)
    return scatter_or_ref(v, rows, marks.reshape(-1, v.shape[1]))
