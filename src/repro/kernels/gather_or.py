"""Destination-major gather-OR: the dense packed level's pull and scatter
as one gather over a static slot table (DESIGN.md §11.2).

XLA has no OR-combining scatter (§3.3), but a dense level ORs every
slot's marks into ``row_ids[q, j]``, and ``row_ids`` is static per graph.
So the scatter is inverted once, on the host, into a gather whose indices
never change:

* stage 1, ``parents`` and ``masks``, both ``(D, num_cols)``: the real
  slots (nonzero masks) sorted by destination row, each row's list cut
  into chunks of ``D``; an entry holds its slot's parent slice set
  (``v2r[q]``, int32) and sigma-bit mask (uint8).  Padding entries have
  mask 0.  Column ``num_chunks`` and the columns after it are all
  padding: ``num_cols`` rounds ``num_chunks + 1`` up to whole blocks;
* stage 2, ``rows`` ``(C, n_rows)`` int32: each row's chunk ids, padded
  with the zero chunk ``num_chunks``; ``C`` is the most chunks any row has.

A level gathers each entry's parent frontier tile, one row of sigma·kw
uint32 words of ``f`` (32 bytes at kappa 32), and computes the slot's mark
in place as the OR of the tile's bit-rows its mask selects: the
arithmetic of :func:`repro.kernels.pull_ms_packed.packed_marks`.  It ORs
the marks over ``D``, then the chunks over ``C``.  Every real slot lands
in exactly one chunk and every chunk in exactly one row, so the result is
the scatter-OR bit for bit; a zero mask selects nothing, so padding ORs
nothing in.  No marks array is written.

On a TPU v5e XLA gathers a 32-byte row in about 1.35 ns and a scalar in
about 7.3 ns, whatever the operand's size: stage 1 fetches rows and stage
2 scalars, so the chunk width weighs them by those costs.  Stage 1 runs
in blocks of at most ``BLOCK_ROWS`` rows, which keeps the gathered tiles
(one lane-padded vector row each) in on-chip memory instead of writing
16 times their bytes to HBM.  The short axis leads in both tables: the
TPU compiler takes minutes over a gather whose indices have a short minor
axis, and seconds over these.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# the chunk widths D may take; a graph gets the one whose two stages cost
# the least at the v5e's gather costs (ns an index), a stage-1 row of
# sigma·kw words against a stage-2 scalar
CHUNK_WIDTHS = (8, 16, 32, 64, 128)
ROW_NS, SCALAR_NS = 1.35, 7.3
# stage-1 rows gathered per loop step
BLOCK_ROWS = 1 << 17
_LANES = 128  # block widths are whole vector rows


class SlotTable(NamedTuple):
    parents: np.ndarray  # (D, num_cols) int32 parent slice set of each slot
    masks: np.ndarray    # (D, num_cols) uint8 sigma-bit masks, 0 = padding
    rows: np.ndarray     # (C, n_rows) int32 chunk ids

    @property
    def entries(self) -> int:
        """Indices a level gathers: stage 1's rows and stage 2's scalars."""
        return int(self.parents.size + self.rows.size)


def chunk_width(counts: np.ndarray) -> int:
    """D for rows that receive ``counts`` real slices each: the width of
    least modelled time, stage 1's ``(sum ceil(counts/D) + 1) * D`` rows at
    ``ROW_NS`` plus stage 2's ``len(counts) * max ceil(counts/D)`` scalars
    at ``SCALAR_NS``; ties go to the smaller D.  Few slices a row favour
    narrow chunks (less padding), a hot row wide ones (fewer stage-2
    lines)."""
    def cost(d):
        per_row = -(-counts // d)
        return ((int(per_row.sum()) + 1) * d * ROW_NS
                + counts.size * max(int(per_row.max(initial=0)), 1)
                * SCALAR_NS)
    return min(CHUNK_WIDTHS, key=cost)


def _block_cols(d: int, cols: int) -> int:
    """Columns a stage-1 block spans for ``cols`` columns of width ``d``:
    the fewest blocks of at most ``BLOCK_ROWS`` rows, each a whole number
    of vector rows wide."""
    blocks = -(-cols // (BLOCK_ROWS // d))
    per_block = -(-cols // blocks)
    return -(-per_block // _LANES) * _LANES


def slot_table(row_ids: np.ndarray, masks: np.ndarray, v2r: np.ndarray,
               n_rows: int) -> SlotTable:
    """Build the two gather stages for a ``(N_q, tau)`` slot grid whose
    slot ``(q, j)`` ORs the frontier tile of slice set ``v2r[q]``, under
    mask ``masks[q, j]``, into row ``row_ids[q, j]``; zero-mask slots are
    left out."""
    tau = masks.shape[1]
    flat_masks = masks.reshape(-1)
    real = np.flatnonzero(flat_masks)
    dest = row_ids.reshape(-1)[real]
    order = np.argsort(dest)  # any order within a row will do
    dest, slots = dest[order], real[order]

    counts = np.bincount(dest, minlength=n_rows)
    d = chunk_width(counts)
    per_row = -(-counts // d)                     # chunks of each row
    first = np.cumsum(per_row) - per_row          # each row's first chunk
    num_chunks = int(per_row.sum())
    blk = _block_cols(d, num_chunks + 1)
    cols = -(-(num_chunks + 1) // blk) * blk
    # k-th slice of its row -> chunk first[row] + k // d, column k % d
    k = np.arange(dest.size) - np.repeat(np.cumsum(counts) - counts, counts)
    at = (k % d, first[dest] + k // d)
    parents = np.zeros((d, cols), np.int32)
    parents[at] = v2r[slots // tau]
    table_masks = np.zeros((d, cols), np.uint8)
    table_masks[at] = flat_masks[slots]
    c = max(int(per_row.max(initial=0)), 1)
    rows = np.full((c, n_rows), num_chunks, np.int32)
    which = np.repeat(np.arange(n_rows), per_row)
    rows[np.arange(num_chunks) - np.repeat(first, per_row), which] = (
        np.arange(num_chunks))
    return SlotTable(parents, table_masks, rows)


def _or_reduce(x: jax.Array, axis: int) -> jax.Array:
    return jax.lax.reduce(x, np.uint32(0), jax.lax.bitwise_or, (axis,))


def gather_or(f: jax.Array, parents: jax.Array, masks: jax.Array,
              rows: jax.Array) -> jax.Array:
    """OR every slot's marks into its destination row: frontier tiles
    ``(num_sets_ext, sigma, kw)`` uint32 -> ``(n_rows, kw)`` uint32 new
    bits."""
    num_sets, sigma, kw = f.shape
    tiles = f.reshape(num_sets, sigma * kw)
    d, cols = parents.shape
    blk = _block_cols(d, cols)
    bit = jnp.arange(sigma, dtype=jnp.uint32)

    def block(i, per_chunk):
        p = jax.lax.dynamic_slice_in_dim(parents, i * blk, blk, 1)
        m = jax.lax.dynamic_slice_in_dim(masks, i * blk, blk, 1)
        t = tiles[p].reshape(d, blk, sigma, kw)
        sel = ((m.astype(jnp.uint32)[:, :, None] >> bit) & 1) != 0
        marks = jnp.where(sel[..., None], t, jnp.uint32(0))
        # OR over the chunk's slots, then over the selected bit-rows
        out = _or_reduce(_or_reduce(marks, 0), 1)          # (blk, kw)
        return jax.lax.dynamic_update_slice_in_dim(per_chunk, out,
                                                   i * blk, 0)

    per_chunk = jax.lax.fori_loop(0, cols // blk, block,
                                  jnp.zeros((cols, kw), jnp.uint32))
    return _or_reduce(per_chunk[rows], 0)                  # (n_rows, kw)
