"""Destination-major gather-OR: the dense packed level's scatter, turned
around (DESIGN.md §11.2).

XLA has no OR-combining scatter (§3.3), but a dense level scatters every
slot's mark word to ``row_ids[q, j]``, and ``row_ids`` is static per
graph.  So the scatter is inverted once, on the host, into a gather whose
indices never change:

* stage 1, ``chunks`` ``(D, num_chunks + 1)`` int32: the real slots
  (nonzero masks) sorted by destination row, each row's list cut into
  chunks of ``D`` and padded with a zero-mask slot; the last chunk is all
  padding (the zero chunk);
* stage 2, ``rows`` ``(C, n_rows)`` int32: each row's chunk ids, padded
  with the zero chunk; ``C`` is the most chunks any row has.

The short axis leads: the TPU compiler takes minutes over a gather whose
indices have a short minor axis, and under a second over this one.

A level then ORs ``marks[chunks]`` over ``D`` and the result over ``C``:
every slot lands in exactly one chunk and every chunk in exactly one row,
so the result is the scatter-OR bit for bit.  A zero-mask slot's marks are
zero at every level, so padding ORs nothing in.

Indices address the lane-dense marks of
:func:`repro.kernels.pull_ms_packed.pull_ms_packed_lanes`: word ``w`` of
slot ``(q, j)`` is element ``q*kw*tau + w*tau + j`` of the flat marks; the
tables hold the word-0 index and the gather adds ``w*tau``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# the chunk widths D may take; a graph gets the one whose table has the
# fewest entries (an entry of either stage costs about the same: 7.7 and
# 7.0 ns fitted over D = 8..64 at GAP urand scale 18 on a TPU v5e)
CHUNK_WIDTHS = (8, 16, 32, 64)


class SlotTable(NamedTuple):
    chunks: np.ndarray   # (D, num_chunks + 1) int32 word-0 mark indices
    rows: np.ndarray     # (C, n_rows) int32 chunk ids

    @property
    def entries(self) -> int:
        """Indices a level gathers: both stages."""
        return int(self.chunks.size + self.rows.size)


def chunk_width(counts: np.ndarray) -> int:
    """D for rows that receive ``counts`` real slices each: the width of
    fewest entries, stage 1's ``(sum ceil(counts/D) + 1) * D`` plus stage
    2's ``len(counts) * max ceil(counts/D)``; ties go to the smaller D.
    Few slices a row favour narrow chunks (less padding), a hot row wide
    ones (fewer stage-2 lines)."""
    def entries(d):
        per_row = -(-counts // d)
        return ((int(per_row.sum()) + 1) * d
                + counts.size * max(int(per_row.max(initial=0)), 1))
    return min(CHUNK_WIDTHS, key=entries)


def slot_table(row_ids: np.ndarray, masks: np.ndarray, n_rows: int,
               kw: int) -> SlotTable:
    """Build the two gather stages for a ``(N_q, tau)`` slot grid whose
    slot ``(q, j)`` ORs into row ``row_ids[q, j]`` when ``masks[q, j]`` is
    nonzero.  The grid needs at least one zero-mask slot (BVSS padding
    always gives one)."""
    tau = masks.shape[1]
    flat_masks = masks.reshape(-1)
    real = np.flatnonzero(flat_masks)
    zero_slot = int(np.flatnonzero(flat_masks == 0)[0])
    dest = row_ids.reshape(-1)[real]
    order = np.argsort(dest)  # any order within a row will do
    dest, slots = dest[order], real[order]
    # word-0 index of slot s = q*tau + j in the lane-dense marks
    slots = (slots // tau) * (kw * tau) + slots % tau
    zero = (zero_slot // tau) * (kw * tau) + zero_slot % tau

    counts = np.bincount(dest, minlength=n_rows)
    d = chunk_width(counts)
    per_row = -(-counts // d)                     # chunks of each row
    first = np.cumsum(per_row) - per_row          # each row's first chunk
    num_chunks = int(per_row.sum())
    # k-th slice of its row -> chunk first[row] + k // d, column k % d
    k = np.arange(dest.size) - np.repeat(np.cumsum(counts) - counts, counts)
    chunks = np.full((d, num_chunks + 1), zero, np.int32)
    chunks[k % d, first[dest] + k // d] = slots
    c = max(int(per_row.max(initial=0)), 1)
    rows = np.full((c, n_rows), num_chunks, np.int32)
    which = np.repeat(np.arange(n_rows), per_row)
    rows[np.arange(num_chunks) - np.repeat(first, per_row), which] = (
        np.arange(num_chunks))
    return SlotTable(chunks, rows)


def _or_reduce(x: jax.Array, axis: int) -> jax.Array:
    return jax.lax.reduce(x, np.int32(0), jax.lax.bitwise_or, (axis,))


def gather_or(marks: jax.Array, chunks: jax.Array, rows: jax.Array,
              kw: int) -> jax.Array:
    """OR every slot's marks into its destination row: lane-dense marks
    ``(N_blk, kw*tau)`` int32 -> ``(n_rows, kw)`` uint32 new bits."""
    tau = marks.shape[1] // kw
    flat = marks.reshape(-1)
    words = tau * jnp.arange(kw, dtype=jnp.int32)[:, None, None]
    per_chunk = _or_reduce(flat[chunks + words], 1)   # (kw, num_chunks + 1)
    per_row = _or_reduce(per_chunk[:, rows], 1)       # (kw, n_rows)
    return jax.lax.bitcast_convert_type(per_row.T, jnp.uint32)
