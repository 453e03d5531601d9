"""Scatter-OR Pallas kernel — the missing XLA primitive that unlocks the
paper's packed kappa-bit MS-BFS state on TPU (§Perf cell-1 iteration 4).

XLA scatter combiners are {set, add, min, max, mul}: OR over packed uint32
words is inexpressible, which forced the byte-plane visited layout
(DESIGN.md §2) costing 8x the byte floor.  This kernel implements

    out = dest;  out[rows[i], :] |= marks[i, :]   (duplicates OR-combine)

On the TPU the destination words stay resident in VMEM for the whole grid,
viewed lane-dense as ``(R, 128)`` int32 rows (:func:`to_lane_rows`; a
``(n, kw)`` block with ``kw = 1`` would waste 127 of every 128 lanes).
Step 0 copies ``dest`` in from HBM; every step then takes one block of
scatter rows and mark words into SMEM and ORs each nonzero word into its
destination lane with a one-row read-modify-write (:func:`scatter_block`).
Grid steps run sequentially on one core, so duplicate rows combine in a
well-defined order.  The fused pull+scatter kernels reuse the same
resident-output machinery with marks computed in-kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_SUBLANES = 8
# slots per scatter_or grid step: an (8, 128) int32 block of rows in SMEM
_SLOT_ROWS = 8
# VMEM a v5e core can give one kernel (128 MiB physical, headroom kept)
_VMEM_CAP = 100 << 20


def to_lane_rows(words: jax.Array) -> jax.Array:
    """(n, kw) uint32 -> (R, 128) int32 row-major lane view, R % 8 == 0."""
    flat = jax.lax.bitcast_convert_type(words, jnp.int32).reshape(-1)
    pad = (-flat.shape[0]) % (LANES * _SUBLANES)
    return jnp.pad(flat, (0, pad)).reshape(-1, LANES)


def from_lane_rows(rows: jax.Array, shape) -> jax.Array:
    """Inverse of :func:`to_lane_rows`."""
    n = shape[0] * shape[1]
    flat = rows.reshape(-1)[:n].reshape(shape)
    return jax.lax.bitcast_convert_type(flat, jnp.uint32)


def pad_blocks(n: int, *arrays):
    """Block height for a leading axis of ``n`` (32, 16 or 8 rows) and the
    arrays, zero-padded along it to a multiple of 8 when none of those
    divides ``n``.  Zero padding is inert: zero masks or marks write
    nothing."""
    blk = next((b for b in (32, 16, 8) if n % b == 0), 0)
    if blk:
        return blk, arrays
    pad = (-n) % 8
    return 8, tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                    for a in arrays)


def or_into_word(out_ref, flat, word):
    """``out.flat[flat] |= word`` on the lane-row view; no-op for 0."""
    @pl.when(word != 0)
    def _():
        r = flat // LANES
        hit = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) == (
            flat % LANES)
        cur = out_ref[pl.ds(r, 1), :]
        out_ref[pl.ds(r, 1), :] = cur | jnp.where(hit, word, 0)


def scatter_block(out_ref, rows_s, marks_s, *, kw: int, width: int):
    """OR ``marks_s[q, w*width + j]`` into word ``rows_s[q, j]*kw + w`` for
    every slot ``(q, j)`` of one SMEM block (scalar loop, VMEM RMW)."""
    def slot(q, j):
        base = rows_s[q, j] * kw
        for w in range(kw):
            or_into_word(out_ref, base + w, marks_s[q, w * width + j])

    @pl.loop(0, rows_s.shape[0])
    def _(q):
        @pl.loop(0, width)
        def _(j):
            slot(q, j)


def copy_in_first_step(dest_hbm, out_ref):
    """Step 0 of a resident-output grid: load the destination words."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        pltpu.sync_copy(dest_hbm, out_ref)


def resident_scatter_call(kernel, dest_rows: jax.Array, inputs, in_specs,
                          *, grid: int, scratch_shapes=(),
                          interpret: bool = False) -> jax.Array:
    """Run ``kernel(dest_hbm, *in_refs, out_ref, *scratch)`` over ``grid``
    sequential steps with the ``(R, 128)`` output resident in VMEM."""
    out_bytes = int(dest_rows.size) * 4
    limit = 2 * out_bytes + (16 << 20)
    if limit > _VMEM_CAP:
        raise ValueError(
            f"visited words ({out_bytes} B) do not fit one core's VMEM "
            f"twice over; shard the graph across devices")
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY), *in_specs],
        out_specs=pl.BlockSpec(dest_rows.shape, lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(dest_rows.shape, jnp.int32),
        scratch_shapes=list(scratch_shapes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=limit),
        interpret=interpret,
    )(dest_rows, *inputs)


def _scatter_or_kernel(dest_hbm, rows_ref, marks_ref, out_ref, *, kw):
    copy_in_first_step(dest_hbm, out_ref)
    scatter_block(out_ref, rows_ref, marks_ref, kw=kw, width=LANES)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_or(
    dest: jax.Array,     # (n_rows, words) uint32
    rows: jax.Array,     # (t,) int32 — destination row per scatter element
    marks: jax.Array,    # (t, words) uint32 — values to OR in
    *,
    interpret: bool = False,
) -> jax.Array:
    """Returns dest with marks OR-scattered in (duplicate-safe)."""
    words = dest.shape[1]
    t = marks.shape[0]
    per_step = _SLOT_ROWS * LANES
    pad = (-t) % per_step
    # padding slots carry zero marks, so their (valid) row 0 is never hit
    rows2 = jnp.pad(rows.astype(jnp.int32), (0, pad)).reshape(-1, LANES)
    marks2 = jax.lax.bitcast_convert_type(
        jnp.pad(marks, ((0, pad), (0, 0))), jnp.int32)
    # slot k = q*128 + j, word w -> marks2[q, w*128 + j]
    marks2 = marks2.reshape(-1, LANES, words).transpose(0, 2, 1).reshape(
        -1, words * LANES)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    out = resident_scatter_call(
        functools.partial(_scatter_or_kernel, kw=words),
        to_lane_rows(dest), (rows2, marks2),
        [smem((_SLOT_ROWS, LANES), lambda i: (i, 0)),
         smem((_SLOT_ROWS, words * LANES), lambda i: (i, 0))],
        grid=rows2.shape[0] // _SLOT_ROWS, interpret=interpret)
    return from_lane_rows(out, dest.shape)


def scatter_or_ref(dest, rows, marks):
    """Oracle: OR-scatter via 32 bit-plane scatter-max passes."""
    acc = dest
    for b in range(32):
        bit = ((marks >> b) & jnp.uint32(1)).astype(jnp.uint32)
        plane = jnp.zeros(dest.shape, jnp.uint32).at[rows].max(bit)
        acc = acc | (plane << b)
    return acc
