"""MMA-layout packed multi-source pull: neighbor checks as blocked binary
matrix products (DESIGN.md §13).

The paper's headline trick maps the bit-level frontier×adjacency neighbor
check onto binary MMA instructions with *no wasted outputs*: every element
of the product tile is a needed (slot, lane) check.  The VPU formulation in
:mod:`repro.kernels.pull_ms_packed` evaluates, per VSS ``q`` with sigma-bit
masks ``m`` and parent frontier tile ``F``,

    marks[q, j, w] = OR_{b : m[j]_b = 1}  F[v2r[q]][b, w]

as ``sigma`` selective ORs.  Observed bit-level, that OR-reduction *is* a
binary matrix product: with ``A[q] = unpack(m)`` the (tau, sigma) 0/1 mask
matrix and ``B[q] = unpack(F[v2r[q]])`` the (sigma, kappa) 0/1 frontier
plane matrix,

    marks_bit[q] = (A[q] @ B[q]  >  0)           -- one MMA per VSS tile,

an integer matmul whose (tau, kappa) output tile holds exactly the
tau*kappa neighbor checks the level needs — the MXU analogue of the
paper's ``BMMA`` formulation (SlimSell's vectorizable-representation
framing applied to the packed lanes).  ``A`` is static per graph, so it is
unpacked to int8 planes **once** at tile-prep time (:func:`prep_mma_tiles`,
held in ``GraphArtifacts`` and counted against the cache budget);
``B`` changes every level and is unpacked in-kernel from the packed words.

Three entry points, each with a bit-identical jnp reference twin (the PR 4
pattern — the twin is the CPU path and the oracle):

* :func:`pull_mma_ms_packed` — the blocked Pallas kernel: the grid walks
  ``n_q // block`` steps; per VSS tile it feeds the MXU one int8
  ``(kappa, sigma) x (sigma, tau)`` product (:func:`mma_marks`, the
  frontier planes transposed so the tau slots land on the lanes) and packs
  the sign of the counts back to uint32 marks.  The frontier tiles are
  pre-gathered by XLA (``f_packed[v2r]``) so the grid can block over VSS
  tiles.
* :func:`pull_scatter_mma_ms_packed` — the fused scatter variant
  (DESIGN.md §11.2 applied to the MMA pull): each block's MMA marks go
  straight into the VMEM-resident visited words through the
  :mod:`kernels.scatter_or` machinery, so the marks array never reaches
  HBM.  Its jnp twin exploits the count formulation: integer counts are
  scatter-**add** safe (OR is not XLA-native), so one ``at[].add`` pass
  replaces the 32-bit-plane scatter-max ladder of ``scatter_or_ref`` —
  the popcount path, and the reason the MMA layout beats the fused gather
  kernel on dense levels off-TPU (benchmarks/serve_mma.py).
* :func:`pull_mma_byteplane_ref` — the AND-OR/popcount fallback for the
  byteplane substrate: same counts-matmul over uint8 bit-planes,
  bit-identical to ``kernels.ref.pull_ms_ref``.

Tile prep pads the VSS list to a multiple of the MMA block with *masked*
tiles (zero mask planes, sentinel parent set, sentinel scatter rows) — the
explicit pad-and-mask that the blocked grid requires (a ragged last tile
would otherwise read out of bounds); :func:`pull_mma_ms_packed` asserts the
alignment instead of assuming it.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pull_ms_packed import frontier_tiles
from repro.kernels.scatter_or import (copy_in_first_step, from_lane_rows,
                                      pad_blocks, resident_scatter_call,
                                      scatter_block, to_lane_rows)

MMA_VSS_BLOCK = 8  # VSS tiles per grid step (one MXU dot each)


# ---------------------------------------------------------------------------
# Tile prep (graph-static: built once, cached in GraphArtifacts)
# ---------------------------------------------------------------------------


def unpack_mask_planes(masks: np.ndarray, sigma: int) -> np.ndarray:
    """(N, tau) uint8 sigma-bit masks -> (N, tau, sigma) int8 0/1 planes —
    the static ``A`` operand of the binary MMA."""
    m = np.asarray(masks)
    return ((m[..., None] >> np.arange(sigma, dtype=np.uint8)) & 1).astype(
        np.int8)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["a_planes", "v2r", "rows", "nz_planes"],
                   meta_fields=["block"])
@dataclasses.dataclass(frozen=True)
class MmaTiles:
    """Graph-static MMA operands (DESIGN.md §13.1), device-resident and
    counted against the :class:`~repro.serve.bfs_engine.GraphCache` byte
    budget like every other per-graph substrate array.  A pytree, so jitted
    code takes the tiles as an argument (never as embedded constants).

    ``a_planes``/``v2r``/``rows`` serve the packed-word kernels; the VSS
    dimension is padded to a multiple of ``block`` with masked tiles (zero
    planes, sentinel parent set ``num_sets``, sentinel rows ``n_pad``) so
    the blocked grid divides evenly — pad tiles contribute zero counts and
    their scatter rows land in the sentinel scratch zone.  ``nz_planes``
    is the byteplane-substrate twin: mask planes of the slice-compacted
    nonzero-slot list (§11.2 ``_nz_*`` ordering, sentinel entry last).
    """

    a_planes: jax.Array   # (n_q_pad, tau, sigma) int8
    v2r: jax.Array        # (n_q_pad,) int32 — sentinel-padded parent sets
    rows: jax.Array       # (n_q_pad * tau,) int32 — sentinel-padded rows
    nz_planes: jax.Array  # (S + 1, sigma) int8 — compacted byteplane A rows
    block: int

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in
                   (self.a_planes, self.v2r, self.rows, self.nz_planes))


def prep_mma_tiles(bd, *, block: int = MMA_VSS_BLOCK) -> MmaTiles:
    """Unpack the BVSS masks to int8 MMA planes, explicitly pad-and-mask
    the VSS list to a ``block`` multiple, and compact the byteplane twin.

    ``bd`` is a :class:`repro.core.blest.BvssDevice`.  The pad rows are
    *masked*, not merely present: zero planes produce zero counts, the
    sentinel ``v2r`` names the always-empty frontier tile, and the
    sentinel rows scatter into the ``n_pad..n_ext`` scratch rows — so a
    misaligned graph (``num_vss_pad % block != 0``) is exact, not
    truncated (tests/test_mma_layout.py pins a deliberately misaligned n).
    """
    masks = np.asarray(bd.masks)
    n_q, tau = masks.shape
    pad = (-n_q) % block
    a = unpack_mask_planes(masks, bd.sigma)
    if pad:
        a = np.concatenate([a, np.zeros((pad, tau, bd.sigma), np.int8)])
    v2r = np.concatenate([np.asarray(bd.v2r),
                          np.full(pad, bd.num_sets, np.int32)]).astype(
        np.int32)
    rows = np.concatenate([np.asarray(bd.row_ids),
                           np.full((pad, tau), bd.n_pad, np.int32)]).astype(
        np.int32).reshape(-1)
    # byteplane twin: planes of the slice-compacted nonzero mask bytes, in
    # the engine's _nz_* order (np.nonzero row-major) + the sentinel entry
    nz_vss, nz_slot = np.nonzero(masks)
    nz_mask = np.append(masks[nz_vss, nz_slot], 0).astype(np.uint8)
    return MmaTiles(
        a_planes=jnp.asarray(a),
        v2r=jnp.asarray(v2r),
        rows=jnp.asarray(rows),
        nz_planes=jnp.asarray(unpack_mask_planes(nz_mask, bd.sigma)),
        block=block,
    )


# ---------------------------------------------------------------------------
# Blocked MMA pull (marks materialized; core/msbfs_packed + parity suite)
# ---------------------------------------------------------------------------


def _unpack_words(words, kw: int):
    """(..., kw) uint32 -> (..., kw*32) int8 0/1 bit-planes."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.astype(jnp.int8).reshape(*words.shape[:-1], kw * 32)


def _pack_bits(bits):
    """(..., kw, 32) bool/int -> (..., kw) uint32 packed words."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (bits.astype(jnp.uint32) << shifts).sum(axis=-1).astype(jnp.uint32)


def mma_marks(a, ft_row, *, sigma: int, kw: int):
    """Binary MMA pull of one VSS on the MXU: ``a`` (tau, sigma) int8 mask
    planes, ``ft_row`` (1, kw*sigma) int32 frontier words (``w*sigma+b``)
    -> kw arrays (1, tau) int32 of mark word w, slots on the lanes.

    The frontier planes are built transposed, ``x[l, b]`` = bit ``l % 32``
    of word ``(b, l // 32)``, so the one int8 product
    ``x (kappa, sigma) . a^T`` yields the (kappa, tau) count tile with the
    tau slots on the lanes; packing its signs is then a sublane sum of
    distinct bits (exact in int32)."""
    kappa = 32 * kw
    lane = jax.lax.broadcasted_iota(jnp.int32, (kappa, sigma), 0)
    x = jnp.zeros((kappa, sigma), jnp.int32)
    for w in range(kw):
        word = ft_row[:, w * sigma:(w + 1) * sigma]          # (1, sigma)
        x = jnp.where(lane // 32 == w, (word >> (lane % 32)) & 1, x)
    counts = jax.lax.dot_general(
        x.astype(jnp.int8), a, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                     # (kappa, tau)
    shift = jax.lax.broadcasted_iota(jnp.int32, (32, 1), 0)
    return [jnp.sum((counts[w * 32:(w + 1) * 32] > 0).astype(jnp.int32)
                    << shift, axis=0, keepdims=True) for w in range(kw)]


def _mma_block_marks(a_ref, ft_ref, marks_ref, *, sigma, kw):
    """Fill ``marks_ref`` (block, kw*tau) with one MMA pull per VSS."""
    @pl.loop(0, a_ref.shape[0])
    def _(q):
        words = mma_marks(a_ref[q], ft_ref[pl.ds(q, 1), :], sigma=sigma,
                          kw=kw)
        marks_ref[pl.ds(q, 1), :] = jnp.concatenate(words, axis=1)


def _pull_mma_kernel(a_ref, ft_ref, out_ref, *, sigma, kw):
    # the binary MMA: one int8 product per VSS tile; every element of the
    # (kappa, tau) count tile is a needed neighbor check
    _mma_block_marks(a_ref, ft_ref, out_ref, sigma=sigma, kw=kw)


@functools.partial(jax.jit, static_argnames=("sigma", "block", "interpret"))
def pull_mma_ms_packed(
    a_planes: jax.Array,   # (n_q_pad, tau, sigma) int8 — prep_mma_tiles
    f_packed: jax.Array,   # (num_sets_ext, sigma, kw) uint32 frontier words
    v2r: jax.Array,        # (n_q_pad,) int32 — sentinel-padded parent sets
    *,
    sigma: int = 8,
    block: int = MMA_VSS_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """marks (n_q_pad, tau, kw) uint32 — the dense packed pull as blocked
    binary matrix products.  Bit-identical to
    ``pull_ms_packed(masks, f_packed, v2r)`` over the real VSS prefix."""
    n_q, tau, sig = a_planes.shape
    _, sig_f, kw = f_packed.shape
    assert sig == sigma and sig_f == sigma
    if n_q % block:
        raise ValueError(
            f"MMA grid needs the VSS count padded to the block: {n_q} tiles "
            f"% block {block} != 0 — run prep_mma_tiles (pad-and-mask), the "
            f"kernel does not truncate ragged last tiles")
    # XLA pre-gathers the per-VSS frontier tiles so the grid can block over
    # VSS tiles
    out = pl.pallas_call(
        functools.partial(_pull_mma_kernel, sigma=sigma, kw=kw),
        grid=(n_q // block,),
        in_specs=[
            pl.BlockSpec((block, tau, sigma), lambda i: (i, 0, 0)),
            pl.BlockSpec((block, kw * sigma), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block, kw * tau), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_q, kw * tau), jnp.int32),
        interpret=interpret,
    )(a_planes, frontier_tiles(f_packed, v2r))
    out = out.reshape(n_q, kw, tau).transpose(0, 2, 1)
    return jax.lax.bitcast_convert_type(out, jnp.uint32)


def pull_mma_ms_packed_ref(a_planes, f_tiles):
    """Oracle twin: the same counts matmul in one batched XLA dot.
    ``f_tiles`` is pre-gathered ``f_packed[v2r]`` (the convention of
    ``pull_ms_packed_ref``); bit-identical to it and to the kernel."""
    kw = f_tiles.shape[-1]
    planes = _unpack_words(f_tiles, kw)
    counts = jax.lax.dot_general(
        a_planes, planes, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32)
    return _pack_bits((counts > 0).reshape(*counts.shape[:-1], kw, 32))


# ---------------------------------------------------------------------------
# Fused MMA pull + scatter (visited words update in-kernel)
# ---------------------------------------------------------------------------


def _pull_scatter_mma_kernel(v_hbm, a_ref, ft_ref, rows_ref, out_ref,
                             marks_v, marks_s, *, sigma, kw):
    copy_in_first_step(v_hbm, out_ref)
    _mma_block_marks(a_ref, ft_ref, marks_v, sigma=sigma, kw=kw)
    pltpu.sync_copy(marks_v, marks_s)
    scatter_block(out_ref, rows_ref, marks_s, kw=kw, width=a_ref.shape[1])


@functools.partial(jax.jit, static_argnames=("sigma", "interpret"))
def pull_scatter_mma_ms_packed(
    v: jax.Array,          # (n_rows, kw) uint32 visited words
    a_planes: jax.Array,   # (n_q_pad, tau, sigma) int8 — prep_mma_tiles
    f_packed: jax.Array,   # (num_sets_ext, sigma, kw) uint32 frontier words
    v2r: jax.Array,        # (n_q_pad,) int32 — sentinel-padded parent sets
    rows: jax.Array,       # (n_q_pad*tau,) int32 — sentinel-padded rows
    *,
    sigma: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Returns ``v`` with the MMA pull's marks OR-scattered in — the
    §11.2 fused grid (VMEM-resident visited words, one VSS block per
    step) with the marks computed as binary products on the MXU instead
    of the selective-OR ladder.  Bit-identical to
    ``pull_scatter_ms_packed``."""
    kw = v.shape[1]
    n_q, tau, sig = a_planes.shape
    assert sig == sigma
    assert rows.shape[0] == n_q * tau
    blk, (a_planes, ft, rows2) = pad_blocks(
        n_q, a_planes, frontier_tiles(f_packed, v2r), rows.reshape(n_q, tau))
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    out = resident_scatter_call(
        functools.partial(_pull_scatter_mma_kernel, sigma=sigma, kw=kw),
        to_lane_rows(v), (a_planes, ft, rows2),
        [pl.BlockSpec((blk, tau, sigma), lambda i: (i, 0, 0)),
         pl.BlockSpec((blk, kw * sigma), lambda i: (i, 0)),
         smem((blk, tau), lambda i: (i, 0))],
        grid=a_planes.shape[0] // blk,
        scratch_shapes=[pltpu.VMEM((blk, kw * tau), jnp.int32),
                        pltpu.SMEM((blk, kw * tau), jnp.int32)],
        interpret=interpret)
    return from_lane_rows(out, v.shape)


def pull_scatter_mma_ms_packed_ref(v, a_planes, f_packed, v2r, rows):
    """Oracle twin — and the fast CPU path of the MMA layout: the counts
    are plain integers, so the duplicate-safe combine is scatter-**add**
    (one XLA pass) instead of ``scatter_or_ref``'s 32 bit-plane
    scatter-max passes; the packed OR happens after, on the (n, kw)
    result.  Bit-identical to the fused kernel and to
    ``pull_scatter_ms_packed_ref``."""
    kw = v.shape[1]
    kappa = kw * 32
    planes = _unpack_words(f_packed[v2r], kw)           # (n_q, sigma, kappa)
    counts = jax.lax.dot_general(
        a_planes, planes, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32)               # (n_q, tau, kappa)
    acc = jnp.zeros((v.shape[0], kappa), jnp.int32).at[rows].add(
        counts.reshape(-1, kappa))
    return v | _pack_bits((acc > 0).reshape(v.shape[0], kw, 32))


# ---------------------------------------------------------------------------
# Byteplane-substrate fallback (AND-OR as popcount over uint8 planes)
# ---------------------------------------------------------------------------


def pull_mma_byteplane_ref(a_planes, f_tiles):
    """The byteplane-substrate MMA fallback: counts matmul over uint8
    bit-planes.  ``a_planes`` (N, tau, sigma) int8 (or (N, sigma) for
    slice-compacted rows, via a leading reshape), ``f_tiles``
    (N, sigma, kappa) uint8 in {0,1}; returns (N, tau, kappa) uint8 marks,
    bit-identical to ``kernels.ref.pull_ms_ref(masks, f_tiles)``."""
    counts = jax.lax.dot_general(
        a_planes, f_tiles.astype(jnp.int8), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32)
    return (counts > 0).astype(jnp.uint8)
