"""Packed-word multi-source pull: the VPU formulation of the (popc, AND)
pull over kappa-bit packed frontier words.

For one VSS, slice j with sigma-bit mask m pulls

    marks[j, w] = OR_{b : m_b = 1}  F_packed[parent*sigma + b, w]

i.e. at most sigma selective ORs of kappa/32-word rows — no unpacking, no
matmul, 1/8 the frontier bytes of the byte-plane path.  Paired with
kernels/scatter_or.py this keeps the whole MS-BFS state packed end-to-end
(§Perf cell-1 iteration 4).

The kernel blocks over VSSs: XLA gathers each VSS's parent frontier tile
(:func:`frontier_tiles`), and one grid step computes a ``(block, tau)``
mask tile against them with the slots on the lanes, writing the marks
lane-dense as ``(block, kw*tau)``; the wrapper restores ``(N_q, tau, kw)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.scatter_or import pad_blocks


def frontier_tiles(f_packed: jax.Array, v2r: jax.Array) -> jax.Array:
    """(num_sets, sigma, kw) uint32 frontier words gathered per VSS ->
    (N_q, kw*sigma) int32 with word ``w`` of bit-row ``b`` at ``w*sigma+b``."""
    ft = jnp.swapaxes(f_packed[v2r], 1, 2)
    return jax.lax.bitcast_convert_type(ft, jnp.int32).reshape(v2r.shape[0], -1)


def packed_marks(m, ft, *, sigma: int, kw: int):
    """Selective-OR pull of one block: ``m`` (B, tau) int32 masks, ``ft``
    (B, kw*sigma) frontier tiles -> kw arrays (B, tau) int32 of mark word w."""
    sels = [((m >> b) & 1) != 0 for b in range(sigma)]
    out = []
    for w in range(kw):
        acc = jnp.zeros(m.shape, jnp.int32)
        for b in range(sigma):
            c = w * sigma + b
            acc = acc | jnp.where(sels[b], ft[:, c:c + 1], 0)
        out.append(acc)
    return out


def _pull_blocked_kernel(masks_ref, ft_ref, out_ref, *, sigma, kw):
    tau = masks_ref.shape[1]
    words = packed_marks(masks_ref[...].astype(jnp.int32), ft_ref[...],
                         sigma=sigma, kw=kw)
    for w, acc in enumerate(words):
        out_ref[:, w * tau:(w + 1) * tau] = acc


@functools.partial(jax.jit, static_argnames=("sigma", "interpret"))
def pull_ms_packed_lanes(
    masks: jax.Array,      # (N_q, tau) uint8
    f_packed: jax.Array,   # (num_sets, sigma, kw) uint32 frontier words
    v2r: jax.Array,        # (N_q,) int32
    *,
    sigma: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """marks as the kernel writes them: (N_blk, kw*tau) int32 with word
    ``w`` of slot ``(q, j)`` at ``[q, w*tau + j]``.  Rows from N_q up to
    N_blk (a block multiple) are zero padding."""
    n_q, tau = masks.shape
    kw = f_packed.shape[2]
    assert f_packed.shape[1] == sigma
    blk, (masks, ft) = pad_blocks(n_q, masks, frontier_tiles(f_packed, v2r))
    n_blk = masks.shape[0]
    return pl.pallas_call(
        functools.partial(_pull_blocked_kernel, sigma=sigma, kw=kw),
        grid=(n_blk // blk,),
        in_specs=[pl.BlockSpec((blk, tau), lambda i: (i, 0)),
                  pl.BlockSpec((blk, kw * sigma), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((blk, kw * tau), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blk, kw * tau), jnp.int32),
        interpret=interpret,
        name="pull_ms_packed",
    )(masks, ft)


@functools.partial(jax.jit, static_argnames=("sigma", "interpret"))
def pull_ms_packed(
    masks: jax.Array,      # (N_q, tau) uint8
    f_packed: jax.Array,   # (num_sets, sigma, kw) uint32 frontier words
    v2r: jax.Array,        # (N_q,) int32
    *,
    sigma: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """marks (N_q, tau, kw) uint32 — packed pull for queued VSSs."""
    n_q, tau = masks.shape
    kw = f_packed.shape[2]
    out = pull_ms_packed_lanes(masks, f_packed, v2r, sigma=sigma,
                               interpret=interpret)
    out = out[:n_q].reshape(n_q, kw, tau).transpose(0, 2, 1)
    return jax.lax.bitcast_convert_type(out, jnp.uint32)


def lanes_of(marks: jax.Array) -> jax.Array:
    """(N_q, tau, kw) uint32 marks -> the lane-dense (N_q, kw*tau) int32
    layout of :func:`pull_ms_packed_lanes`."""
    n_q = marks.shape[0]
    out = jax.lax.bitcast_convert_type(marks, jnp.int32)
    return out.transpose(0, 2, 1).reshape(n_q, -1)


def pull_ms_packed_ref(masks, f_tiles, sigma: int = 8):
    """Oracle.  masks (N_q, tau) uint8; f_tiles (N_q, sigma, kw) uint32."""
    acc = jnp.zeros((masks.shape[0], masks.shape[1], f_tiles.shape[2]),
                    jnp.uint32)
    for b in range(sigma):
        sel = ((masks >> b) & 1).astype(jnp.uint32)[:, :, None]
        acc = acc | (sel * f_tiles[:, b][:, None, :])
    return acc
