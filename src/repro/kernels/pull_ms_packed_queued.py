"""Frontier-compacted packed multi-source pull (DESIGN.md §10.1).

The queued-mode companion of :mod:`kernels.pull_ms_packed`: instead of
sweeping all ``N_v`` VSSs (dense work ~ N_v * tau even when one frontier
bit is set), the grid is the *active* VSS list ``qids`` — the union over
all kappa lanes of VSSs whose parent slice set holds a frontier bit,
bucket-padded to a power of two with a guaranteed padding VSS id — so the
pull does ~ |Q| * tau work, the paper's queued/top-down scheduling (Eq. (6)
left branch) applied to packed lanes.

For bucket entry i the kernel pulls, for VSS ``q = qids[i]`` with
sigma-bit masks m:

    marks[i, j, w] = OR_{b : m[j]_b = 1}  F_packed[v2r[q]*sigma + b, w]

The kernel is the blocked pull of :mod:`kernels.pull_ms_packed`: XLA
gathers the queued rows (``masks[qids]`` and the parent tiles through
``v2r[qids]``) on the device, so the grid blocks over queued VSSs with the
slots on the lanes.  (Scalar-prefetching ``qids`` to pick one VSS per grid
step needs ``(1, tau)`` blocks, which Mosaic refuses.)  Padding bucket
slots name a padding VSS (zero masks, sentinel parent set), so they
contribute no marks; the caller scatters with ``row_ids[qids]`` whose
padding rows land in the sentinel vertex slots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.pull_ms_packed import pull_ms_packed


@functools.partial(jax.jit, static_argnames=("sigma", "interpret"))
def pull_ms_packed_queued(
    masks: jax.Array,      # (N_v, tau) uint8 — ALL VSS masks (not gathered)
    f_packed: jax.Array,   # (num_sets_ext, sigma, kw) uint32 frontier words
    v2r: jax.Array,        # (N_v,) int32
    qids: jax.Array,       # (B,) int32 — active VSS ids, bucket-padded
    *,
    sigma: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """marks (B, tau, kw) uint32 — packed pull over the queued VSSs only."""
    return pull_ms_packed(masks[qids], f_packed, v2r[qids], sigma=sigma,
                          interpret=interpret)


def pull_ms_packed_queued_ref(masks, f_packed, v2r, qids, sigma: int = 8):
    """Oracle: XLA take of the queued rows, then the dense-pull reference."""
    m = masks[qids]                 # (B, tau) uint8
    f_tiles = f_packed[v2r[qids]]   # (B, sigma, kw) uint32
    acc = jnp.zeros((m.shape[0], m.shape[1], f_tiles.shape[2]), jnp.uint32)
    for b in range(sigma):
        sel = ((m >> b) & 1).astype(jnp.uint32)[:, :, None]
        acc = acc | (sel * f_tiles[:, b][:, None, :])
    return acc
