"""Jitted public wrappers around the Pallas kernels.

On non-TPU backends the kernels execute with ``interpret=True`` (kernel body
run as plain JAX on CPU) so correctness is validated everywhere; on TPU they
compile to Mosaic.  Callers can force either path or fall back to the pure-jnp
reference (used by the ablation benchmarks as the "no-kernel" variant).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import frontier_sweep as _sweep
from repro.kernels import pull_ms as _pull_ms
from repro.kernels import pull_ss as _pull_ss
from repro.kernels import ref as kref


@functools.cache
def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_rows(x: jax.Array, mult: int, fill=0) -> jax.Array:
    n = x.shape[0]
    rem = (-n) % mult
    if rem == 0:
        return x
    pad_width = [(0, rem)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad_width, constant_values=fill)


def pull_ss(masks, alphas, *, block_v=_pull_ss.DEFAULT_BLK_V,
            use_pallas: bool = True, interpret: bool | None = None):
    """SS-BFS pull. Pads N_v to a block multiple, trims the result."""
    if not use_pallas:
        return kref.pull_ss_ref(masks, alphas)
    interpret = _interpret_default() if interpret is None else interpret
    n_v = masks.shape[0]
    block_v = min(block_v, max(8, 1 << (n_v - 1).bit_length())) if n_v else block_v
    m = _pad_rows(masks, block_v)
    a = _pad_rows(alphas, block_v)
    out = _pull_ss.pull_ss(m, a, block_v=block_v, interpret=interpret)
    return out[:n_v]


def pull_ss_packed(masks_packed, alphas, *, block_v=_pull_ss.DEFAULT_BLK_V,
                   use_pallas: bool = True, interpret: bool | None = None):
    if not use_pallas:
        return kref.pull_ss_packed_ref(masks_packed, alphas)
    interpret = _interpret_default() if interpret is None else interpret
    n_v = masks_packed.shape[0]
    block_v = min(block_v, max(8, 1 << (n_v - 1).bit_length())) if n_v else block_v
    m = _pad_rows(masks_packed, block_v)
    a = _pad_rows(alphas, block_v)
    out = _pull_ss.pull_ss_packed(m, a, block_v=block_v, interpret=interpret)
    return out[:n_v]


def pull_ms(masks, f_planes, v2r, *, sigma: int = 8,
            use_pallas: bool = True, interpret: bool | None = None):
    """MS-BFS pull. f_planes: (num_sets, sigma, kappa) bit-planes."""
    if not use_pallas:
        f_tiles = f_planes[v2r]
        return kref.pull_ms_ref(masks, f_tiles)
    interpret = _interpret_default() if interpret is None else interpret
    return _pull_ms.pull_ms(masks, f_planes, v2r, sigma=sigma,
                            interpret=interpret)


def frontier_sweep(v_curr, v_next, level, ell, *, sigma: int = 8,
                   block_n: int | None = None,
                   use_pallas: bool = True, interpret: bool | None = None):
    if not use_pallas:
        return kref.frontier_sweep_ref(v_curr, v_next, level, ell, sigma=sigma)
    interpret = _interpret_default() if interpret is None else interpret
    n_pad = v_curr.shape[0]
    # the kernel works on rows of 128*sigma vertices: round the block to
    # whole rows, and to one block when the array is smaller
    row = _sweep.LANES * sigma
    block_n = _sweep.DEFAULT_BLK_N if block_n is None else block_n
    block_n = min(-(-block_n // row), -(-n_pad // row)) * row
    rem = (-n_pad) % block_n
    if rem:
        v_curr = jnp.pad(v_curr, (0, rem))
        v_next = jnp.pad(v_next, (0, rem))
        level = jnp.pad(level, (0, rem))
    v_new, level_new, f_words, active = _sweep.frontier_sweep(
        v_curr, v_next, level, ell, sigma=sigma, block_n=block_n,
        interpret=interpret)
    if rem:
        v_new = v_new[:n_pad]
        level_new = level_new[:n_pad]
        f_words = f_words[: n_pad // sigma]
        active = active[: n_pad // sigma]
    return v_new, level_new, f_words, active


pack_masks = _pull_ss.pack_masks
unpack_marks = _pull_ss.unpack_marks
