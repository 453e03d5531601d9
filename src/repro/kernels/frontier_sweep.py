"""Fused Stage-2 frontier-finalization kernel (paper Alg. 3, lines 33-50).

One coalesced sweep over the visited bytes computes, per (BLK_N,) tile:
  diff       = V_next & ~V_curr          (vertices new to the frontier)
  level[u]   = ell where diff[u]         (level assignment)
  f_words[s] = sigma-bit frontier word   (packing diff into F_curr^sigma)
  active[s]  = f_words[s] != 0           (next-level slice-set activity)

This is the TPU analogue of the paper's fully-coalesced 32-bit-word sweep:
threads = lanes, __ffs bit iteration = vectorized packing, and because lanes
own disjoint vertices no atomics are needed — exactly the property the paper
engineered for.  Each grid step takes rows of ``128 * sigma`` consecutive
vertices, so every row packs into exactly 128 lane-dense frontier words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# vertices per grid step: a (32, 128 * sigma) tile, the uint8 native height
DEFAULT_BLK_N = 32 * LANES * 8


def pack_weights(sigma: int) -> jax.Array:
    """(128*sigma, 128) bf16 packing matrix: ``2**k`` at ``[c*sigma + k, c]``,
    so ``diff_row @ W`` is the 128 sigma-bit frontier words of one row."""
    i = jnp.arange(LANES * sigma)[:, None]
    c = jnp.arange(LANES)[None, :]
    return jnp.where(i // sigma == c, 1 << (i % sigma), 0).astype(jnp.bfloat16)


def _sweep_kernel(ell_ref, w_ref, v_curr_ref, v_next_ref, level_ref,
                  v_out_ref, level_out_ref, fw_ref, act_ref):
    ell = ell_ref[0]
    v_next = v_next_ref[...]
    diff = v_next.astype(jnp.int32) & (1 - v_curr_ref[...].astype(jnp.int32))
    v_out_ref[...] = v_next
    level_out_ref[...] = jnp.where(diff != 0, ell, level_ref[...])
    # vertices lie row-major on the lanes, sigma per word: packing them is a
    # lane compaction, done as one exact bf16 product on the MXU (0/1
    # times powers of two below 2**8, summed in f32)
    words = jnp.dot(diff.astype(jnp.bfloat16), w_ref[...],
                    preferred_element_type=jnp.float32).astype(jnp.int32)
    fw_ref[...] = words.astype(jnp.uint8)
    act_ref[...] = (words != 0).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("sigma", "block_n", "interpret"))
def frontier_sweep(
    v_curr: jax.Array,
    v_next: jax.Array,
    level: jax.Array,
    ell: jax.Array,
    *,
    sigma: int = 8,
    block_n: int = DEFAULT_BLK_N,
    interpret: bool = False,
):
    """Returns (v_curr_new, level_new, f_words, active_sets).

    v_curr/v_next: (n_pad,) uint8 in {0,1}; level: (n_pad,) int32; ell scalar.
    n_pad must be a multiple of block_n and block_n of 128*sigma (ops.py
    pads); the kernel sees the arrays as rows of 128*sigma vertices.
    """
    (n_pad,) = v_curr.shape
    row = LANES * sigma
    assert n_pad % block_n == 0 and block_n % row == 0
    rows, br = n_pad // row, block_n // row
    blk = lambda i, ell_: (i, 0)  # noqa: E731
    out_shapes = (
        jax.ShapeDtypeStruct((rows, row), jnp.uint8),
        jax.ShapeDtypeStruct((rows, row), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.uint8),
        jax.ShapeDtypeStruct((rows, LANES), jnp.uint8),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((row, LANES), lambda i, ell_: (0, 0)),
            pl.BlockSpec((br, row), blk),
            pl.BlockSpec((br, row), blk),
            pl.BlockSpec((br, row), blk),
        ],
        out_specs=[
            pl.BlockSpec((br, row), blk),
            pl.BlockSpec((br, row), blk),
            pl.BlockSpec((br, LANES), blk),
            pl.BlockSpec((br, LANES), blk),
        ],
    )
    v_new, level_new, f_words, active = pl.pallas_call(
        _sweep_kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
    )(jnp.asarray(ell, jnp.int32).reshape(1), pack_weights(sigma),
      v_curr.reshape(rows, row), v_next.reshape(rows, row),
      level.reshape(rows, row))
    return (v_new.reshape(-1), level_new.reshape(-1), f_words.reshape(-1),
            active.reshape(-1))
