"""Ahead-of-time compiles of the serve-path Pallas kernels for a described
TPU v5e, at the shapes ``chip_smoke.py`` serves (urand scale 20: about
16.8M BVSS slots, 1M visited rows, kappa = 32, so one packed word per
row).

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described rather than attached, so Mosaic refuses here
what it would refuse on the chip (unaligned blocks, unsupported vector
ops, VMEM over-use).  Every call passes ``interpret=False`` explicitly,
because ``jax.default_backend()`` still reports the CPU.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import pull_mma_ms_packed as mma
from repro.kernels.gather_or import gather_or
from repro.kernels.pull_ms_packed import pull_ms_packed_lanes
from repro.kernels.pull_ms_packed_queued import pull_ms_packed_queued
from repro.kernels.pull_scatter_ms_packed import pull_scatter_ms_packed
from repro.kernels.scatter_or import scatter_or

N_Q = 131080            # VSSs (num_vss_pad), tau = 128 slots each
TAU, SIGMA, KW = 128, 8, 1
N_EXT = (1 << 20) + 8   # visited rows: n_pad + sigma
N_SETS = (1 << 17) + 1  # slice sets + the sentinel set
BUCKET = 4096           # a queued-level bucket of active VSSs
CHUNKS, D, C = 2_300_000, 8, 8   # the dense level's slot table (§11.2)
# the name each serve-path kernel carries in the compiled program, which a
# device trace names its op by (the queued pull is ``pull_ms_packed``'s
# kernel, run on the gathered queued VSSs)
KERNEL_NAMES = {"pull_scatter_ms_packed": "pull_scatter_ms_packed",
                "dense_gather_or": "pull_ms_packed",
                "pull_ms_packed_queued": "pull_ms_packed",
                "scatter_or": "scatter_or"}


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile cannot be read back without the chip, so
    the persistent cache stays off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` -> a shape on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _cases(spec):
    v = spec((N_EXT, KW), jnp.uint32)
    masks = spec((N_Q, TAU), jnp.uint8)
    f = spec((N_SETS, SIGMA, KW), jnp.uint32)
    v2r = spec((N_Q,), jnp.int32)
    rows = spec((N_Q * TAU,), jnp.int32)
    planes = spec((N_Q, TAU, SIGMA), jnp.int8)
    return {
        "pull_scatter_ms_packed": (
            lambda *a: pull_scatter_ms_packed(*a, sigma=SIGMA,
                                              interpret=False),
            (v, masks, f, v2r, rows)),
        # the packed dense level: the pull, then the slot-table gather-OR
        "dense_gather_or": (
            lambda v, m, f, v2r, chunks, rows: v | gather_or(
                pull_ms_packed_lanes(m, f, v2r, sigma=SIGMA,
                                     interpret=False), chunks, rows, KW),
            (v, masks, f, v2r, spec((D, CHUNKS), jnp.int32),
             spec((C, N_EXT), jnp.int32))),
        "pull_ms_packed_queued": (
            lambda *a: pull_ms_packed_queued(*a, sigma=SIGMA,
                                             interpret=False),
            (masks, f, v2r, spec((BUCKET,), jnp.int32))),
        "scatter_or": (
            lambda *a: scatter_or(*a, interpret=False),
            (v, spec((BUCKET * TAU,), jnp.int32),
             spec((BUCKET * TAU, KW), jnp.uint32))),
        "pull_mma_ms_packed": (
            lambda *a: mma.pull_mma_ms_packed(*a, sigma=SIGMA,
                                              interpret=False),
            (planes, f, v2r)),
        "pull_scatter_mma_ms_packed": (
            lambda *a: mma.pull_scatter_mma_ms_packed(*a, sigma=SIGMA,
                                                      interpret=False),
            (v, planes, f, v2r, rows)),
        # the single-source entry (repro.launch.bfs) on the chip
        "pull_ss_packed": (
            lambda *a: ops.pull_ss_packed(*a, use_pallas=True,
                                          interpret=False),
            (spec((N_Q, TAU // 4), jnp.uint32), spec((N_Q,), jnp.uint8))),
        "frontier_sweep": (
            lambda *a: ops.frontier_sweep(*a, use_pallas=True,
                                          interpret=False),
            (spec((N_EXT,), jnp.uint8), spec((N_EXT,), jnp.uint8),
             spec((N_EXT,), jnp.int32), spec((), jnp.int32))),
    }


@pytest.mark.parametrize("kernel", [
    "pull_scatter_ms_packed", "dense_gather_or", "pull_ms_packed_queued",
    "scatter_or", "pull_mma_ms_packed", "pull_scatter_mma_ms_packed", "pull_ss_packed",
    "frontier_sweep"])
def test_kernel_compiles_for_v5e(spec, kernel):
    fn, args = _cases(spec)[kernel]
    text = jax.jit(fn).lower(*args).compile().as_text()
    # the Mosaic kernel is in the program (not the interpreter's XLA ops)
    assert "tpu_custom_call" in text
    if kernel in KERNEL_NAMES:
        assert re.search(rf"%{KERNEL_NAMES[kernel]}(\.\d+)? = [^\n]*"
                         r"custom-call\(", text), kernel
