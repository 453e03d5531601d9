"""The generators reproduce from a seed and have the published shape."""
from __future__ import annotations

import numpy as np
import pytest

from chip_bench_tiny import BENCH  # noqa: F401  (puts the harness on the path)

import generators

URAND = {"family": "urand", "scale": 12, "edge_factor": 16}
RGG = {"family": "rgg", "scale": 11, "radius_coeff": 0.55}


@pytest.mark.parametrize("spec", [URAND, RGG], ids=["urand", "rgg"])
def test_same_seed_same_graph(spec):
    a = generators.make({**spec, "seed": 2**31 + 3})
    b = generators.make({**spec, "seed": 2**31 + 3})
    c = generators.make({**spec, "seed": 2**31 + 4})
    assert a[0] == b[0] and (a[1] == b[1]).all() and (a[2] == b[2]).all()
    assert a[1].size != c[1].size or not (a[1] == c[1]).all()


@pytest.mark.parametrize("spec", [URAND, RGG], ids=["urand", "rgg"])
def test_symmetric_simple(spec):
    n, src, dst = generators.make({**spec, "seed": 7})
    assert n == 1 << spec["scale"]
    assert src.dtype == dst.dtype == np.int32
    assert (src != dst).all()
    key = src.astype(np.int64) * n + dst
    assert np.unique(key).size == key.size
    rev = np.sort(dst.astype(np.int64) * n + src)
    assert (np.sort(key) == rev).all()


def test_urand_edge_factor():
    n, src, _ = generators.make({**URAND, "seed": 7})
    # 16 undirected edges per vertex, both directions, a few collisions
    assert 0.99 * 32 * n <= src.size <= 32 * n


def test_rgg_is_every_pair_within_the_radius():
    spec = {"family": "rgg", "scale": 9, "radius_coeff": 0.55}
    n, src, dst = generators.make({**spec, "seed": 11})
    pts = np.random.default_rng(11).random((n, 2))
    r = 0.55 * np.sqrt(np.log(n) / n)
    d2 = ((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    want = np.argwhere((d2 <= r * r) & ~np.eye(n, dtype=bool))
    got = np.stack([src, dst], 1)
    assert (np.unique(got, axis=0) == np.unique(want, axis=0)).all()


def test_rgg_mean_degree():
    n, src, _ = generators.make({"family": "rgg", "scale": 16,
                                 "radius_coeff": 0.55, "seed": 3})
    expect = np.pi * 0.55**2 * np.log(n)  # n * pi * r^2, edges ignored
    assert abs(src.size / n - expect) / expect < 0.05
