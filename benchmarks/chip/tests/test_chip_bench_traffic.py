"""The query generator: the mix's shares and the seed."""
from __future__ import annotations

import collections

import pytest

from chip_bench_tiny import BENCH  # noqa: F401  (puts the harness on the path)

import traffic

MIX = {"loop": "closed", "kinds": {"distance": 0.8, "bfs": 0.2},
       "sources": {"dist": "uniform"}, "targets": {"dist": "uniform"}}


def test_every_block_holds_the_shares_exactly():
    stream = traffic.Stream(MIX, 1000, 2**31 + 1)
    for _ in range(3):
        block = [stream.next() for _ in range(traffic.KIND_BLOCK)]
        assert collections.Counter(q.kind for q in block) == {
            "distance": 80, "bfs": 20}
        assert all((q.target is None) == (q.kind == "bfs") for q in block)


def test_same_seed_same_queries():
    a = traffic.Stream(MIX, 1000, 5)
    b = traffic.Stream(MIX, 1000, 5)
    c = traffic.Stream(MIX, 1000, 6)
    qa = [a.next() for _ in range(300)]
    assert qa == [b.next() for _ in range(300)]
    assert qa != [c.next() for _ in range(300)]


def test_sources_and_targets_cover_the_vertices():
    stream = traffic.Stream(MIX, 50, 9)
    qs = [stream.next() for _ in range(2000)]
    assert {q.source for q in qs} == set(range(50))
    assert {q.target for q in qs if q.target is not None} == set(range(50))


@pytest.mark.parametrize("bad", [
    {"loop": "open"},
    {"sources": {"dist": "zipf", "s": 1.1}},
    {"targets": {"dist": "zipf", "s": 1.1}},
], ids=["open_loop", "zipf_sources", "zipf_targets"])
def test_a_mix_it_cannot_draw_is_refused(bad):
    with pytest.raises(ValueError):
        traffic.Stream({**MIX, **bad}, 1000, 1)
