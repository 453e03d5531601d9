"""The one query generator: reads a traffic mix's parameters and draws its
queries from the run's seed.

A mix (``traffic/<name>.json``) holds:

``loop``
    ``"closed"``: ``clients`` callers, each sending its next query as soon
    as its last one is answered.  The only loop the harness drives.
``kinds``
    query kind -> share.  Every block of ``KIND_BLOCK`` queries holds the
    shares exactly, in an order drawn from the seed, so every seed sends
    the same mix.
``sources`` / ``targets``
    ``{"dist": "uniform"}``: uniform over all vertices.  ``targets`` is
    used by the ``distance`` kind alone.
``warmup_queries``
    how many queries set-up answers, with the same mix and another seed,
    before the window opens.
"""
from __future__ import annotations

import dataclasses

import numpy as np

KIND_BLOCK = 100
TARGETED = ("distance",)
LOOPS = ("closed",)
DISTS = ("uniform",)


@dataclasses.dataclass(frozen=True)
class Query:
    kind: str
    source: int
    target: int | None


def check(mix: dict) -> None:
    """Raise ``ValueError`` on a mix this generator cannot draw."""
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"loop {mix.get('loop')!r} is not one of {LOOPS}")
    for key in ("sources", "targets"):
        dist = mix.get(key, {}).get("dist", "uniform")
        if dist not in DISTS:
            raise ValueError(f"{key} dist {dist!r} is not one of {DISTS}")


class Stream:
    """The endless query sequence of one mix, one graph and one seed."""

    def __init__(self, mix: dict, n: int, seed: int):
        check(mix)
        self.n = n
        self.rng = np.random.default_rng(seed)
        shares = mix["kinds"]
        counts = {k: int(round(v * KIND_BLOCK)) for k, v in shares.items()}
        if sum(counts.values()) != KIND_BLOCK:
            raise ValueError(f"kind shares {shares} do not fill a block of "
                             f"{KIND_BLOCK} queries")
        self.block = np.array([k for k, c in counts.items() for _ in range(c)])
        self._buf: list[Query] = []

    def _refill(self) -> None:
        kinds = self.rng.permutation(self.block)
        src = self.rng.integers(0, self.n, kinds.size)
        tgt = self.rng.integers(0, self.n, kinds.size)
        self._buf = [Query(str(k), int(s), int(t) if k in TARGETED else None)
                     for k, s, t in zip(kinds, src, tgt)][::-1]

    def next(self) -> Query:
        if not self._buf:
            self._refill()
        return self._buf.pop()
