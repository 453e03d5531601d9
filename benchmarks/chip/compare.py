"""The comparison that decides ``correct``.

Every answer of this system is exact, so each compared number has the
limit 0: ``wrong`` counts answers that differ from the plain reference in
any field the query kind returns, ``missing`` counts queries that were due
and never came back answered (still pending a minute past the window's
close, or ended in any state but DONE).  A run that compared no answer at
all proves nothing and is not correct either.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import reference

# the program's documented sentinel for an unreached vertex in a level array
PROGRAM_UNREACHED = np.iinfo(np.int32).max


@dataclasses.dataclass
class Verdict:
    checked: int
    wrong: int
    missing: int
    first_wrong: str | None = None

    @property
    def correct(self) -> bool:
        return self.checked > 0 and self.wrong == 0 and self.missing == 0

    def limits(self) -> dict:
        """Each compared number beside its limit, for the result line."""
        return {"wrong_answers": {"value": self.wrong, "limit": 0,
                                  "pass_if": "<="},
                "missing_answers": {"value": self.missing, "limit": 0,
                                    "pass_if": "<="},
                "answers_checked": {"value": self.checked, "limit": 1,
                                    "pass_if": ">="}}


def program_answer(kind: str, res) -> dict:
    """The fields of the program's result that a query kind returns."""
    if kind == "bfs":
        lv = np.asarray(res.levels)
        return {"levels": np.where(lv == PROGRAM_UNREACHED, -1, lv)}
    if kind == "closeness":
        return {"far": res.far, "reach": res.reach,
                "closeness": res.closeness}
    if kind == "distance":
        return {"distance": res.distance}
    return {"reach": res.reach}


def _same(a, b) -> bool:
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        a, b = np.asarray(a), np.asarray(b)
        return a.shape == b.shape and bool((a == b).all())
    return a == b


def reference_answers(g: reference.Csr, queries, *,
                      control: bool = False) -> list[dict]:
    """The reference's answers to ``queries`` (``traffic.Query``), or with
    ``control=True`` the control's."""
    sources = [q.source for q in queries]
    targets = [-1 if q.target is None else q.target for q in queries]
    keep = [i for i, q in enumerate(queries) if q.kind == "bfs"]
    ref = reference.traverse(g, sources, targets, keep, stop_early=control)
    return [reference.answer(q.kind, ref, i, g.n)
            for i, q in enumerate(queries)]


def compare(g: reference.Csr, queries, answers, missing: int) -> Verdict:
    """``answers[i]`` is what the system under test answered to
    ``queries[i]``, as ``program_answer`` gives it."""
    v = Verdict(checked=len(queries), wrong=0, missing=missing)
    for q, got, want in zip(queries, answers, reference_answers(g, queries)):
        bad = [k for k in want if not _same(got.get(k), want[k])]
        if bad:
            v.wrong += 1
            if v.first_wrong is None:
                v.first_wrong = (f"{q.kind} from {q.source}"
                                 f"{'' if q.target is None else f' to {q.target}'}"
                                 f": {', '.join(bad)} differ")
    return v
