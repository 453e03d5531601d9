"""The comparison that decides ``correct``: a sound run passes, and a run
with the timed path broken underneath does not.

Each fault is planted at a public seam of the program, so that the test
keeps its meaning while the program's insides change: the engine's
``step``, its ``submit`` and ticket ``cancel``, and the workload plugins'
``extract``.  The cells run on one chip, so there is no exchange between
chips to leave out.
"""
from __future__ import annotations

import pytest

from chip_bench_tiny import run, tiny_cell

from repro.serve import workloads as workloads_mod
from repro.serve.bfs_engine import BfsEngine

CELLS = ("urand18.mixed-backlog", "rgg18.routing-backlog")


def _step_unchanged(monkeypatch):
    """``step`` returns with the engine's state unchanged."""
    monkeypatch.setattr(BfsEngine, "step", lambda self: [])


def _half_left_out(monkeypatch):
    """Every other query is accepted and then dropped."""
    submit = BfsEngine.submit
    calls = []

    def lossy(self, *a, **kw):
        t = submit(self, *a, **kw)
        calls.append(t)
        if len(calls) % 2 == 0:
            t.cancel()
        return t

    monkeypatch.setattr(BfsEngine, "submit", lossy)


def _answer_altered(monkeypatch):
    """Each workload's answer is altered where it is produced."""
    registry = workloads_mod.default_registry

    class Altered(workloads_mod.Workload):
        def __init__(self, inner):
            self.inner = inner
            self.kind = inner.kind
            self.needs_levels = inner.needs_levels
            self.watches_target = inner.watches_target

        def validate(self, query, graph):
            self.inner.validate(query, graph)

        def extract(self, lane):
            out = dict(self.inner.extract(lane) or {})
            if self.kind == "distance" and out.get("distance") is not None:
                out["distance"] += 1
            elif self.kind == "bfs":
                out["levels"] = out["levels"].copy()
                out["levels"][lane.query.source] = 1
            else:
                out["reach"] = lane.reach + 1
            return out

    monkeypatch.setattr(workloads_mod, "default_registry",
                        lambda: {k: Altered(w) for k, w in registry().items()})


FAULTS = {"step_unchanged": _step_unchanged,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch)
    out, lines = run(tiny_cell(cell, scale=8), drain_s=1.0, warmup_s=5.0)
    assert out["correct"] is False, (fault, out["checks"], lines)
    checks = out["checks"]
    assert (checks["wrong_answers"]["value"] > 0
            or checks["missing_answers"]["value"] > 0
            or checks["answers_checked"]["value"] == 0)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out, lines = run(tiny_cell(cell, scale=8), seed=12345)
    assert out["correct"] is True, (out["checks"], lines)
    assert out["checks"]["wrong_answers"]["value"] == 0
    assert out["attempted"] >= out["checks"]["answers_checked"]["value"] > 0
