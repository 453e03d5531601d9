"""Replica levels in flight together per round of a replica group: the
window's ``levels`` over its ``rounds``.  Nothing where the engine has no
``rounds`` counter, or no round ran."""


def read(rec):
    s0, s1 = rec["stats"]["start"], rec["stats"]["end"]
    rounds = s1.get("rounds", 0) - s0.get("rounds", 0)
    if not rounds:
        return None
    return (s1["levels"] - s0["levels"]) / rounds
