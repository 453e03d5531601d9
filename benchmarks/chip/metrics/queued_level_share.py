"""Share of the window's levels that the Eq. 6 policy ran queued
(frontier-compacted) rather than dense, from the engine's counters."""


def read(rec):
    s0, s1 = rec["stats"]["start"], rec["stats"]["end"]
    levels = s1["levels"] - s0["levels"]
    if not levels:
        return None
    return 100.0 * (s1["levels_queued"] - s0["levels_queued"]) / levels
