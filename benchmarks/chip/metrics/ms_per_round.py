"""Host-clock milliseconds of the window per round of a replica group
(the engine's ``rounds`` counter: group ticks that ran a level, each
putting every active replica's level in flight before it waits on any).
Nothing where the engine has no such counter, or no round ran."""


def read(rec):
    s0, s1 = rec["stats"]["start"], rec["stats"]["end"]
    rounds = s1.get("rounds", 0) - s0.get("rounds", 0)
    return 1e3 * rec["window"]["elapsed_s"] / rounds if rounds else None
