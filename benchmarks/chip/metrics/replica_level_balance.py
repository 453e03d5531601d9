"""How evenly a replica group's chips share the levels: 100 x the fewest
over the most of the window's ``levels:replica<k>`` counters.  Nothing
where the engine has no such counters, or a replica ran no level."""

PREFIX = "levels:replica"


def read(rec):
    s0, s1 = rec["stats"]["start"], rec["stats"]["end"]
    levels = [s1[k] - s0.get(k, 0) for k in s1 if k.startswith(PREFIX)]
    if len(levels) < 2 or not min(levels):
        return None
    return 100.0 * min(levels) / max(levels)
