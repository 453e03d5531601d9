"""Device programs the engine launched while serving, per traversal level
in the window (its ``dispatches`` and ``levels`` counters).  Nothing where
the engine does not count them, or no level ran."""


def read(rec):
    s0, s1 = rec["stats"]["start"], rec["stats"]["end"]
    levels = s1.get("levels", 0) - s0.get("levels", 0)
    if "dispatches" not in s1 or not levels:
        return None
    return (s1["dispatches"] - s0.get("dispatches", 0)) / levels
