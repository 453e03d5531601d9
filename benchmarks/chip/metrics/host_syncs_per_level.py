"""Host round trips per traversal level in the window, from the engine's
``host_syncs`` and ``levels`` counters."""


def read(rec):
    s0, s1 = rec["stats"]["start"], rec["stats"]["end"]
    levels = s1["levels"] - s0["levels"]
    return (s1["host_syncs"] - s0["host_syncs"]) / levels if levels else None
