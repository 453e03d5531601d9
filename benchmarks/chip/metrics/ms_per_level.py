"""Host-clock milliseconds of the window per traversal level run in it
(the engine's ``levels`` counter)."""


def read(rec):
    levels = rec["stats"]["end"]["levels"] - rec["stats"]["start"]["levels"]
    return 1e3 * rec["window"]["elapsed_s"] / levels if levels else None
