"""Queries answered in the window, over the window's seconds."""


def read(rec):
    return sum(q["in_window"] for q in rec["queries"]) / rec["window"]["seconds"]
