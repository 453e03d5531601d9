"""Seconds from the process's start to the window's start: generation,
artifact build (reorder, BVSS, MMA tile prep), the switching probe,
compilation or cache loads, and the warm-up queries."""


def read(rec):
    return rec["setup"]["setup_s"]
