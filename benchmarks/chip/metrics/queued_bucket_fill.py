"""Share of the rows dispatched for queued levels that are real active
VSSs rather than the padding of a power-of-two bucket: the window's
``queued_vss`` over its ``queued_rows``.  Nothing where the engine does
not count them, or no queued level ran."""


def read(rec):
    s0, s1 = rec["stats"]["start"], rec["stats"]["end"]
    rows = s1.get("queued_rows", 0) - s0.get("queued_rows", 0)
    if "queued_vss" not in s1 or not rows:
        return None
    return 100.0 * (s1["queued_vss"] - s0.get("queued_vss", 0)) / rows
