"""Share of the HBM roofline that a queued level reaches.

Numerator: the least time a queued level can take, the larger of its
bytes over the chip's HBM bandwidth and its int8 operations over the int8
peak (``roofline_queued.py``), for the mean number of real active VSSs a
queued level pulled in the window (``queued_vss`` over ``levels_queued``).
Denominator: the device seconds of the queued-level program in the trace,
over the queued levels the engine ran in the traced window.  Nothing
where no queued level ran, or the engine does not count active VSSs."""
import re

import roofline_queued

# the jitted queued level of the engine's lane runner
QUEUED_PROGRAM = re.compile(r"^jit__level_queued$")


def read(rec):
    red = rec["trace"]
    shapes = rec["artifact"]["shapes"]
    s0, s1 = rec["stats"]["start"], rec["stats"]["end"]
    queued = s1.get("levels_queued", 0) - s0.get("levels_queued", 0)
    if red is None or shapes is None or "queued_vss" not in s1 or not queued:
        return None
    secs = sum(s for name, (_, s) in red.modules.items()
               if QUEUED_PROGRAM.match(name))
    if not secs:
        return None
    active = (s1["queued_vss"] - s0.get("queued_vss", 0)) / queued
    kappa, peaks = rec["artifact"]["kappa"], rec["peaks"]
    least = max(roofline_queued.queued_level_bytes(shapes, kappa, active)
                / peaks["hbm_bytes_per_s"],
                roofline_queued.queued_level_int8_ops(shapes, kappa, active)
                / peaks["int8_ops_per_s"])
    return 100.0 * least / (secs / queued)
