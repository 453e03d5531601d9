"""Share of the HBM roofline that a dense level reaches.

Numerator: the least time a dense level can take, the larger of its
bytes over the chip's HBM bandwidth and its int8 operations over the
int8 peak (``roofline.py``; the bytes bound it).  Denominator: the device seconds of the
dense-level program in the trace, over the dense levels the engine ran
in the traced window.  Nothing where no dense level ran."""
import re

import roofline

# the jitted dense level of the engine's lane runner
DENSE_PROGRAM = re.compile(r"^jit__level$")


def read(rec):
    red = rec["trace"]
    shapes = rec["artifact"]["shapes"]
    s0, s1 = rec["stats"]["start"], rec["stats"]["end"]
    dense = s1["levels_dense"] - s0["levels_dense"]
    if red is None or shapes is None or not dense:
        return None
    secs = sum(s for name, (_, s) in red.modules.items()
               if DENSE_PROGRAM.match(name))
    if not secs:
        return None
    kappa, peaks = rec["artifact"]["kappa"], rec["peaks"]
    least = max(roofline.dense_level_bytes(shapes, kappa)
                / peaks["hbm_bytes_per_s"],
                roofline.dense_level_int8_ops(shapes, kappa)
                / peaks["int8_ops_per_s"])
    return 100.0 * least / (secs / dense)
