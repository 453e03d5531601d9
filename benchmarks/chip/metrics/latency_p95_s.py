"""95th percentile of the seconds from submission to answer, over every
query sent in the window, the ones answered after its close included (no
chunked medians)."""
import numpy as np


def read(rec):
    lat = [q["latency_s"] for q in rec["queries"] if q["latency_s"] is not None]
    return float(np.percentile(lat, 95)) if lat else None
