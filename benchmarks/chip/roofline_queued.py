"""Bytes and operations a queued level needs, from an artifact's shapes and
the number of active VSSs it pulls.

A queued level pulls only the VSSs under the active slice sets (|Q| of
them).  For each it reads its sigma-bit masks (tau bytes), the row id of
each of its tau slots, its VSS-to-slice-set entry and the one frontier
tile it pulls from (sigma planes of kappa bits), and reads and writes the
visited words of its tau rows.  The counts are those of the real active
VSSs: the padding rows of a power-of-two bucket are no work the algorithm
needs.  Its int8 operations, one (kappa x sigma) by (sigma x tau) product
per VSS as for a dense level (``roofline.py``), take under a tenth of the
byte time at the v5e's peaks, so bytes bound a queued level too.
"""
from __future__ import annotations


def queued_level_bytes(shapes: dict, kappa: int, active_vss: float) -> float:
    words = kappa // 32
    tau, sigma = shapes["tau"], shapes["sigma"]
    per_vss = (tau * 1                     # uint8 masks
               + tau * 4                   # int32 row ids
               + 4                         # int32 VSS -> slice set
               + sigma * words * 4         # its frontier tile
               + 2 * tau * words * 4)      # its rows' visited words, r + w
    return active_vss * per_vss


def queued_level_int8_ops(shapes: dict, kappa: int, active_vss: float) -> float:
    """Multiply-adds counted as two operations."""
    return 2 * active_vss * kappa * shapes["sigma"] * shapes["tau"]
