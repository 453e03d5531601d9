"""Bytes and operations the algorithm needs, from an artifact's shapes.

A dense level pulls every VSS once: it reads the packed BVSS artifact
(the sigma-bit masks, the row id of every slot, the VSS-to-slice-set map
and the slice-set pointers) once, reads the frontier planes and reads and
writes the visited words.  That is the work of the algorithm whatever
layout runs it: an implementation that moves more (the MMA layout's
unpacked int8 planes, level stamps rewritten whole) is measured against
the same count.  Its int8 operations, one (kappa x sigma) by
(sigma x tau) product per VSS, take under a tenth of the byte time at the
v5e's peaks, so bytes bound a dense level.
"""
from __future__ import annotations


def dense_level_bytes(shapes: dict, kappa: int) -> int:
    words = kappa // 32
    slots = shapes["num_vss_pad"] * shapes["tau"]
    artifact = (slots * 1                        # uint8 masks
                + slots * 4                      # int32 row ids
                + shapes["num_vss_pad"] * 4      # int32 VSS -> slice set
                + (shapes["num_sets"] + 1) * 4)  # int32 slice-set pointers
    visited = shapes["n_ext"] * words * 4
    frontier = shapes["num_sets_ext"] * shapes["sigma"] * words * 4
    return artifact + frontier + 2 * visited


def dense_level_int8_ops(shapes: dict, kappa: int) -> int:
    """Multiply-adds counted as two operations."""
    return 2 * shapes["num_vss_pad"] * kappa * shapes["sigma"] * shapes["tau"]
