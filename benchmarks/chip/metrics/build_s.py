"""Artifact build seconds on the host clock (reorder, BVSS, device
transfer, MMA tile prep): ``GraphArtifacts.build_s``."""


def read(rec):
    return rec["artifact"]["build_s"]
