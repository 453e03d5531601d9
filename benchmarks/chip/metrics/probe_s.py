"""Switching-probe seconds on the host clock (``GraphArtifacts.probe_s``);
nothing where the probe did not run."""


def read(rec):
    return rec["artifact"]["probe_s"]
