"""Host milliseconds per traversal level that the engine's step spends in
its own code, outside device read-backs: the window's sum of the self
seconds of every ``serve.*`` span but the ``serve.sync.*`` ones
(``stats["host_s:<span>"]``), over the window's levels.  Nothing where the
engine has no such spans, or no level ran."""

PREFIX, SYNC = "host_s:serve.", "host_s:serve.sync."


def read(rec):
    s0, s1 = rec["stats"]["start"], rec["stats"]["end"]
    keys = [k for k in s1 if k.startswith(PREFIX) and not k.startswith(SYNC)]
    levels = s1.get("levels", 0) - s0.get("levels", 0)
    if not keys or not levels:
        return None
    return 1e3 * sum(s1[k] - s0.get(k, 0) for k in keys) / levels
