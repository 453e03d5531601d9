"""Host milliseconds per traversal level spent in device -> host
read-backs (waiting for the device, and the transfer): the window's sum of
the ``serve.sync.*`` spans' seconds (``stats["host_s:serve.sync.<site>"]``),
over the window's levels.  Nothing where the engine has no such spans, or
no level ran."""

PREFIX = "host_s:serve.sync."


def read(rec):
    s0, s1 = rec["stats"]["start"], rec["stats"]["end"]
    keys = [k for k in s1 if k.startswith(PREFIX)]
    levels = s1.get("levels", 0) - s0.get("levels", 0)
    if not keys or not levels:
        return None
    return 1e3 * sum(s1[k] - s0.get(k, 0) for k in keys) / levels
