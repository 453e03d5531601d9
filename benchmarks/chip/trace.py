"""The reduction from a profiler trace to the benchmark's device numbers.

``record(dir)`` traces what runs inside it; ``reduce`` reads the
``.xplane.pb`` that the JAX profiler wrote, with ``jax.profiler.ProfileData``:

* busy seconds of each device: the union of the intervals of the events
  on its op line (``XLA Ops`` on a TPU);
* device seconds per op (its name and operand shapes), and per program
  name (``XLA Modules``) with the count of each program's runs;
* the idle gaps between busy intervals, each named by the innermost
  ``bench.*`` host span (``jax.profiler.TraceAnnotation`` in the harness)
  that covers the gap's middle, summed by that name.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Reduced:
    busy_s: float                 # busy seconds, averaged over the chips
    devices: int                  # device planes that held an event
    ops: dict                     # op name -> device seconds (all devices)
    modules: dict                 # program name -> [runs, device seconds]
    idle_gaps: dict               # host span -> idle seconds inside it
    span_s: dict                  # host span -> seconds (summed)


@contextlib.contextmanager
def record(log_dir: str):
    """Trace the body into ``log_dir``; Python calls are not traced."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_file(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {found}")
    return found[0]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covering(spans, starts, t, outer) -> str:
    """The innermost span that covers ``t``.  The harness's spans inside
    the window follow one another without nesting, so the one that
    started last before ``t`` is the only candidate; else the window's."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i][1] >= t:
        return spans[i][2]
    return next((nm for a, b, nm in outer if a <= t <= b), "untraced")


def _op_name(hlo: str) -> str:
    """An op's name and operand shapes, ``%name = type op(operands)``,
    from the HLO text a TPU trace names it by: layouts and attributes are
    left out, so that the ``breakdown`` stays short."""
    s = hlo
    while True:
        t = re.sub(r"\{[^{}]*\}", "", s)
        if t == s:
            break
        s = t
    # the operand list opens at the first "(" right after an op's name
    m = re.search(r"[\w-]\(", s[s.find(" = ") + 1:])
    if m is None:
        return s
    depth = 0
    for i in range(s.find(" = ") + 1 + m.end() - 1, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return s[:i + 1]
    return s


def _base_name(name: str) -> str:
    """A program's name without the run id that the trace appends."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(path: str, *, chips: int, device_plane=DEVICE_PLANE,
           op_line: str = OP_LINE, module_line: str = MODULE_LINE,
           host_plane: str = HOST_PLANE) -> Reduced:
    """Reduce one ``.xplane.pb``.  ``device_plane`` matches the planes of
    the devices and ``op_line`` names their op line; a test on the CPU
    points them at the host's XLA threads instead."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    ops: dict[str, float] = {}
    modules: dict[str, list] = {}
    busy_total, devices = 0.0, 0
    busy_all = []
    spans = []
    for plane in prof.planes:
        if plane.name == host_plane:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name))
        if not device_plane.match(plane.name):
            continue
        intervals = []
        for line in plane.lines:
            if line.name.startswith(op_line):
                for ev in line.events:
                    intervals.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns))
                    op = _op_name(ev.name)
                    ops[op] = ops.get(op, 0.0) + ev.duration_ns
            elif line.name == module_line:
                for ev in line.events:
                    m = modules.setdefault(_base_name(ev.name), [0, 0.0])
                    m[0] += 1
                    m[1] += ev.duration_ns * 1e-9
        merged = _union(intervals)
        if merged:
            devices += 1
            busy_total += sum(e - s for s, e in merged) * 1e-9
            busy_all.extend(merged)
    gaps: dict[str, float] = {}
    merged = _union(busy_all)
    outer = [sp for sp in spans if sp[2] == WINDOW_SPAN]
    inner = sorted(sp for sp in spans if sp[2] != WINDOW_SPAN)
    starts = [a for a, _, _ in inner]
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        name = _covering(inner, starts, (e0 + s1) / 2, outer)
        gaps[name] = gaps.get(name, 0.0) + (s1 - e0) * 1e-9
    span_s: dict[str, float] = {}
    for a, b, nm in spans:
        span_s[nm] = span_s.get(nm, 0.0) + (b - a) * 1e-9
    return Reduced(busy_s=busy_total / max(chips, 1), devices=devices,
                   ops={k: v * 1e-9 for k, v in ops.items()},
                   modules=modules, idle_gaps=gaps, span_s=span_s)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line."""
    def head(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": head(red.ops), "idle_gaps": head(red.idle_gaps)}
