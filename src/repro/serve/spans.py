"""Host spans of the serving path, on the profiler's clock and in ``stats``.

A span does two things at once.  It enters a
``jax.profiler.TraceAnnotation``, so a profiler trace shows it on the
host plane, on the same clock as the device's ops and nested in the span
that was open when it began.  And it adds its *self* seconds (its
duration less the seconds of the spans nested in it) to
``stats["host_s:<name>"]`` on ``time.perf_counter``, so that the sums of
all spans of a step add up to the step's wall time, each second counted
once.  Both are always on: with no profiler running an annotation costs
about a microsecond.  The sums are flat totals, read as deltas between
two snapshots of ``stats``; the profiler keeps the spans themselves.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

KEY_PREFIX = "host_s:"


class Spans:
    """Nested host spans of one engine thread, summed into ``stats``.

    Every name a span may take is given at construction and its key is
    created there, so that a snapshot of ``stats`` taken at any time holds
    every key.  ``with spans("serve.tick", graph=g) as ann:`` opens one;
    ``ann.set_metadata(...)`` adds arguments known only inside it."""

    def __init__(self, stats: dict, names):
        self.stats = stats
        # seconds of the finished children of each open span, innermost last
        self._child_s: list[float] = []
        for name in names:
            stats[KEY_PREFIX + name] = 0.0

    def __call__(self, name: str, **args) -> "_Span":
        return _Span(self, name, args)


class _Span:
    __slots__ = ("spans", "key", "ann", "t0")

    def __init__(self, spans: Spans, name: str, args: dict):
        self.spans = spans
        self.key = KEY_PREFIX + name
        self.ann = TraceAnnotation(name, **args)

    def __enter__(self) -> TraceAnnotation:
        self.ann.__enter__()
        self.spans._child_s.append(0.0)
        self.t0 = time.perf_counter()
        return self.ann

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self.t0
        open_ = self.spans._child_s
        self.spans.stats[self.key] += dt - open_.pop()
        if open_:
            open_[-1] += dt
        self.ann.__exit__(*exc)
