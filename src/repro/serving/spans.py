"""In-program spans and counters of the serving engine.

A :class:`SpanRecorder` handed to ``ServeEngine(..., spans=recorder)``
records what the engine is doing on ``time.perf_counter``: one
:class:`Span` per phase (poll, join and its steps, page growth, each fused
chunk and its steps, snapshot), nested by ``parent``, with the counts taken
at the phase's boundary.  Each span also enters
``jax.profiler.TraceAnnotation(name)``, so a ``jax.profiler`` trace shows
the engine's phases on the host plane beside the device ops they launch.

Spans stay in memory until ``clear()``; nothing is written.  Without a
recorder (the default) the engine records nothing and annotates nothing.
See docs/serving_engine.md, "Spans".
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax


@dataclasses.dataclass
class Span:
    name: str
    start: float                  # perf_counter seconds
    end: float = -1.0             # -1 while open
    parent: int = -1              # index of the enclosing span, -1 at the top
    rid: int | None = None        # the request a per-request span belongs to
    counts: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start


class SpanRecorder:
    """Nested spans on one clock, in the order they opened."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.spans: list[Span] = []
        self._open: list[tuple[int, jax.profiler.TraceAnnotation]] = []

    def open(self, name: str, rid: int | None = None, *,
             start: float | None = None) -> Span:
        """Open ``name`` inside the innermost open span; ``start`` defaults
        to now (pass a clock reading the caller also uses)."""
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        parent = self._open[-1][0] if self._open else -1
        span = Span(name, self.clock() if start is None else start,
                    parent=parent, rid=rid)
        self._open.append((len(self.spans), ann))
        self.spans.append(span)
        return span

    def close(self, *, end: float | None = None, **counts: int) -> Span:
        """Close the innermost open span, adding ``counts``."""
        i, ann = self._open.pop()
        span = self.spans[i]
        span.end = self.clock() if end is None else end
        ann.__exit__(None, None, None)
        span.counts.update(counts)
        return span

    def switch(self, name: str, t: float | None = None) -> Span:
        """Close the innermost open span and open ``name`` beside it, both
        at ``t`` (default now): consecutive steps on one clock reading."""
        t = self.clock() if t is None else t
        self.close(end=t)
        return self.open(name, start=t)

    @contextlib.contextmanager
    def span(self, name: str, rid: int | None = None):
        """``open``/``close`` around a block; yields the span, whose
        ``counts`` the block may fill.  Spans the block opened and left
        open (it raised) close with it."""
        span = self.open(name, rid)
        depth = len(self._open)
        try:
            yield span
        finally:
            while len(self._open) >= depth:
                self.close()

    def self_seconds(self, i: int) -> float:
        """Span ``i``'s duration less what its children cover."""
        return self.spans[i].seconds - sum(
            s.seconds for s in self.spans if s.parent == i)

    def clear(self) -> None:
        """Drop every span; one left open (its block raised) is ended."""
        while self._open:
            self.close()
        self.spans.clear()
