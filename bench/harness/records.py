"""What a run records from outside the program, and the window accounting.

Everything here is on the host's ``time.perf_counter`` clock, in seconds.
The harness sees the program only through ``ServeEngine``'s public hooks:
the admission hook (called per candidate request between chunks),
``on_prefill`` (called once per join, after its first token is on the host)
and ``on_chunk`` (called after each fused decode chunk), plus the
``EngineReport`` that ``run`` returns.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Span:
    name: str          # "admission wait" | "join" | "chunk"
    start: float
    end: float


@dataclasses.dataclass
class Req:
    """One window request as the harness saw it."""
    rid: int
    due: float               # scheduled arrival (absolute clock)
    prompt_len: int
    max_new_tokens: int
    admitted: float = -1.0   # the hook said yes
    first: float = -1.0      # first token on the host (engine admit_t)
    finish: float = -1.0     # last token on the host (engine finish_t)
    n_tokens: int = 0
    saved: int = 0           # prompt tokens restored from the prefix cache
    finish_reason: str = ""
    tokens: list = dataclasses.field(default_factory=list)
    prompt: np.ndarray | None = None


@dataclasses.dataclass
class Chunk:
    end: float               # on_chunk time
    wall_s: float            # ChunkStats.wall_s (the program's own span)
    n_active: int
    tokens_kept: int
    steps: int


class WallClockAdmission:
    """Open-loop arrivals on the wall clock, without changing the program.

    Every request reaches the engine with ``arrival_step=0``; this hook
    admits a request once its scheduled time has passed.  Called on an empty
    engine (``n_active_after == 1``) it sleeps until that time and admits,
    so the engine never meets a refusal at zero load.  A refusal stops the
    engine's poll, which keeps admission FIFO.  With ``due`` unset (set-up)
    it admits everything at once."""

    def __init__(self, sleep=time.sleep, clock=time.perf_counter):
        self.sleep = sleep
        self.clock = clock
        self.due: dict[int, float] | None = None
        self.admitted: dict[int, float] = {}
        self.late: list[float] = []      # oversleep past a due time
        self.spans: list[Span] = []

    def __call__(self, request, n_active_after: int) -> bool:
        if self.due is None:
            return True
        due = self.due[request.rid]
        now = self.clock()
        if now < due:
            if n_active_after != 1:
                return False
            called = now
            self.sleep(due - now)
            now = self.clock()
            self.spans.append(Span("admission wait", called, now))
            self.late.append(now - due)
        self.admitted[request.rid] = now
        return True


class Recorder:
    """``on_prefill`` / ``on_chunk`` hooks: timestamps only, no energy."""

    def __init__(self, clock=time.perf_counter, chunk_steps: int = 1):
        self.clock = clock
        self.chunk_steps = chunk_steps
        self.prefills: list[float] = []
        self.chunks: list[Chunk] = []
        self.chunk_listeners = []

    def on_prefill(self, n_computed: int, n_saved: int):
        self.prefills.append(self.clock())
        return None

    def on_chunk(self, stats):
        c = Chunk(self.clock(), stats.wall_s, stats.n_active,
                  stats.tokens_kept, self.chunk_steps)
        self.chunks.append(c)
        for fn in self.chunk_listeners:
            fn(c)
        return None


def engine_clock_zero(prefill_times: list[float], admit_ts: list[float]):
    """The engine's ``t0`` on the harness clock.  ``RequestResult.admit_t``
    is engine-relative; ``on_prefill`` fires right after it is taken, so the
    k-th join in time order pairs the k-th ``on_prefill`` time with the k-th
    smallest ``admit_t``, and the smallest difference is ``t0``."""
    if len(prefill_times) != len(admit_ts):
        raise RuntimeError(f"{len(prefill_times)} joins seen by on_prefill, "
                           f"{len(admit_ts)} in the report (preemption?)")
    return min(p - a for p, a in zip(sorted(prefill_times), sorted(admit_ts)))


def pct(values, q: float) -> float | None:
    """q-th percentile (linear), None on no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclasses.dataclass
class Window:
    """The measured window and everything recorded in it."""
    start: float
    seconds: float
    reqs: list[Req]
    chunks: list[Chunk]
    spans: list[Span]
    late: list[float]
    report: object = None          # the program's EngineReport

    @property
    def end(self) -> float:
        return self.start + self.seconds

    # -- end-to-end quantities (all requests whose arrival is in the window)
    def ttft(self) -> list[float]:
        return [r.first - r.due for r in self.reqs]

    def tpot(self) -> list[float]:
        return [(r.finish - r.first) / (r.n_tokens - 1) for r in self.reqs
                if r.n_tokens > 1]

    def tokens_in_window(self) -> int:
        """Output tokens on the host inside the window: first tokens at
        their join, the rest at the end of the chunk that made them."""
        first = sum(1 for r in self.reqs if self.start <= r.first < self.end)
        rest = sum(c.tokens_kept for c in self.chunks
                   if self.start <= c.end < self.end)
        return first + rest

    def chunks_in_window(self) -> list[Chunk]:
        return [c for c in self.chunks if self.start <= c.end < self.end]

    def covered(self, intervals) -> float:
        """Seconds of the window that the union of ``intervals`` covers."""
        clipped = sorted((max(a, self.start), min(b, self.end))
                         for a, b in intervals)
        return union_length([(a, b) for a, b in clipped if b > a])


def union_length(intervals) -> float:
    """Length of the union of (start, end) intervals sorted by start."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in intervals:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def decode_contexts(window: Window, chunk: int, t_start: float,
                    t_stop: float):
    """Per chunk that ran inside ``[t_start, t_stop)``: the context length
    each active slot attends at the chunk's first step, from the harness's
    own bookkeeping.  A request joins before the first chunk that ends after
    its first token, holds its slot for ``ceil((tokens - 1) / chunk)``
    chunks, and feeds position ``prompt + chunk * i + s`` at step ``s`` of
    its ``i``-th chunk (attending one more).  Returns None where the count of
    active slots disagrees with the program's own (``ChunkStats.n_active``).
    """
    ends = [c.end for c in window.chunks]
    active: dict[int, list[int]] = {}
    for r in window.reqs:
        n_chunks = -(-(r.n_tokens - 1) // chunk)
        j = next((k for k, e in enumerate(ends) if e > r.first), None)
        if j is None:
            continue
        for i in range(n_chunks):
            if j + i < len(ends):
                active.setdefault(j + i, []).append(
                    r.prompt_len + chunk * i + 1)
    out = []
    for k, c in enumerate(window.chunks):
        if len(active.get(k, [])) != c.n_active:
            return None
        if t_start <= c.end - c.wall_s and c.end <= t_stop:
            out.append(active.get(k, []))
    return out
