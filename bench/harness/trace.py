"""A profiler trace of part of the window, reduced to device metrics.

``WindowTracer`` starts JAX's profiler from the ``on_chunk`` hook once 40%
of the window has passed and stops it at the first chunk ``trace_s``
seconds later (15% of the window, 2 to 8 s), also from
``on_chunk``: the traced interval then holds whole chunks and joins, and
the harness knows exactly which decode steps ran in it.  At each chunk in
between it writes a ``bench.clock`` annotation, whose host time it also
keeps, to put the trace's clock on the harness's.

The reduction works on plain event lists (``collect`` makes them from the
``.xplane.pb`` file), so it is tested on a small recorded trace:

  busy_s      length of the union of device op intervals in the window
  ops         device self seconds by op (ops nest on the op line: a while
              loop holds its body; a parent's self time leaves its
              children out)
  gaps        device idle intervals, each named by the harness span that
              covers its middle: "admission wait", "join", "chunk", or
              "engine host" where none does

On a TPU v5e under JAX 0.9 an op event's name is its HLO instruction text,
and a Pallas kernel is a ``custom-call`` with
``custom_call_target="tpu_custom_call"`` that carries no kernel name, so
readers pick kernels by that text (``op_events``).
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import shutil
import time

from harness import records

CLOCK = "bench.clock"
# device planes are "/device:TPU:<n>"; the line that holds one event per
# executed HLO op (fusions and custom calls, Pallas kernels among them)
DEVICE_PLANE = "/device:TPU:0"
OP_LINE = "XLA Ops"


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[8,2048]... fusion(...), kind=kLoop`` ->
    ``fusion.12 bf16[8,2048]{...}``; custom calls add their target."""
    head, _, rest = hlo.partition(" = ")
    shape = rest.split(" ", 1)[0] if rest else ""
    tgt = ""
    if 'custom_call_target="' in rest:
        tgt = " " + rest.split('custom_call_target="', 1)[1].split('"', 1)[0]
    return f"{head.lstrip('%')}{tgt} {shape}".strip()


def self_times(evs) -> dict:
    """Self seconds by short name of (name, start, end) events sorted by
    start, where an event lying inside another is its child."""
    out: dict[str, float] = {}
    stack: list[list] = []          # [name, end, child seconds, start]

    def pop():
        name, end, child, start = stack.pop()
        key = short_name(name)
        out[key] = out.get(key, 0.0) + (end - start) - child
        if stack:
            stack[-1][2] += end - start

    for name, a, b in evs:
        # an event that starts where another ends (to a nanosecond) follows
        # it; one that starts inside it is its child
        while stack and a >= stack[-1][1] - 1e-9:
            pop()
        if stack:
            b = min(b, stack[-1][1])      # a child ends inside its parent
        stack.append([name, b, 0.0, a])
    while stack:
        pop()
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: dict              # short op name -> device self seconds
    op_events: list        # (HLO text, start, end) on the harness clock
    gaps: list             # (label, seconds), longest first
    t_start: float
    t_stop: float

    def idle_share(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    @property
    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def collect(path: str) -> dict:
    """Events of an ``.xplane.pb`` file: device ops of the first TPU and the
    harness's clock markers, times in ns from the trace's origin."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, marks = [], []
    for plane in data.planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops += [[e.name, e.start_ns, e.duration_ns]
                            for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                marks += [e.start_ns for e in line.events if e.name == CLOCK]
    return {"ops": ops, "marks": sorted(marks)}


def label_of(t: float, spans) -> str:
    """The harness span covering time ``t``, by priority."""
    for name in ("admission wait", "join", "chunk"):
        if any(s.start <= t < s.end for s in spans if s.name == name):
            return name
    return "engine host"


def reduce(events: dict, host_marks: list[float], t_start: float,
           t_stop: float, spans) -> Summary:
    """Device metrics of ``[t_start, t_stop)`` (harness clock, seconds).

    The k-th clock marker in the trace was written at ``host_marks[k]``;
    the median difference maps trace nanoseconds onto the harness clock."""
    marks = events["marks"]
    n = min(len(marks), len(host_marks))
    if n == 0:
        raise RuntimeError("trace holds no clock markers")
    offs = sorted(host_marks[k] - marks[k] * 1e-9 for k in range(n))
    off = offs[n // 2]
    evs = []
    for name, start_ns, dur_ns in events["ops"]:
        a = start_ns * 1e-9 + off
        b = a + dur_ns * 1e-9
        a, b = max(a, t_start), min(b, t_stop)
        if b > a:
            evs.append((name, a, b))
    evs.sort(key=lambda e: (e[1], -e[2]))
    ops = self_times(evs)
    busy = records.union_length([(a, b) for _, a, b in evs])
    gaps, cur = [], t_start
    for _, a, b in evs:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t_stop > cur:
        gaps.append((cur, t_stop))
    by_label: dict[str, float] = {}
    for a, b in gaps:
        lab = label_of((a + b) / 2, spans)
        by_label[lab] = by_label.get(lab, 0.0) + (b - a)
    named = sorted(by_label.items(), key=lambda kv: -kv[1])
    return Summary(t_stop - t_start, busy, ops, evs, named, t_start, t_stop)


class WindowTracer:
    """Traces ``trace_s`` seconds of the window from the ``on_chunk`` hook."""

    def __init__(self, root, seconds: float):
        self.dir = os.path.join(str(root), ".bench_trace")
        self.seconds = seconds
        self.trace_s = min(8.0, max(2.0, 0.15 * seconds))
        self.state = "idle"
        self.host_marks: list[float] = []
        self.t_start = self.t_stop = 0.0

    def begin(self, t0: float) -> None:
        self.t0 = t0
        self.state = "wait"

    def _mark(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation(CLOCK):
            self.host_marks.append(time.perf_counter())

    def on_chunk(self, chunk) -> None:
        import jax
        if self.state == "wait" and \
                chunk.end >= self.t0 + 0.4 * self.seconds:
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no event per Python call
            opts.host_tracer_level = 1       # keeps TraceAnnotation spans
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._mark()
            self.t_start = time.perf_counter()
            self.state = "on"
        elif self.state == "on":
            self._mark()
            if time.perf_counter() >= self.t_start + self.trace_s:
                self._stop()

    def _stop(self) -> None:
        import jax
        self.t_stop = time.perf_counter()
        self._mark()
        jax.profiler.stop_trace()
        self.state = "done"

    def finish(self) -> None:
        if self.state == "on":
            self._stop()

    def reduce(self, window: records.Window) -> Summary:
        if self.state != "done":
            raise RuntimeError("the window ended before the trace began")
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"no trace written under {self.dir}")
        try:
            events = collect(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return reduce(events, self.host_marks, self.t_start, self.t_stop,
                      window.spans)


def load_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)
