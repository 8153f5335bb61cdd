"""Readings of the engine's own spans (``repro.serving.SpanRecorder``).

The spans are on ``time.perf_counter``, the harness's clock, so they line
up with the harness's records and, through the ``bench.clock`` markers,
with the device trace.  ``bench/run.py`` attaches no recorder; the tool
``bench/tools/engine_spans.py`` does.
"""
from __future__ import annotations

from harness import records


def join_p50_s(spans, reqs) -> float | None:
    """Median length of each window request's own (first) join."""
    joins: dict = {}
    for s in spans:
        if s.name == "join":
            joins.setdefault(s.rid, s)
    return records.pct([joins[r.rid].seconds for r in reqs
                        if r.rid in joins], 50)


def wait_in_joins_share(spans, reqs) -> float | None:
    """Share of the summed queue wait (scheduled arrival to the admission
    hook's yes) that other requests' joins cover."""
    joins = [s for s in spans if s.name == "join"]
    total = covered = 0.0
    for r in reqs:
        if r.admitted <= r.due:
            continue
        total += r.admitted - r.due
        covered += records.union_length(sorted(
            (max(s.start, r.due), min(s.end, r.admitted)) for s in joins
            if s.rid != r.rid and s.start < r.admitted and s.end > r.due))
    return 100.0 * covered / total if total > 0 else None


def label(t: float, spans, harness_spans, near=None) -> str:
    """The harness's admission wait covering ``t``, else the innermost
    engine span covering it as ``top/inner`` (``poll`` at the top level),
    else ``engine host``.  ``near``: the indices of ``spans`` that may
    cover ``t`` (default all)."""
    if any(s.start <= t < s.end for s in harness_spans
           if s.name == "admission wait"):
        return "admission wait"
    inner = None
    for i in range(len(spans)) if near is None else near:
        if spans[i].start <= t < spans[i].end:
            inner = i                    # spans open in order: last = inner
    if inner is None:
        return "engine host"
    top = inner
    while spans[top].parent >= 0:
        top = spans[top].parent
    return spans[top].name if top == inner else \
        f"{spans[top].name}/{spans[inner].name}"


def gaps(op_events, t_start: float, t_stop: float, spans,
         harness_spans) -> list:
    """Device idle intervals of ``[t_start, t_stop)`` summed by ``label``
    of their middle, longest first (``trace.reduce``'s gaps, labelled by
    the engine's spans)."""
    near = [i for i, s in enumerate(spans)
            if s.start < t_stop and s.end > t_start]
    waits = [s for s in harness_spans if s.name == "admission wait"]
    out: dict[str, float] = {}
    cur = t_start
    edges = [(a, b) for _, a, b in sorted(op_events, key=lambda e: e[1])]
    for a, b in edges + [(t_stop, t_stop)]:
        if a > cur:
            lab = label((cur + a) / 2, spans, waits, near)
            out[lab] = out.get(lab, 0.0) + (a - cur)
        cur = max(cur, b)
    return sorted(out.items(), key=lambda kv: -kv[1])
