"""What a device trace says about programs and kernels by name.

On a TPU v5e under JAX 0.9 an XLA op event's name is its HLO instruction
text, and a Pallas kernel given ``pallas_call(name=...)`` is a
``tpu_custom_call`` whose instruction is named after it
(``%paged_decode_sweep.3 = bf16[...] custom-call(...)``): ``kernel``
reads that name from ``Summary.op_events`` as ``trace.reduce`` keeps them.
The program an op belongs to is the event on the device plane's
``XLA Modules`` line that holds it (``jit_<function name>(<id>)``; the op
events carry no program stat there), which ``trace.collect`` drops;
``collect`` here keeps it, with the host plane's annotations (the engine's
spans, when a recorder is attached), for tools that read the profile file
themselves.
"""
from __future__ import annotations

import bisect

from harness import trace

CUSTOM = 'custom_call_target="tpu_custom_call"'
MODULE_LINE = "XLA Modules"


def kernel(hlo: str) -> str | None:
    """The Pallas kernel name an op's HLO text carries; None for other
    ops."""
    if CUSTOM not in hlo:
        return None
    return hlo.partition(" = ")[0].lstrip("%").split(".", 1)[0]


def busy_intervals(op_events) -> list[tuple[float, float]]:
    """The union of (name, start, end) events as disjoint sorted
    intervals."""
    out: list[list[float]] = []
    for a, b in sorted((a, b) for _, a, b in op_events):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_between(merged, a: float, b: float) -> float:
    """Seconds of ``[a, b)`` that disjoint sorted intervals cover."""
    i = max(bisect.bisect_right(merged, (a, float("inf"))) - 1, 0)
    total = 0.0
    for x, y in merged[i:]:
        if x >= b:
            break
        total += max(0.0, min(y, b) - max(x, a))
    return total


def collect(path: str) -> dict:
    """``trace.collect``'s events plus, in parallel to ``ops``, each op's
    program (``programs``: the ``jit_*`` module whose execution holds the
    op's start, "" where none does), and every host-plane event that is not
    a clock marker (``annotations``: name, start and duration in ns)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, mods, marks, notes = [], [], [], []
    for plane in data.planes:
        if plane.name == trace.DEVICE_PLANE:
            for line in plane.lines:
                if line.name == trace.OP_LINE:
                    ops += [[e.name, e.start_ns, e.duration_ns]
                            for e in line.events]
                elif line.name == MODULE_LINE:
                    mods += [(e.start_ns, e.start_ns + e.duration_ns,
                              e.name.split("(", 1)[0]) for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace.CLOCK:
                        marks.append(e.start_ns)
                    else:
                        notes.append([e.name, e.start_ns, e.duration_ns])
    mods.sort()
    starts = [a for a, _, _ in mods]
    progs = []
    for _, s, _ in ops:
        k = bisect.bisect_right(starts, s) - 1
        progs.append(mods[k][2] if k >= 0 and s < mods[k][1] else "")
    return {"ops": ops, "programs": progs, "marks": sorted(marks),
            "annotations": notes}


def clock_offset(events: dict, host_marks: list[float]) -> float:
    """Seconds to add to trace time to get the harness clock: the median
    marker difference, as ``trace.reduce`` takes it."""
    n = min(len(events["marks"]), len(host_marks))
    if n == 0:
        raise RuntimeError("trace holds no clock markers")
    offs = sorted(host_marks[k] - events["marks"][k] * 1e-9
                  for k in range(n))
    return offs[n // 2]


def alignment(events: dict, off: float, spans, t_start: float,
              t_stop: float) -> list[float]:
    """For each recorded span that lies inside ``[t_start, t_stop)`` (an
    annotation still open when the profiler stops is not written), the
    start of the nearest host annotation of its name, on the harness
    clock, less the span's recorded start."""
    notes: dict[str, list[float]] = {}
    for name, s, _ in events["annotations"]:
        notes.setdefault(name, []).append(s * 1e-9 + off)
    for v in notes.values():
        v.sort()
    out = []
    for sp in spans:
        v = notes.get(sp.name)
        if not v or not t_start <= sp.start <= sp.end < t_stop:
            continue
        i = bisect.bisect_left(v, sp.start)
        out.append(min((v[j] - sp.start for j in (i - 1, i)
                        if 0 <= j < len(v)), key=abs))
    return out
