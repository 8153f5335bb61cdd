"""Device time of one chunked suffix-prefill sweep (the engine's
``suffix_step`` program), over the traced part of the window.

A sweep calls the named paged verify kernel (``paged_verify_sweep``, or
``paged_verify_partials`` where it splits the KV) once per layer, so the
trace's calls fall into sweeps of ``layers`` calls (the trace starts and
stops between chunks, never inside a join).  A join's sweeps run back to
back: from the first call of one sweep to the first call of the next the
device runs exactly one sweep's program, the rest of the one and the head
of the next.  The device's busy time over those intervals, over their
number; an interval holding a request's first token (a join's end) spans
two joins and is left out.  Kernels without names: no reading."""
from harness import programs

VERIFY = ("paged_verify_sweep", "paged_verify_partials")


def read(run):
    t = run.trace
    if t is None:
        return None
    layers = run.sizes["layers"]
    calls = sorted(a for name, a, _ in t.op_events
                   if programs.kernel(name) in VERIFY)
    if not calls or len(calls) % layers:
        return None
    starts = calls[::layers]
    firsts = [r.first for r in run.window.reqs]
    merged = programs.busy_intervals(t.op_events)
    busy, n = 0.0, 0
    for a, b in zip(starts, starts[1:]):
        if any(a < f < b for f in firsts):
            continue
        busy += programs.busy_between(merged, a, b)
        n += 1
    return 1000.0 * busy / n if n else None
