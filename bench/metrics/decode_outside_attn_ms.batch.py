"""Device time per decode step of the fused decode loop outside the named
paged decode attention kernels (``paged_decode_sweep``,
``paged_decode_partials``, ``split_kv_merge``): projections, MLP, norms,
head, sampling and the K/V pool copies around the kernel.

A chunk's device work lies between the harness's last reading before its
dispatch (the previous chunk's end, or a join's first token; a join's
device work ends with its first token) and the chunk's end.  Chunks whose
dispatch (``ChunkStats.wall_s`` before their end) lies in the traced part
of the window count: device busy time there less the kernels' time, over
their decode steps.  Kernels without names: no reading."""
import bisect

from harness import programs

ATTN = ("paged_decode_sweep", "paged_decode_partials", "split_kv_merge")


def read(run):
    t, w = run.trace, run.window
    if t is None:
        return None
    kern = programs.busy_intervals(
        [e for e in t.op_events if programs.kernel(e[0]) in ATTN])
    if not kern:
        return None
    merged = programs.busy_intervals(t.op_events)
    marks = sorted([c.end for c in w.chunks] + [r.first for r in w.reqs])
    outside, steps = 0.0, 0
    for c in w.chunks:
        start = c.end - c.wall_s
        if not t.t_start <= start < c.end <= t.t_stop:
            continue
        i = bisect.bisect_left(marks, start)
        lo = marks[i - 1] if i else t.t_start
        outside += programs.busy_between(merged, lo, c.end) \
            - programs.busy_between(kern, lo, c.end)
        steps += c.steps
    return 1000.0 * outside / steps if steps else None
