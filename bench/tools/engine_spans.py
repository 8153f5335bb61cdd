"""The engine's own spans over one window of a cell, beside the device trace.

    python3 bench/tools/engine_spans.py --workload <cell> --seed <n> \
        --seconds <s> --trace 0|1 [--record <file.json.gz>]

Set-up and the window are ``bench/run.py``'s (``harness.cell.Session``),
with a ``repro.serving.SpanRecorder`` attached to the engine for the window
only, which ``bench/run.py`` never does: the same seed through both gives
the recorder's cost end to end.  Prints one JSON line:

  metrics             the cell's end-to-end metrics (``--trace 0``) or its
                      per-layer metrics (``--trace 1``), read by the
                      benchmark's own readers
  span_seconds        by span name: count, seconds, self seconds
  join_p50_s          median length of a window request's own join span
  wait_in_joins_share share of the summed queue wait (arrival to the
                      admission hook's yes) that other requests' joins cover

and with ``--trace 1``:

  program_seconds     device busy seconds by jit program (the trace's
                      ``XLA Modules`` line)
  kernel_seconds      device seconds by named Pallas kernel
  device_ops          the ten longest device ops by self time, as
                      ``program:op``
  by_programs         the two device-trace metrics as the engine's spans
                      and the trace's programs define them: device time of
                      ``jit_suffix_step`` inside the traced joins over
                      their ``sweeps``, and of ``jit_decode_loop`` outside
                      the paged decode kernels inside the traced chunks
                      over their steps (what the readers estimate)
  alignment_s         each span's host annotation start, on the harness
                      clock, less its recorded start: count, median and
                      largest absolute value
  gaps, gaps_by_spans idle device time by the harness's labels, and by the
                      engine's spans

``--record`` writes a short piece of the traced window, with what the
readers need, for the CPU tests: one join with several suffix sweeps
(``join``) and one decode chunk (``chunk``), where the window has them.
No correctness check runs: spans change no token (``tests/test_spans.py``).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import warnings  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

ATTN = ("paged_decode_sweep", "paged_decode_partials", "split_kv_merge")


def span_seconds(rec) -> dict:
    out: dict[str, dict] = {}
    for i, s in enumerate(rec.spans):
        d = out.setdefault(s.name, {"n": 0, "seconds": 0.0,
                                    "self_seconds": 0.0})
        d["n"] += 1
        d["seconds"] += s.seconds
        d["self_seconds"] += rec.self_seconds(i)
    return out


def program_ops(events: dict, off: float, t0: float, t1: float) -> list:
    """(program, HLO text, start, end) of every op, on the harness clock,
    clipped to ``[t0, t1)``."""
    out = []
    for (name, s, d), prog in zip(events["ops"], events["programs"]):
        a, b = max(s * 1e-9 + off, t0), min((s + d) * 1e-9 + off, t1)
        if b > a:
            out.append((prog, name, a, b))
    return out


def busy(evs) -> float:
    from harness import programs
    return sum(b - a for a, b in programs.busy_intervals(evs))


def by_program(ops) -> tuple[dict, list]:
    """Device busy seconds by program, and the ten longest ops by self
    time as ``program:op``."""
    from harness import trace
    by: dict[str, list] = {}
    for prog, name, a, b in ops:
        by.setdefault(prog, []).append((name, a, b))
    seconds, selfs = {}, {}
    for prog, evs in sorted(by.items()):
        seconds[prog] = busy(evs)
        evs.sort(key=lambda e: (e[1], -e[2]))
        for op, sec in trace.self_times(evs).items():
            selfs[f"{prog.removeprefix('jit_')}:{op}"] = sec
    return seconds, sorted(selfs.items(), key=lambda kv: -kv[1])[:10]


def by_programs(ops, rec, t0, t1, decode_chunk) -> dict:
    from harness import programs
    inside = [sp for sp in rec.spans if t0 <= sp.start and sp.end <= t1]
    joins = [j for j in inside if j.name == "join" and j.counts["sweeps"]]
    chunks = [c for c in inside if c.name == "chunk"]

    def within(prog, spans):
        return [(n, a, b) for p, n, a, b in ops if p == prog
                and any(sp.start <= a < sp.end for sp in spans)]

    out = {}
    sweeps = sum(j.counts["sweeps"] for j in joins)
    if sweeps:
        out["suffix_sweep_ms"] = 1000 * busy(
            within("jit_suffix_step", joins)) / sweeps
    if chunks:
        loop = within("jit_decode_loop", chunks)
        kern = sum(b - a for n, a, b in loop if programs.kernel(n) in ATTN)
        out["decode_outside_attn_ms"] = 1000 * (busy(loop) - kern) / (
            len(chunks) * decode_chunk)
    return out


def piece(events, host_marks, window, rec, sizes, a: float, b: float,
          off: float) -> dict:
    """The trace's events and the records of ``[a, b)`` (harness clock);
    of the host annotations, the engine's spans only."""
    names = {s.name for s in rec.spans}
    keep = [k for k, (_, s, d) in enumerate(events["ops"])
            if s * 1e-9 + off < b and (s + d) * 1e-9 + off > a]
    return {
        "t_start": a, "t_stop": b, "sizes": sizes, "host_marks": host_marks,
        "events": {
            "ops": [events["ops"][k] for k in keep],
            "programs": [events["programs"][k] for k in keep],
            "marks": events["marks"],
            "annotations": [e for e in events["annotations"]
                            if e[0] in names and a <= e[1] * 1e-9 + off < b]},
        "chunks": [[c.end, c.wall_s, c.n_active, c.tokens_kept, c.steps]
                   for c in window.chunks],
        "firsts": [r.first for r in window.reqs],
        "spans": [[s.name, s.start, s.end, s.parent, s.rid, s.counts]
                  for s in rec.spans]}


def record(path, events, host_marks, window, rec, sizes, summary,
           off: float) -> None:
    t0, t1 = summary.t_start, summary.t_stop
    out = {}
    joins = [s for s in rec.spans if s.name == "join"
             and s.counts["sweeps"] >= 3 and t0 <= s.start and s.end <= t1]
    if joins:
        j = joins[0]
        out["join"] = piece(events, host_marks, window, rec, sizes,
                            j.start - 1e-4, j.end + 1e-4, off)
    ends = [c.end for c in window.chunks]
    inside = [k for k, c in enumerate(window.chunks)
              if k and t0 <= ends[k - 1] and c.end + 1e-4 <= t1]
    if inside:
        k = inside[0]
        out["chunk"] = piece(events, host_marks, window, rec, sizes,
                             ends[k - 1], ends[k] + 1e-4, off)
    with gzip.open(path, "wt") as f:
        json.dump(out, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--record", default=None,
                    help="gzipped JSON file for a piece of the trace")
    ap.add_argument("--benchmark", default=None,
                    help="another BENCHMARK.json (rehearsals)")
    ap.add_argument("--traffic-dir", default=None)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU; no number it prints is a "
                         "device number")
    args = ap.parse_args(argv)
    if args.record and not args.trace:
        ap.error("--record needs --trace 1")
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    import run as bench_run
    bench_run.enable_cache(ROOT)
    from harness import cell, engine_spans, programs, spec, traffic
    from harness import trace as tracing
    from repro.kernels.ops import FallbackWarning
    from repro.serving import SpanRecorder
    warnings.simplefilter("error", FallbackWarning)

    c = spec.load_cell(ROOT, args.workload, benchmark_file=args.benchmark,
                       traffic_dir=args.traffic_dir)
    s = cell.Session(ROOT, c, args.seed, allow_cpu=args.allow_cpu)
    planned, warm = traffic.make_trace(c.traffic, seed=args.seed,
                                       seconds=args.seconds,
                                       vocab=s.sizes["vocab"])
    s.warm(planned, warm)
    rec = s.engine.spans = SpanRecorder()
    tracer = tracing.WindowTracer(ROOT, args.seconds) if args.trace else None
    window = s.window(planned, args.seconds, tracer)
    out = {"workload": args.workload, "seed": args.seed,
           "trace": bool(args.trace), "device": s.device()}
    summary = events = None
    if tracer is not None:
        files = glob.glob(os.path.join(tracer.dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        events = programs.collect(files[0]) if files else None
        summary = tracer.reduce(window)
    run_ = cell.Run(s.sizes, s.bench["engine"], window,
                    window.start - T_PROCESS, s.peaks, summary)
    entries = c.per_layer if tracer is not None else c.end_to_end
    out["metrics"] = {m["name"]: spec.metric_reader(m["name"])(run_)
                      for m in entries}
    out["span_seconds"] = span_seconds(rec)
    out["join_p50_s"] = engine_spans.join_p50_s(rec.spans, window.reqs)
    out["wait_in_joins_share"] = engine_spans.wait_in_joins_share(
        rec.spans, window.reqs)
    if summary is not None and events is not None:
        t0, t1 = summary.t_start, summary.t_stop
        off = programs.clock_offset(events, tracer.host_marks)
        ops = program_ops(events, off, t0, t1)
        out["program_seconds"], out["device_ops"] = by_program(ops)
        kernels: dict[str, float] = {}
        for _, name, a, b in ops:
            k = programs.kernel(name)
            if k is not None:
                kernels[k] = kernels.get(k, 0.0) + (b - a)
        out["kernel_seconds"] = kernels
        out["by_programs"] = by_programs(ops, rec, t0, t1,
                                         s.bench["engine"]["decode_chunk"])
        res = programs.alignment(events, off, rec.spans, t0, t1)
        out["alignment_s"] = {
            "n": len(res),
            "median_abs": statistics.median(map(abs, res)) if res else None,
            "max_abs": max(map(abs, res)) if res else None}
        out["gaps"] = summary.gaps
        out["gaps_by_spans"] = engine_spans.gaps(
            summary.op_events, t0, t1, rec.spans, window.spans)
        if args.record:
            record(args.record, events, tracer.host_marks, window, rec,
                   s.sizes, summary, off)
    s.free()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
