"""Knee sweep: serve a cell's mix at several fixed rates on one engine.

    python3 bench/tools/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 0.2,0.3,0.4 [--seeds a,b,c] [--dump <dir>]

Each rate is one window of the mix at that many requests per second (all
else as the mix file says), on the same engine after one warm-up; before
each window the engine serves that window's own set-up trace, so its
pooled prefixes are cached as in a run of the cell.  Window ``i`` draws
its trace from seed ``seed + i``, or from the ``i``-th of ``--seeds``.
Prints one JSON line per rate: completed requests and tokens per second
inside the window, TTFT, TPOT and queue-wait tails, and the queue wait of
the first and last third of the arrivals.  A backlog that grows through
the window shows as a last third that waits far longer than the first;
the knee is the highest rate without one.  ``--dump`` writes each
window's requests (due, admitted, first and last token, lengths) as
``<dir>/<i>.jsonl``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", default=None,
                    help="one seed per rate, in place of seed + i")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--backlog", type=int, default=None,
                    help="requests due when each window opens (default: "
                         "the mix's)")
    ap.add_argument("--dump", default=None,
                    help="directory for each window's request records")
    ap.add_argument("--benchmark", default=None,
                    help="another BENCHMARK.json (rehearsals)")
    ap.add_argument("--traffic-dir", default=None)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU; no number it prints is a "
                         "device number")
    args = ap.parse_args(argv)
    import run as bench_run
    bench_run.enable_cache(BENCH.parent)
    from harness import cell, records, spec, traffic
    c = spec.load_cell(BENCH.parent, args.workload,
                       benchmark_file=args.benchmark,
                       traffic_dir=args.traffic_dir)
    rates = [float(r) for r in args.rates.split(",")]
    seeds = ([int(x) for x in args.seeds.split(",")] if args.seeds
             else [args.seed + i for i in range(len(rates))])
    if len(seeds) != len(rates):
        ap.error("--seeds needs one seed per rate")
    s = cell.Session(BENCH.parent, c, args.seed, allow_cpu=args.allow_cpu)
    plans = []
    for rate, seed in zip(rates, seeds):
        mix = copy.deepcopy(c.traffic)
        mix["rate"] = {"knee_req_per_s": rate, "share_of_knee": 1.0}
        if args.backlog is not None:
            mix["backlog"] = args.backlog
        plans.append((rate, seed, *traffic.make_trace(
            mix, seed=seed, seconds=args.seconds, vocab=s.sizes["vocab"])))
    s.warm([p for _, _, planned, _ in plans for p in planned], plans[0][3])
    cell.log(f"set-up {time.perf_counter() - T_PROCESS:.1f} s")
    dump = pathlib.Path(args.dump) if args.dump else None
    if dump:
        dump.mkdir(parents=True, exist_ok=True)
    for i, (rate, seed, planned, warm) in enumerate(plans):
        s.hook.due = None
        s.engine.run(s.requests(warm, (i + 1) * 10**9))
        t = time.perf_counter()
        w = s.window(planned, args.seconds)
        n = len(w.reqs)
        by_due = sorted(w.reqs, key=lambda r: r.due)
        third = max(n // 3, 1)
        wait = lambda rs: records.pct([r.admitted - r.due for r in rs], 50)
        done = sum(1 for r in w.reqs if r.finish < w.end)
        steps = sum(ch.steps for ch in w.chunks_in_window())
        print(json.dumps({
            "rate": rate, "seed": seed, "requests": n,
            "completed_in_window": done,
            "tokens_per_s": w.tokens_in_window() / w.seconds,
            "ttft_p50_s": records.pct(w.ttft(), 50),
            "ttft_p90_s": records.pct(w.ttft(), 90),
            "tpot_p50_s": records.pct(w.tpot(), 50),
            "tpot_p90_s": records.pct(w.tpot(), 90),
            "queue_wait_p90_s": records.pct(
                [r.admitted - r.due for r in w.reqs], 90),
            "prefill_p50_s": records.pct(
                [r.first - r.admitted for r in w.reqs], 50),
            "decode_step_ms": 1e3 * sum(
                ch.wall_s for ch in w.chunks_in_window()) / max(steps, 1),
            "wait_p50_first_third_s": wait(by_due[:third]),
            "wait_p50_last_third_s": wait(by_due[-third:]),
            "drain_s": max(r.finish for r in w.reqs) - w.end,
            "mean_active": sum(ch.n_active for ch in w.chunks_in_window())
            / max(len(w.chunks_in_window()), 1),
            "prefix_hit_rate": w.report.prefix_hit_rate,
            "compiles_in_window": s.meter.between(t, w.end),
        }), flush=True)
        if dump:
            with open(dump / f"{i}.jsonl", "w") as f:
                for r in w.reqs:
                    f.write(json.dumps({
                        "rid": r.rid, "due": r.due - w.start,
                        "admitted": r.admitted - w.start,
                        "first": r.first - w.start,
                        "finish": r.finish - w.start,
                        "prompt_len": r.prompt_len, "saved": r.saved,
                        "n_tokens": r.n_tokens}) + "\n")
                for ch in w.chunks:
                    f.write(json.dumps({
                        "chunk_end": ch.end - w.start, "wall_s": ch.wall_s,
                        "n_active": ch.n_active}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
