"""Readings that the correctness limits are set from.

    python3 bench/tools/readings.py --workload <cell> --seconds <s> \
        --seeds 11,12,13

For each seed, in one process: the cell's set-up and a window of the cell's
own mix and load, then the comparison of the served tokens with the plain
reference (the sound reading), and the same prompts and tokens scored with
the reference in float8 (e4m3) put in the program's place (the control's
reading: at each position the token float8 puts first, judged by the
float32 reference).  One JSON line per seed.  The limit in the
configuration file lies between the largest sound reading and the smallest
control reading; PERF.md gives both.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--benchmark", default=None,
                    help="another BENCHMARK.json (rehearsals)")
    ap.add_argument("--traffic-dir", default=None)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU")
    args = ap.parse_args(argv)
    import run as bench_run
    bench_run.enable_cache(BENCH.parent)
    from harness import cell, check, spec, traffic
    c = spec.load_cell(BENCH.parent, args.workload,
                       benchmark_file=args.benchmark,
                       traffic_dir=args.traffic_dir)
    for seed in (int(x) for x in args.seeds.split(",")):
        s = cell.Session(BENCH.parent, c, seed, allow_cpu=args.allow_cpu)
        planned, warm = traffic.make_trace(c.traffic, seed=seed,
                                           seconds=args.seconds,
                                           vocab=s.sizes["vocab"])
        s.warm(planned, warm)
        w = s.window(planned, args.seconds)
        s.free()
        failed = sum(1 for r in w.reqs if r.n_tokens != r.max_new_tokens)
        got = {}
        for name, chooser in (("sound", None), ("control", "fp8")):
            t = time.perf_counter()
            v = check.compare(s.ref, c.config, s.sizes, s.key, w.reqs,
                              seed=seed, failed=failed, chooser=chooser)
            got[name] = {"max_logit_gap": v.checks["max_logit_gap"]["value"],
                         "tokens": v.checks["tokens_compared"]["value"],
                         "correct": v.correct,
                         "seconds": time.perf_counter() - t}
        print(json.dumps({"seed": seed, "requests": len(w.reqs),
                          "failed": failed, **got}), flush=True)
        del s
    return 0


if __name__ == "__main__":
    sys.exit(main())
