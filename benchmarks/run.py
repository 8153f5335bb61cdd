"""Benchmark harness — one entry per paper table/figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig2,roofline]

Prints ``name,value,derived`` CSV lines and saves JSON artifacts.  The
serving-path jobs (decode / serve / spec) additionally write compact
machine-readable ``BENCH_<name>.json`` trajectory files at the repo root
(tok/s, J/token, acceptance) so the perf trajectory is tracked across PRs
— diff them in review like any other artifact, or print the full
git-SHA-stamped history table with::

    PYTHONPATH=src python -m benchmarks.run trajectory [bench ...]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
ART = ROOT / "artifacts" / "bench"

# headline perf-trajectory schema per serving-path job: every field must be
# a plain number so cross-PR diffs stay line-per-metric
TRAJECTORY = {
    "decode": lambda r: {
        "tok_per_s": r["tok_per_s"],
        "speedup_vs_per_token": r["speedup"],
        "j_per_token": r["j_per_token_cap100"],
        "j_per_token_deep_cap": r["j_per_token_deep_cap"],
    },
    "serve": lambda r: {
        "tok_per_s": r["tok_per_s"],
        "j_per_token": r["engine"]["j_per_token"],
        "j_per_token_ratio_vs_static": r["j_per_token_ratio"],
        "p50_latency_ratio_vs_static": r["p50_latency_ratio"],
    },
    "spec": lambda r: {
        "tok_per_s": r["tok_per_s"],
        "speedup_vs_plain": r["speedup"],
        "best_k": r["best_k"],
        "acceptance": r["acceptance"],
        "j_per_accepted_token": r["j_per_token"],
        "j_per_token_plain": r["j_per_token_plain"],
    },
    "prefix": lambda r: {
        "tok_per_s": r["tok_per_s"],
        "prefix_hit_rate": r["prefix_hit_rate"],
        "prefill_tokens_saved": r["prefill_tokens_saved"],
        "n_preemptions": r["n_preemptions"],
        "j_per_token_ratio_vs_plain": r["j_per_token_ratio"],
        "p50_latency_ratio_vs_plain": r["p50_latency_ratio"],
    },
    "chaos": lambda r: {
        "tok_per_s": r["tok_per_s"],
        "tokens_lost": r["tokens_lost"],
        "n_restores": r["n_restores"],
        "requests_requeued": r["requests_requeued"],
        "recovery_latency_s": r["recovery_latency_s"],
        "degraded_steps": r["degraded_steps"],
        "j_per_token_overhead_vs_faultfree": r["j_per_token_overhead"],
    },
    "kernel": lambda r: {
        "deep_speedup_vs_single_split": r["deep_speedup"],
        "deep_kv_len": r["deep_kv_len"],
        "deep_best_splits": r["deep_best_splits"],
        "shallow_auto_ratio": r["shallow_auto_ratio"],
        "max_exactness_err": r["max_exactness_err"],
    },
    "mla": lambda r: {
        "deep_speedup_vs_single_split": r["deep_speedup"],
        "deep_kv_len": r["deep_kv_len"],
        "deep_best_splits": r["deep_best_splits"],
        "kv_bytes_per_token": r["kv_bytes_per_token"],
        "kv_bytes_ratio_vs_gqa_eq": r["kv_bytes_ratio"],
        "transfer_j_per_token": r["transfer_j_per_token"],
        "max_exactness_err": r["max_exactness_err"],
    },
    "kvtier": lambda r: {
        "tok_per_s": r["tok_per_s"],
        "logical_pool_ratio": r["logical_pool_ratio"],
        "effective_hit_rate": r["effective_hit_rate"],
        "n_preemptions": r["n_preemptions"],
        "n_demotions": r["n_demotions"],
        "n_promotions": r["n_promotions"],
        "transfer_j": r["transfer_j"],
        "j_per_token_ratio_vs_evict": r["j_per_token_ratio"],
        "int8_oracle_max_err": r["int8_oracle_max_err"],
    },
}

# one human-readable headline CSV line per trajectory job (printed for CI
# logs next to the machine-readable artifact)
HEADLINE = {
    "decode": lambda r: (f"decode.tok_per_s,{r['tok_per_s']:.1f},"
                         f"fused loop, {r['speedup']:.2f}x over per-token "
                         "host loop (largest cache)"),
    "serve": lambda r: (f"serve.tok_per_s,{r['tok_per_s']:.1f},"
                        f"engine vs static: {r['j_per_token_ratio']:.2f}x "
                        f"J/token, {r['p50_latency_ratio']:.2f}x p50 latency"),
    "spec": lambda r: (f"spec.tok_per_s,{r['tok_per_s']:.1f},"
                       f"{r['speedup']:.2f}x over plain fused loop at "
                       f"K={r['best_k']} (replay acceptance "
                       f"{r['acceptance']:.2f})"),
    "prefix": lambda r: (f"prefix.hit_rate,{r['prefix_hit_rate']:.2f},"
                         f"{r['prefill_tokens_saved']} prefill tokens "
                         f"saved; {r['j_per_token_ratio']:.2f}x J/token, "
                         f"{r['p50_latency_ratio']:.2f}x p50 vs no-sharing"),
    "chaos": lambda r: (f"chaos.tokens_lost,{r['tokens_lost']},"
                        f"{r['n_restores']} crash-restores, "
                        f"{r['requests_requeued']} requeued, "
                        f"{r['degraded_steps']} capped steps; "
                        f"{r['j_per_token_overhead']:.2f}x J/token "
                        "vs fault-free"),
    "kernel": lambda r: (f"kernel.deep_speedup,{r['deep_speedup']:.2f},"
                         f"two-stage split-KV at KV={r['deep_kv_len']} "
                         f"(S={r['deep_best_splits']}); shallow auto ratio "
                         f"{r['shallow_auto_ratio']:.2f}x, exactness "
                         f"{r['max_exactness_err']:.1e}"),
    "mla": lambda r: (f"mla.deep_speedup,{r['deep_speedup']:.2f},"
                      f"latent split sweep at KV={r['deep_kv_len']} "
                      f"(S={r['deep_best_splits']}); "
                      f"{r['kv_bytes_ratio']:.1f}x KV bytes/token vs "
                      f"GQA-equivalent, exactness "
                      f"{r['max_exactness_err']:.1e}"),
    "kvtier": lambda r: (f"kvtier.j_per_token_ratio,"
                         f"{r['j_per_token_ratio']:.2f}x,"
                         f"{r['logical_pool_ratio']:.1f}x logical pool "
                         f"(int8 + host tier) vs evict-and-recompute; "
                         f"{r['n_demotions']} paged out, "
                         f"{r['n_preemptions']} preemptions"),
}


def _git_sha() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "HEAD"], cwd=ROOT,
            stderr=subprocess.DEVNULL).decode().strip()
    except Exception:
        return "unknown"


def _write_trajectory(name: str, res: dict, quick: bool) -> None:
    if quick:
        # --quick shrinks the workload (CI smoke); overwriting the repo-root
        # artifact would make cross-PR diffs compare incommensurate runs
        print(f"{name}.trajectory,skipped,--quick runs do not rewrite "
              f"BENCH_{name}.json")
        return
    path = ROOT / f"BENCH_{name}.json"
    payload = {"bench": name, "git_sha": _git_sha(),
               **TRAJECTORY[name](res)}
    # a trajectory artifact without its commit stamp can't be diffed across
    # PRs — refuse to write one (regenerate from a git checkout instead)
    assert payload.get("git_sha"), f"BENCH_{name}.json payload missing git_sha"
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"{name}.trajectory,{path.name},machine-readable perf artifact")


def _bench_versions(path: pathlib.Path) -> list[dict]:
    """Every committed version of one BENCH_*.json, oldest first, plus the
    working-tree copy when it differs from HEAD's.  Each version carries
    the artifact's own ``git_sha`` stamp (the commit it was *generated*
    at), which is what the table keys on."""
    versions: list[dict] = []
    seen: set[str] = set()
    try:
        commits = subprocess.check_output(
            ["git", "log", "--reverse", "--format=%H", "--", path.name],
            cwd=ROOT, stderr=subprocess.DEVNULL).decode().split()
    except Exception:
        commits = []
    for commit in commits:
        try:
            blob = subprocess.check_output(
                ["git", "show", f"{commit}:{path.name}"], cwd=ROOT,
                stderr=subprocess.DEVNULL)
            rec = json.loads(blob)
        except Exception:
            continue
        sha = rec.get("git_sha", commit)
        if sha not in seen:
            seen.add(sha)
            versions.append(rec)
    try:
        rec = json.loads(path.read_text())
        if rec.get("git_sha") not in seen:
            versions.append(rec)
    except Exception:
        pass
    return versions


def trajectory_main(argv) -> int:
    """``benchmarks.run trajectory [bench ...]`` — print the perf history
    recorded by the BENCH_*.json artifacts as one table per bench: a row
    per generating commit (git-SHA-stamped), a column per metric.  The
    artifacts are committed with the code, so the table is exactly the
    cross-PR diff review sees, assembled from git history."""
    names = set(argv)
    files = sorted(ROOT.glob("BENCH_*.json"))
    if names:
        files = [f for f in files if f.stem[len("BENCH_"):] in names]
    if not files:
        print("no BENCH_*.json artifacts"
              + (f" matching {sorted(names)}" if names else "")
              + " — run the serving-path benchmarks first")
        return 1
    for path in files:
        bench = path.stem[len("BENCH_"):]
        versions = _bench_versions(path)
        if not versions:
            continue
        metrics = [k for k in versions[-1] if k not in ("bench", "git_sha")]
        print(f"# ---- {bench} trajectory ({len(versions)} recorded runs) "
              "----")
        head = "  ".join(f"{m:>24}" for m in metrics)
        print(f"{'git_sha':>10}  {head}")
        for rec in versions:
            row = "  ".join(
                f"{rec[m]:>24.6g}" if isinstance(rec.get(m), (int, float))
                else f"{str(rec.get(m, '-')):>24}" for m in metrics)
            print(f"{str(rec.get('git_sha', '?'))[:10]:>10}  {row}")
    return 0


def main(argv=None) -> int:
    if argv is None:
        import sys
        argv = sys.argv[1:]
    if argv and argv[0] == "trajectory":
        # subcommand, dispatched before the flat argparse: reads the
        # committed BENCH_*.json history instead of running anything
        return trajectory_main(argv[1:])
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced model set / steps (CI mode)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset, e.g. fig2,roofline")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (chaos_serve, ctrl_overhead, decode_kernel,
                            decode_throughput, fig2_energy, fig3_overhead,
                            fig4_capping, fig5_edxp, fig6_tradeoff, kv_tier,
                            prefix_cache, roofline, serve_engine, spec_decode)
    ART.mkdir(parents=True, exist_ok=True)
    jobs = {
        "fig2": lambda: fig2_energy.main(quick=args.quick),
        "fig3": lambda: fig3_overhead.main(quick=args.quick),
        "fig4": lambda: fig4_capping.main(quick=args.quick),
        "fig5": lambda: fig5_edxp.main(quick=args.quick),
        "fig6": lambda: fig6_tradeoff.main(quick=args.quick),
        "ctrl": lambda: ctrl_overhead.main(quick=args.quick),
        "decode": lambda: decode_throughput.main(quick=args.quick),
        "serve": lambda: serve_engine.main(quick=args.quick),
        "spec": lambda: spec_decode.main(quick=args.quick),
        "prefix": lambda: prefix_cache.main(quick=args.quick),
        "kvtier": lambda: kv_tier.main(quick=args.quick),
        "chaos": lambda: chaos_serve.main(quick=args.quick),
        "kernel": lambda: decode_kernel.main(quick=args.quick),
        "mla": lambda: decode_kernel.main_mla(quick=args.quick),
        "roofline": lambda: [roofline.main(m) for m in ("single", "multi")],
    }
    failures = 0
    for name, job in jobs.items():
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"# ---- {name} ----")
        try:
            res = job()
            (ART / f"{name}.json").write_text(json.dumps(res, default=str))
            if name in TRAJECTORY:
                _write_trajectory(name, res, args.quick)
            print(f"{name}.seconds,{time.time()-t0:.1f},ok")
            if name in HEADLINE:       # headline perf-trajectory line for CI
                print(HEADLINE[name](res))
        except Exception as e:                         # keep the harness alive
            failures += 1
            print(f"{name}.seconds,{time.time()-t0:.1f},"
                  f"FAIL {type(e).__name__}: {str(e)[:200]}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
