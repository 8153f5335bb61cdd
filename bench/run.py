"""Serving benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the repository root on a machine with the chips the cell asks for.
Builds the configuration's weights from the seed on the device, warms the
engine up (set-up), serves the cell's traffic mix open-loop for ``--seconds``
(the window), drains, then checks what was served against the plain
reference.  The last line of stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of part
of the window), ``device``, ``breakdown`` (``--trace 1``) and ``checks``
(each number compared, with its limit; also the last lines on stderr).
Exits non-zero with no result when JAX finds no TPU or fewer chips than
the cell asks for.  JAX's persistent compilation cache lives in
``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import warnings  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def enable_cache(root: pathlib.Path) -> None:
    import jax
    path = root / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    enable_cache(ROOT)
    from harness import cell
    from repro.kernels.ops import FallbackWarning
    warnings.simplefilter("error", FallbackWarning)
    try:
        result = cell.run(ROOT, args.workload, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t_process=T_PROCESS)
    except cell.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']}, "
              f"{c['rule']})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
