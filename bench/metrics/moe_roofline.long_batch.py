"""The decode steps' routed-expert grouped matmuls' share of their
roofline, over the traced part of the window.

Kernel time: the device time of the grouped matmuls XLA makes of
``jax.lax.ragged_dot`` in the decode loop, picked from the trace by their
HLO signature: a ``tpu_custom_call`` whose result is (slots * top_k rows,
width) and whose operands hold those rows and the held experts' stacked
weights, (held, hidden, expert width) for gate and up, (held, expert
width, hidden) for down; three calls per expert layer per step.  The
float32 -> bfloat16 casts of the expert weights that feed them are
separate ops, not the kernel's, and are not counted.
Least time: against the chip's peaks,

  flops  2 * 3 * rows * hidden * expert width, where rows are the
         (token, expert) rows the held experts computed per decode step:
         the program's ``EngineReport.expert_rows_decode`` over the run's
         decode steps (every slot computes every step, so this is the
         run's mean), times the traced steps;
  bytes  every held expert's three weight matrices at 2 bytes a
         parameter each step and layer (an expert given no row in a step
         is rare, about e^-6 at 6 rows an expert, and is charged all the
         same), plus the rows in and out of the three matmuls at 2 bytes.
"""
import re

from harness import flops


def _signatures(rows: int, d: int, ff: int, held: int):
    shape = r"bf16\[%d,%d\]\{[^}]*\} custom-call\(.*"
    return (re.compile(r"= " + shape % (rows, ff) + r"bf16\[%d,%d,%d\]"
                       % (held, d, ff)),
            re.compile(r"= " + shape % (rows, d) + r"bf16\[%d,%d,%d\]"
                       % (held, ff, d)))


def read(run):
    t, w = run.trace, run.window
    if t is None or run.peaks is None or w.report is None:
        return None
    s, e = run.sizes, run.engine
    d, ff, held = s["d"], s["moe_ff"], s["held"]
    sigs = _signatures(e["n_slots"] * s["top_k"], d, ff, held)
    kernel_s = sum(b - a for name, a, b in t.op_events
                   if 'custom_call_target="tpu_custom_call"' in name
                   and any(sig.search(name) for sig in sigs))
    rep = w.report
    run_steps = rep.n_chunks * e["decode_chunk"]
    steps = sum(c.steps for c in w.chunks
                if t.t_start <= c.end - c.wall_s and c.end <= t.t_stop)
    if not kernel_s or not run_steps or not steps:
        return None
    rows = rep.expert_rows_decode / run_steps * steps
    layers = s["layers"] - s["dense_layers"]
    need_flops = 6 * rows * d * ff
    nbytes = 3 * held * d * ff * 2 * steps * layers + rows * 6 * (d + ff)
    share, _ = flops.roofline_share(need_flops, nbytes, kernel_s, run.peaks)
    return share
