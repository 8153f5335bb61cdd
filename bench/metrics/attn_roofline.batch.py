"""The paged decode attention kernel's share of its roofline, over the
traced part of the window.

Kernel time: the device time of the Pallas paged decode sweep, picked from
the trace by its HLO signature (a ``tpu_custom_call`` whose operands start
with the (slots, blocks) block table and the (slots,) positions and whose
result is (slots, q heads, 1, head dim)); one call per layer per step.
Least time: the operations and bytes that the active slots' attention needs
at their context lengths (harness bookkeeping), against the chip's peaks.
"""
import re

from harness import flops, records


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    s, e = run.sizes, run.engine
    B, ps, chunk = e["n_slots"], e["page_size"], e["decode_chunk"]
    nb = -(-e["max_len"] // ps)
    sig = re.compile(
        r"= bf16\[%d,%d,1,%d\]\{[^}]*\} custom-call\(s32\[%d,%d\]\{[^}]*\} "
        r"[^,]+, s32\[%d\]" % (B, s["hq"], s["hd"], B, nb, B))
    kernel_s = sum(b - a for name, a, b in run.trace.op_events
                   if 'custom_call_target="tpu_custom_call"' in name
                   and sig.search(name))
    ctxs = records.decode_contexts(run.window, chunk, run.trace.t_start,
                                   run.trace.t_stop)
    if not kernel_s or not ctxs:
        return None
    need_flops = nbytes = 0
    for slots in ctxs:
        for ctx0 in slots:
            for step in range(chunk):
                f, b = flops.decode_attention(
                    min(ctx0 + step, nb * ps), hq=s["hq"], hkv=s["hkv"],
                    hd=s["hd"], page_size=ps)
                need_flops += f
                nbytes += b
    share, _ = flops.roofline_share(need_flops * s["layers"],
                                    nbytes * s["layers"], kernel_s,
                                    run.peaks)
    return share
