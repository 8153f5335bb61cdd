"""The latent-attention decode sweep's share of its roofline, over the
traced part of the window.

Kernel time: the device time of the named Pallas kernel
``mla_paged_decode_sweep`` (one call per layer per decode step; every
layer, the dense layer 0 too, attends through the latent pool).
Least time: the operations and bytes that the active slots' attention
needs at their context lengths (harness bookkeeping, as
``attn_roofline.batch`` takes them), against the chip's peaks.  Per slot,
layer and step at context ``ctx``:

  bytes  the latent rows of every page the positions touch, at
         ``kv_lora_rank + qk_rope_head_dim`` (576) lanes of 2 bytes: the
         pool's rows as stored, without the lane padding to 640 that HBM
         tiles add (not charged); plus the query row (heads x 576) in and
         the latent output (heads x 512) out, 2 bytes each.
  flops  2 * heads * (576 + 512) * ctx: scores over the latent and rope
         lanes, the probability-weighted sum over the latent.
"""
from harness import flops, programs, records

KERNEL = "mla_paged_decode_sweep"


def need(ctx: int, *, hq: int, r_kv: int, dr: int, page_size: int,
         nbytes: int = 2) -> tuple[int, int]:
    """(flops, bytes) of one query row per head over ``ctx`` latent rows."""
    R = r_kv + dr
    pages = -(-ctx // page_size)
    return (2 * hq * (R + r_kv) * ctx,
            pages * page_size * R * nbytes + hq * (R + r_kv) * nbytes)


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    s, e = run.sizes, run.engine
    chunk, ps = e["decode_chunk"], e["page_size"]
    cap = e["max_len"]
    kernel_s = sum(b - a for name, a, b in run.trace.op_events
                   if programs.kernel(name) == KERNEL)
    ctxs = records.decode_contexts(run.window, chunk, run.trace.t_start,
                                   run.trace.t_stop)
    if not kernel_s or not ctxs:
        return None
    need_flops = nbytes = 0
    for slots in ctxs:
        for ctx0 in slots:
            for step in range(chunk):
                f, b = need(min(ctx0 + step, cap), hq=s["hq"],
                            r_kv=s["r_kv"], dr=s["dr"], page_size=ps)
                need_flops += f
                nbytes += b
    share, _ = flops.roofline_share(need_flops * s["layers"],
                                    nbytes * s["layers"], kernel_s,
                                    run.peaks)
    return share
