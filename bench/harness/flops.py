"""Operations and bytes the algorithm needs, computed from shapes.

These are the least work a step could do, not what the program does: a
kernel that reads more, or computes on slots that hold no request, gets no
credit for it.  Shares of a roofline or a peak divide by measured time.
"""
from __future__ import annotations


def decode_attention(ctx: int, *, hq: int, hkv: int, hd: int,
                     page_size: int, kv_bytes: int = 2,
                     act_bytes: int = 2) -> tuple[int, int]:
    """One query row per head attending ``ctx`` cached positions of one
    sequence, in one layer: (flops, bytes).

    flops: q.k and p.v, 2 * hd each per head per position.
    bytes: the key and value rows of every page the positions touch, for
    each kv head, plus the query in and the output out."""
    pages = -(-ctx // page_size)
    flops = 4 * hq * hd * ctx
    nbytes = 2 * pages * page_size * hkv * hd * kv_bytes \
        + 2 * hq * hd * act_bytes
    return flops, nbytes


def matmul_flops_per_token(s: dict) -> int:
    """Projections, gated MLP and the vocabulary head for one token."""
    d, hq, hkv, hd, ff = s["d"], s["hq"], s["hkv"], s["hd"], s["ff"]
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff
    return 2 * (s["layers"] * per_layer + d * s["vocab"])


def decode_token_flops(s: dict, ctx: float) -> float:
    """Model flops of decoding one token at context ``ctx``."""
    return matmul_flops_per_token(s) + s["layers"] * 4 * s["hq"] * s["hd"] * ctx


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> tuple[float, str]:
    """(percent of the roofline reached, the bound that sets it)."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
