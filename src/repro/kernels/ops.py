"""jit-ready kernel entry points with backend dispatch.

Every op has three interchangeable implementations:

  * ``ref``      — the pure-jnp oracle (kernels/ref.py), O(S^2) memory.
  * ``chunked``  — pure-jnp flash-style chunked algorithm.  This is the
                   DEFAULT: it is what the dry-run lowers (CPU stand-in
                   devices cannot lower Pallas TPU kernels) and it encodes
                   the same tiling the Pallas kernels use, so the roofline
                   derived from its HLO carries over.
  * ``pallas``   — the TPU kernel (kernels/flash_attention.py, ssd_scan.py,
                   rmsnorm.py, decode_attention.py), validated in interpret
                   mode on CPU.

Decode attention (the serving hot path) has its own backend axis on
``KernelPolicy`` (``decode``): ``jnp`` is the chunk-free CPU default,
``ref`` the whole-cache fp32 oracle, ``pallas`` the split-K TPU kernel.
The same axis drives both cache layouts — ``decode_attention`` (ring
buffer) and ``paged_decode_attention`` (block-table page pool, the
continuous-batching serving engine's layout) — and their multi-query
variants (``verify_attention`` / ``paged_verify_attention``: Q queries
share one cache sweep).  The multi-query paged sweep serves TWO callers
through one dispatch entry: speculative verify (Q = K+1 drafts + bonus)
and the prefix-sharing engine's *chunked paged prefill* (Q = suffix
chunk, scoring uncached prompt tokens against shared prefix pages — see
``transformer.prefill_suffix``); no separate prefill kernel exists or is
needed.

Models call these wrappers; the backend is chosen by ``KernelPolicy``.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.ref import NEG_INF


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Which implementation backs each op."""
    attention: str = "auto"      # auto | ref | chunked | pallas | pallas_interpret
    ssd: str = "auto"
    rmsnorm: str = "auto"
    decode: str = "auto"         # auto | ref | jnp | pallas | pallas_interpret
    q_chunk: int = 1024
    k_chunk: int = 1024
    ssd_chunk: int = 128
    decode_k_chunk: int = 256    # split-K block for the Pallas decode kernel
    kv_splits: str | int = "auto"  # two-stage split count: "auto" | int (1 = single-stage)
    kv_dtype: str = "bfloat16"   # KV-pool storage: "bfloat16" | "int8" (per-row fp32 scales)


DEFAULT_POLICY = KernelPolicy()


# ==========================================================================
# Two-stage split-KV: occupancy heuristic + jnp partial/merge helpers
# ==========================================================================
def _sweep_executors() -> int:
    """How many independent executors can run decode-sweep grid cells
    concurrently.  On TPU the (b, h) cells map onto cores/devices; on the
    CPU stand-in, host threads."""
    if jax.default_backend() == "tpu":
        return jax.local_device_count()
    return os.cpu_count() or 1


def choose_kv_splits(batch: int, kv_len: int, q_heads: int,
                     n_cores: int | None = None, *,
                     block: int = 256, max_splits: int = 16) -> int:
    """Occupancy-model heuristic for the two-stage split-KV sweep.

    The stage-1 grid has ``batch * q_heads * num_kv_splits`` independent
    cells.  When ``batch * q_heads`` already oversubscribes the executors
    (the common high-batch serving case), splitting only adds stage-2 merge
    traffic — return 1, which is bit-for-bit today's single-stage sweep.
    Only when the natural grid *underfills* the machine (deep cache, low
    batch — exactly the power-capped latency-bound regime) do we split,
    just enough to cover the executors (2x for load balance), never past
    the number of k-blocks or ``max_splits`` (merge cost grows with S).
    """
    if n_cores is None:
        n_cores = _sweep_executors()
    cells = int(batch) * int(q_heads)
    n_blocks = -(-int(kv_len) // max(1, int(block)))
    if cells >= 2 * n_cores or n_blocks <= 1:
        return 1
    return max(1, min(-(-2 * n_cores // max(1, cells)), n_blocks, max_splits))


def effective_kv_len(kv_len: int, window: int = 0) -> int:
    """Clip the logical KV length to the attention window for occupancy
    decisions.  A windowed layer never attends past ``window`` keys no
    matter how deep the logical position is, so the split heuristic must
    see ``min(kv_len, window)`` — a 32k-position sliding-window cache is a
    SHALLOW sweep, and splitting it only adds merge traffic."""
    kv_len = int(kv_len)
    return min(kv_len, int(window)) if window > 0 else kv_len


def _resolve_kv_splits(policy: KernelPolicy, batch: int, kv_len: int,
                       q_heads: int, *, block: int) -> int:
    if policy.kv_splits == "auto":
        return choose_kv_splits(batch, kv_len, q_heads, block=block)
    return max(1, int(policy.kv_splits))


def _lse_merge_jnp(partial: jax.Array, lse: jax.Array) -> jax.Array:
    """Online-softmax merge over the split axis: ``partial (..., S, Dv)``
    + ``lse (..., S)`` -> ``(..., Dv)``.  Exact: each split's normalized
    partial re-weighted by ``exp(lse_s - max_s lse)`` reconstructs the
    unsplit numerator/denominator pair."""
    m = jnp.max(lse, axis=-1, keepdims=True)
    w = jnp.exp(lse - m)                               # (..., S)
    den = jnp.maximum(jnp.sum(w, axis=-1), 1e-30)
    acc = jnp.sum(partial * w[..., None], axis=-2)     # (..., Dv)
    return acc / den[..., None]


def _split_attend_jnp(s: jax.Array, vf: jax.Array, n_splits: int) -> jax.Array:
    """Two-stage softmax-weighted sum for the jnp backend: partition the
    masked score axis into ``n_splits`` slices, emit per-split normalized
    partials + LSE, then LSE-merge.  Mirrors the Pallas partial contract
    (ragged last split padded with masked scores).

    s:  (B, Hkv, G, R, K) fp32 masked scores (invalid entries = NEG_INF)
    vf: (B, K, Hkv, Dv)   values in logical key order
    -> (B, Hkv, G, R, Dv) fp32
    """
    B, Hkv, G, R, K = s.shape
    Dv = vf.shape[-1]
    S = max(1, min(int(n_splits), K))
    kps = -(-K // S)
    pad = S * kps - K
    sp = jnp.pad(s, [(0, 0)] * 4 + [(0, pad)], constant_values=NEG_INF)
    vp = jnp.pad(vf, [(0, 0), (0, pad), (0, 0), (0, 0)])
    sp = sp.reshape(B, Hkv, G, R, S, kps)
    vp = vp.reshape(B, S, kps, Hkv, Dv)
    m = jnp.max(sp, axis=-1)                           # (B,h,g,R,S)
    p = jnp.exp(sp - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgrsk,bskhd->bhgrsd", p, vp,
                     preferred_element_type=jnp.float32)
    partial = acc / jnp.maximum(l, 1e-30)[..., None]
    # fully-masked splits: every score is NEG_INF, so p = exp(0) = 1 and l
    # counts the padding — the m-guard (not l > 0) is what zeroes their
    # merge weight; the raw-l denominator above keeps partial finite.
    lse = jnp.where(m > 0.5 * NEG_INF,
                    m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
    return _lse_merge_jnp(partial, lse)


class FallbackWarning(RuntimeWarning):
    """A requested path ran as a different one: a Pallas kernel as jnp, or
    an engine feature (int8 pages, host tier) as the plain layout.  Every
    such fallback warns with this category, so a run that must prove the
    requested path ran (``chip_smoke.py``) turns them all into errors."""


_KPOS_FALLBACK_WARNED: set[str] = set()


def _warn_k_pos_fallback(entry: str) -> None:
    """One-time (per entry point) warning when a custom ``k_pos`` costs the
    caller the Pallas fast path."""
    if entry in _KPOS_FALLBACK_WARNED:
        return
    _KPOS_FALLBACK_WARNED.add(entry)
    warnings.warn(
        f"{entry}: custom k_pos slot layout disables the Pallas decode "
        "kernel (it derives ring positions from pos, assuming the canonical "
        "slot = p % C layout); falling back to the jnp backend for this "
        "call", FallbackWarning, stacklevel=3)


_KV_DTYPE_FALLBACK_WARNED: set[str] = set()


def warn_kv_dtype_fallback(family: str, reason: str) -> None:
    """One-time (per model family) warning when ``kv_dtype=int8`` was
    requested but the family's verify/commit path cannot run quantized and
    silently falls back to the unquantized pools."""
    if family in _KV_DTYPE_FALLBACK_WARNED:
        return
    _KV_DTYPE_FALLBACK_WARNED.add(family)
    warnings.warn(
        f"kv_dtype=int8 requested for model family {family!r} but {reason}; "
        "falling back to unquantized (bfloat16) KV pools for this engine",
        FallbackWarning, stacklevel=3)


# ==========================================================================
# Attention
# ==========================================================================
def _chunk_attend(q, k, v, carry, mask, scale, logit_cap):
    """One (q-chunk, k-chunk) online-softmax update.  All fp32.

    q: (B,Hkv,G,Cq,D)  k: (B,Hkv,Ck,D)  v: (B,Hkv,Ck,Dv)
    carry = (m, l, acc): ((B,Hkv,G,Cq), (B,Hkv,G,Cq), (B,Hkv,G,Cq,Dv))
    mask: (Cq, Ck) bool or None.
    """
    m, l, acc = carry
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhgqk,bhkd->bhgqd", p, v, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def flash_attention_jnp(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0, logit_cap: float = 0.0,
    scale: float | None = None, q_offset: int = 0,
    q_chunk: int = 1024, k_chunk: int = 1024,
) -> jax.Array:
    """Flash-style chunked attention, pure jnp.

    Memory is O(Cq*Ck) instead of O(Sq*Sk).  Causal/window structure is
    exploited *structurally*: k-chunks entirely outside [q_lo - window,
    q_hi] are never computed, so HLO FLOPs reflect the real triangle —
    this is what makes the roofline compute term honest for prefill_32k.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5

    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    if Sq % q_chunk or Sk % k_chunk:
        # fall back for ragged shapes (smoke tests)
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  logit_cap=logit_cap, scale=scale,
                                  q_offset=q_offset)

    # keep q/k/v in their storage dtype; the per-chunk einsums accumulate in
    # fp32 via preferred_element_type (pre-casting everything to fp32 would
    # triple the HBM residency of the whole tensor — measured 2.4 GB/layer
    # extra on deepseek-v2 prefill)
    qf = q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 3, 1, 4)
    kf = k.transpose(0, 2, 1, 3)                         # (B,Hkv,Sk,D)
    vf = v.transpose(0, 2, 1, 3)                         # (B,Hkv,Sk,Dv)

    n_q = Sq // q_chunk
    outs = []
    for i in range(n_q):                                  # static python loop
        q_lo = q_offset + i * q_chunk
        q_hi = q_lo + q_chunk - 1
        # visible k range for this q chunk
        k_hi = min(Sk, q_hi + 1) if causal else Sk
        k_lo = max(0, q_lo - window + 1) if window > 0 else 0
        j_lo, j_hi = k_lo // k_chunk, -(-k_hi // k_chunk)  # ceil
        j_lo = min(j_lo, j_hi - 1)
        qi = qf[:, :, :, i * q_chunk:(i + 1) * q_chunk]   # (B,Hkv,G,Cq,D)

        m0 = jnp.full((B, Hkv, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_chunk, Dv), jnp.float32)

        q_pos = q_lo + jnp.arange(q_chunk)[:, None]

        def body(carry, xs, q_pos=q_pos):
            kj, vj, j = xs
            k_pos = j * k_chunk + jnp.arange(k_chunk)[None, :]
            mask = jnp.ones((q_chunk, k_chunk), bool)
            if causal:
                mask &= k_pos <= q_pos
            if window > 0:
                mask &= k_pos > q_pos - window
            return _chunk_attend(qi, kj, vj, carry, mask, scale, logit_cap), None

        nj = j_hi - j_lo
        ks = kf[:, :, j_lo * k_chunk:j_hi * k_chunk].reshape(B, Hkv, nj, k_chunk, D)
        vs = vf[:, :, j_lo * k_chunk:j_hi * k_chunk].reshape(B, Hkv, nj, k_chunk, Dv)
        xs = (jnp.moveaxis(ks, 2, 0), jnp.moveaxis(vs, 2, 0),
              jnp.arange(j_lo, j_hi))
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), xs)
        outs.append(acc / jnp.maximum(l[..., None], 1e-30))

    o = jnp.concatenate(outs, axis=3)                     # (B,Hkv,G,Sq,Dv)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, Dv).astype(q.dtype)


def decode_attention_jnp(
    q: jax.Array,                  # (B, 1, Hq, D)
    k_cache: jax.Array,            # (B, C, Hkv, D)
    v_cache: jax.Array,            # (B, C, Hkv, Dv)
    k_pos: jax.Array,              # (C,) or (B, C) slot positions (-1 invalid)
    pos: jax.Array,                # () or (B,) current absolute position of q
    *, window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    n_splits: int = 1,
    k_scale: jax.Array | None = None,  # (B, C, Hkv, 1) fp32 per-row scales
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Single-token decode against a (ring-buffer) KV cache.  ``k_pos`` /
    ``pos`` may carry a leading batch axis: ragged batches of private ring
    buffers (each slot of the paged engine at its own depth) mask per-row.

    The cache stays in its storage dtype end to end; the two einsums
    accumulate in fp32 via ``preferred_element_type`` (same rationale as
    ``flash_attention_jnp``: decode streams the WHOLE cache per token, so a
    whole-cache fp32 pre-cast would triple the hot path's HBM traffic).
    ``n_splits > 1`` runs the two-stage partial/merge path (exact; mirrors
    the Pallas split contract); 1 is the plain softmax.  When ``k_scale`` /
    ``v_scale`` are given the cache is int8 and is dequantized (cast * scale,
    fp32) before the einsums — the jnp mirror of the fused-dequant block
    load in the Pallas sweep."""
    B, _, Hq, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    if k_scale is not None:
        k_cache = k_cache.astype(jnp.float32) * k_scale
    if v_scale is not None:
        v_cache = v_cache.astype(jnp.float32) * v_scale
    qf = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = s.astype(jnp.float32)
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    k_posb = jnp.asarray(k_pos).reshape(-1, C)           # (1, C) or (B, C)
    posb = jnp.asarray(pos).reshape(-1)[:, None]         # (1, 1) or (B, 1)
    valid = (k_posb >= 0) & (k_posb <= posb)
    if window > 0:
        valid &= k_posb > posb - window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    if n_splits > 1:
        o = _split_attend_jnp(s[:, :, :, None, :], v_cache, n_splits)[..., 0, :]
    else:
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache,
                       preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hq, v_cache.shape[-1]).astype(q.dtype)


def verify_attention_jnp(
    q: jax.Array,                  # (B, Q, Hq, D)   Q = K+1 fed tokens
    k_cache: jax.Array,            # (B, C, Hkv, D)  committed through pos-1
    v_cache: jax.Array,            # (B, C, Hkv, Dv)
    k_new: jax.Array,              # (B, Q, Hkv, D)  in-flight candidate rows
    v_new: jax.Array,              # (B, Q, Hkv, Dv)
    k_pos: jax.Array,              # (C,) absolute position per slot (<0 invalid)
    pos: jax.Array,                # () absolute position of q[:, 0]
    *, window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    n_splits: int = 1,
    k_scale: jax.Array | None = None,  # (B, C, Hkv, 1) fp32 per-row scales
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Speculative multi-query decode (verify) against a ring-buffer cache.

    Query i (absolute position ``pos + i``) attends to the committed cache
    plus candidates ``j <= i`` of the in-flight block; candidate k/v never
    touch the cache so a rejected suffix needs no rollback.  Ring-eviction
    semantics are preserved (``k_pos > q_pos - C``): entries the sequential
    loop would already have overwritten are masked.  Storage dtype is kept
    end to end; einsums accumulate in fp32 (same discipline as
    ``decode_attention_jnp`` — one cache sweep amortised over K+1 queries
    is the whole J/token win).  With ``k_scale``/``v_scale`` the cache is
    int8 and dequantized before use; the in-flight candidates are always
    unquantized (they are transient activations, never pool rows)."""
    B, Q, Hq, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    if k_scale is not None:
        k_cache = k_cache.astype(jnp.float32) * k_scale
    if v_scale is not None:
        v_cache = v_cache.astype(jnp.float32) * v_scale
    qf = q.reshape(B, Q, Hkv, G, D)
    q_pos = pos + jnp.arange(Q)[:, None]                     # (Q, 1)

    s_c = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k_cache,
                     preferred_element_type=jnp.float32).astype(jnp.float32)
    s_n = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k_new,
                     preferred_element_type=jnp.float32).astype(jnp.float32)
    s = jnp.concatenate([s_c, s_n], axis=-1) * scale
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)

    valid_c = (k_pos[None, :] >= 0) & (k_pos[None, :] <= q_pos) \
        & (k_pos[None, :] > q_pos - C)
    n_pos = pos + jnp.arange(Q)[None, :]
    valid_n = n_pos <= q_pos
    if window > 0:
        valid_c &= k_pos[None, :] > q_pos - window
        valid_n &= n_pos > q_pos - window
    valid = jnp.concatenate([valid_c, valid_n], axis=-1)     # (Q, C+Q)
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    vf = jnp.concatenate([v_cache, v_new], axis=1)
    if n_splits > 1:
        o = _split_attend_jnp(s, vf, n_splits)               # (B,h,g,Q,Dv)
        o = o.transpose(0, 3, 1, 2, 4)                       # (B,Q,h,g,Dv)
    else:
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf,
                       preferred_element_type=jnp.float32)
    return o.reshape(B, Q, Hq, Dv).astype(q.dtype)


def paged_verify_attention_jnp(
    q: jax.Array,                  # (B, Q, Hq, D)
    k_pages: jax.Array,            # (P, ps, Hkv, D)   shared page pool
    v_pages: jax.Array,            # (P, ps, Hkv, Dv)
    k_new: jax.Array,              # (B, Q, Hkv, D)    in-flight candidates
    v_new: jax.Array,              # (B, Q, Hkv, Dv)
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) absolute position of q[:, 0]
    *, window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    n_splits: int = 1,
    k_scale: jax.Array | None = None,  # (P, ps, Hkv, 1) fp32 per-row scales
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Paged analogue of ``verify_attention_jnp``: the pool is committed
    through ``pos[b] - 1`` (linear layout, no eviction); ``pos`` is
    per-request so validity is per-row.  With ``k_scale``/``v_scale`` the
    pool is int8: scale rows are gathered through the same block tables and
    the gathered cache is dequantized before the einsums."""
    B, Q, Hq, D = q.shape
    ps, Hkv = k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    Dv = v_pages.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    kg = k_pages[block_tables].reshape(B, nb * ps, Hkv, D)
    vg = v_pages[block_tables].reshape(B, nb * ps, Hkv, Dv)
    if k_scale is not None:
        kg = kg.astype(jnp.float32) \
            * k_scale[block_tables].reshape(B, nb * ps, Hkv, 1)
    if v_scale is not None:
        vg = vg.astype(jnp.float32) \
            * v_scale[block_tables].reshape(B, nb * ps, Hkv, 1)
    qf = q.reshape(B, Q, Hkv, G, D)
    q_pos = pos.reshape(B, 1, 1) + jnp.arange(Q)[None, :, None]  # (B, Q, 1)

    s_c = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kg,
                     preferred_element_type=jnp.float32).astype(jnp.float32)
    s_n = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k_new,
                     preferred_element_type=jnp.float32).astype(jnp.float32)
    s = jnp.concatenate([s_c, s_n], axis=-1) * scale
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)

    k_pos = jnp.arange(nb * ps)[None, None, :]
    valid_c = jnp.broadcast_to(k_pos < pos.reshape(B, 1, 1), (B, Q, nb * ps))
    n_pos = pos.reshape(B, 1, 1) + jnp.arange(Q)[None, None, :]
    valid_n = n_pos <= q_pos
    if window > 0:
        valid_c = valid_c & (k_pos > q_pos - window)
        valid_n &= n_pos > q_pos - window
    valid = jnp.concatenate([valid_c, valid_n], axis=-1)     # (B, Q, K+Q)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    vf = jnp.concatenate([vg, v_new], axis=1)
    if n_splits > 1:
        o = _split_attend_jnp(s, vf, n_splits)               # (B,h,g,Q,Dv)
        o = o.transpose(0, 3, 1, 2, 4)                       # (B,Q,h,g,Dv)
    else:
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf,
                       preferred_element_type=jnp.float32)
    return o.reshape(B, Q, Hq, Dv).astype(q.dtype)


def paged_decode_attention_jnp(
    q: jax.Array,                  # (B, 1, Hq, D)
    k_pages: jax.Array,            # (P, ps, Hkv, D)   shared page pool
    v_pages: jax.Array,            # (P, ps, Hkv, Dv)
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) per-request absolute position of q
    *, window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    n_splits: int = 1,
    k_scale: jax.Array | None = None,  # (P, ps, Hkv, 1) fp32 per-row scales
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Single-token decode against a paged KV cache, pure jnp.

    Gathers each request's pages into logical order (block j holds positions
    [j*ps, (j+1)*ps)) and keeps the pool in its storage dtype — the einsums
    accumulate in fp32 via ``preferred_element_type``, same discipline as
    ``decode_attention_jnp``.  ``pos`` is per-request: the batch is ragged,
    so validity is a (B, K) mask rather than the ring path's shared (C,).
    With ``k_scale``/``v_scale`` the pool is int8: scale rows are gathered
    through the same block tables and dequantized before the einsums."""
    B, _, Hq, D = q.shape
    ps, Hkv = k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    Dv = v_pages.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    kg = k_pages[block_tables].reshape(B, nb * ps, Hkv, D)
    vg = v_pages[block_tables].reshape(B, nb * ps, Hkv, Dv)
    if k_scale is not None:
        kg = kg.astype(jnp.float32) \
            * k_scale[block_tables].reshape(B, nb * ps, Hkv, 1)
    if v_scale is not None:
        vg = vg.astype(jnp.float32) \
            * v_scale[block_tables].reshape(B, nb * ps, Hkv, 1)
    qf = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, kg,
                   preferred_element_type=jnp.float32) * scale
    s = s.astype(jnp.float32)
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    k_pos = jnp.arange(nb * ps)[None, :]
    posb = jnp.asarray(pos).reshape(B, 1)
    valid = k_pos <= posb
    if window > 0:
        valid &= k_pos > posb - window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    if n_splits > 1:
        o = _split_attend_jnp(s[:, :, :, None, :], vg, n_splits)[..., 0, :]
    else:
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgk,bkhd->bhgd", p, vg,
                       preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hq, Dv).astype(q.dtype)


def paged_decode_attention(
    q: jax.Array,                  # (B, 1, Hq, D)
    k_pages: jax.Array,            # (P, ps, Hkv, D)   shared page pool
    v_pages: jax.Array,            # (P, ps, Hkv, Dv)
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) per-request absolute position of q
    *,
    window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    k_scale: jax.Array | None = None,  # (P, ps, Hkv, 1) fp32; int8 pools only
    v_scale: jax.Array | None = None,
    policy: KernelPolicy = DEFAULT_POLICY,
) -> jax.Array:
    """Backend-dispatching paged decode attention (continuous-batching hot
    path).  Shares the ``decode`` backend axis with the ring entry point:
    ``auto`` resolves to the block-table-gather Pallas kernel on TPU and the
    gather-then-attend jnp path elsewhere.  The split-K block is the page
    size — pages are the DMA unit, so ``decode_k_chunk`` does not apply.
    ``k_scale``/``v_scale`` (per-row fp32, int8 pools) flow to every backend:
    the Pallas kernel fuses the dequant into the stage-1 block load."""
    backend = policy.decode
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    ps, nb = k_pages.shape[1], block_tables.shape[1]
    # q_heads = 1: the Pallas sweep tiles every head of a slot in one grid
    # cell, so the stage-1 grid has B cells per split (as for MLA)
    n_splits = _resolve_kv_splits(policy, q.shape[0],
                                  effective_kv_len(nb * ps, window),
                                  1, block=ps)
    if backend in ("pallas", "pallas_interpret"):
        from repro.kernels import decode_attention as da
        return da.paged_decode_attention_pallas(
            q, k_pages, v_pages, block_tables, pos, window=window,
            logit_cap=logit_cap, scale=scale, n_splits=n_splits,
            k_scale=k_scale, v_scale=v_scale,
            interpret=backend == "pallas_interpret")
    if backend == "ref":
        return _ref.paged_decode_attention_ref(
            q, k_pages, v_pages, block_tables, pos, window=window,
            logit_cap=logit_cap, scale=scale,
            k_scale=k_scale, v_scale=v_scale)
    if backend == "jnp":
        return paged_decode_attention_jnp(
            q, k_pages, v_pages, block_tables, pos, window=window,
            logit_cap=logit_cap, scale=scale, n_splits=n_splits,
            k_scale=k_scale, v_scale=v_scale)
    raise ValueError(f"unknown decode backend {backend!r}")


# ==========================================================================
# MLA compressed-latent decode (absorbed-matmul form)
# ==========================================================================
def mla_absorbed_attend_jnp(
    q_abs: jax.Array,              # (B, H, r_kv)  q_nope absorbed through W_uk
    q_rope: jax.Array,             # (B, H, dr)    rope sub-block queries
    c_kv: jax.Array,               # (B, C, r_kv)  compressed latents (k AND v)
    k_rope: jax.Array,             # (B, C, dr)    shared rope keys
    valid: jax.Array,              # (B, C) bool
    *, scale: float, logit_cap: float = 0.0, n_splits: int = 1,
) -> jax.Array:
    """The absorbed-matmul MLA attend shared by the ring ``mla_decode`` and
    the paged jnp path — one latent row per token attended by every head
    (Hkv = 1, G = H), value = the compressed latent itself.  Keeping both
    cache layouts on this one body is what makes the paged engine's greedy
    streams match the ring reference.  Scores and the value reduction
    accumulate in fp32; ``n_splits > 1`` runs the exact two-stage
    partial/LSE-merge path (mirrors the Pallas split contract).
    Returns latent outputs ``(B, H, r_kv)`` in the query dtype."""
    s = jnp.einsum("bhr,bcr->bhc", q_abs, c_kv,
                   preferred_element_type=jnp.float32) \
        + jnp.einsum("bhk,bck->bhc", q_rope, k_rope,
                     preferred_element_type=jnp.float32)
    s = (s * scale).astype(jnp.float32)
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    if n_splits > 1:
        o = _split_attend_jnp(s[:, None, :, None, :],
                              c_kv[:, :, None, :], n_splits)[:, 0, :, 0, :]
    else:
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhc,bcr->bhr", p, c_kv,
                       preferred_element_type=jnp.float32)
    return o.astype(q_abs.dtype)


def mla_decode_paged_jnp(
    q_lat: jax.Array,              # (B, 1, Hq, R) latent queries [q_abs|q_rope]
    lat_pages: jax.Array,          # (P, ps, R)    latent page pool
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) per-request absolute position of q
    *, r_kv: int, scale: float, logit_cap: float = 0.0, n_splits: int = 1,
) -> jax.Array:
    """Paged MLA decode, pure jnp: gather the latent pages into logical
    order, then the shared absorbed attend.  Linear layout — validity is
    simply ``k_pos <= pos[b]``."""
    B, _, Hq, R = q_lat.shape
    ps = lat_pages.shape[1]
    nb = block_tables.shape[1]
    latg = lat_pages[block_tables].reshape(B, nb * ps, R)
    valid = jnp.arange(nb * ps)[None, :] <= jnp.asarray(pos).reshape(B, 1)
    o = mla_absorbed_attend_jnp(
        q_lat[:, 0, :, :r_kv], q_lat[:, 0, :, r_kv:],
        latg[..., :r_kv], latg[..., r_kv:], valid,
        scale=scale, logit_cap=logit_cap, n_splits=n_splits)
    return o[:, None]                              # (B, 1, Hq, r_kv)


def mla_decode_paged(
    q_lat: jax.Array,              # (B, 1, Hq, R) latent queries [q_abs|q_rope]
    lat_pages: jax.Array,          # (P, ps, R)    latent page pool
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) per-request absolute position of q
    *,
    r_kv: int, scale: float, logit_cap: float = 0.0,
    policy: KernelPolicy = DEFAULT_POLICY,
) -> jax.Array:
    """Backend-dispatching compressed-latent MLA paged decode — the model
    zoo's headline sweep.  Shares the ``decode`` backend axis: ``auto``
    resolves to the latent-pool Pallas kernel on TPU and the
    gather-then-attend jnp path elsewhere.  The split count comes from the
    same occupancy heuristic at the MLA grid shape: every q head shares the
    ONE latent row, so the natural grid has ``batch * 1`` cells (the page
    DMA is shared across heads), i.e. ``q_heads = 1`` — MLA decode at low
    batch is the deepest occupancy deficit in the zoo, exactly where
    splitting pays.  Returns latent outputs ``(B, 1, Hq, r_kv)``; the
    ``W_uv`` / ``W_o`` expansion happens in the caller (absorbed form)."""
    backend = policy.decode
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    ps, nb = lat_pages.shape[1], block_tables.shape[1]
    # q_heads = 1: the MLA kernel tiles ALL heads per page DMA (grid is
    # (B, splits, pages), not (B, Hq, splits, pages))
    n_splits = _resolve_kv_splits(policy, q_lat.shape[0], nb * ps, 1,
                                  block=ps)
    if backend in ("pallas", "pallas_interpret"):
        from repro.kernels import decode_attention as da
        return da.mla_paged_decode_attention_pallas(
            q_lat, lat_pages, block_tables, pos, r_kv=r_kv, scale=scale,
            logit_cap=logit_cap, n_splits=n_splits,
            interpret=backend == "pallas_interpret")
    if backend == "ref":
        return _ref.mla_decode_paged_ref(
            q_lat, lat_pages, block_tables, pos, r_kv=r_kv, scale=scale,
            logit_cap=logit_cap)
    if backend == "jnp":
        return mla_decode_paged_jnp(
            q_lat, lat_pages, block_tables, pos, r_kv=r_kv, scale=scale,
            logit_cap=logit_cap, n_splits=n_splits)
    raise ValueError(f"unknown decode backend {backend!r}")


def ring_positions(pos: jax.Array, cache_len: int) -> jax.Array:
    """Absolute position held by each ring-buffer slot under the canonical
    layout (slot = p % C): ``pos - ((pos - s) mod C)``.  Slots not yet
    written resolve to negative positions (masked as invalid everywhere).
    ``pos`` may be scalar -> (C,), or (B,) -> (B, C) for ragged batches of
    private ring buffers (the paged engine's windowed layers)."""
    s = jnp.arange(cache_len)
    p = jnp.asarray(pos)
    if p.ndim == 0:
        return p - jnp.mod(p - s, cache_len)
    return p[:, None] - jnp.mod(p[:, None] - s, cache_len)


def decode_attention(
    q: jax.Array,                  # (B, 1, Hq, D)
    k_cache: jax.Array,            # (B, C, Hkv, D)   ring buffer
    v_cache: jax.Array,            # (B, C, Hkv, Dv)
    pos: jax.Array,                # () current absolute position of q
    *,
    k_pos: jax.Array | None = None,   # (C,) slot positions; None -> canonical ring
    window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    k_scale: jax.Array | None = None,  # (B, C, Hkv, 1) fp32; int8 caches only
    v_scale: jax.Array | None = None,
    policy: KernelPolicy = DEFAULT_POLICY,
) -> jax.Array:
    """Backend-dispatching decode-attention entry point (serving hot path).

    ``auto`` resolves to the split-K Pallas kernel on TPU and the chunk-free
    jnp path elsewhere (CPU stand-ins cannot lower Pallas TPU kernels).  The
    Pallas path derives slot positions from ``pos`` inside the kernel and
    therefore requires the canonical ring layout — callers passing a custom
    ``k_pos`` are routed to the jnp path instead.  ``k_scale``/``v_scale``
    (per-row fp32, int8 caches) flow to every backend; the Pallas kernel
    fuses the dequant into the stage-1 block load.
    """
    backend = policy.decode
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend in ("pallas", "pallas_interpret") and k_pos is not None:
        _warn_k_pos_fallback("decode_attention")
        backend = "jnp"            # custom slot layout: ring derivation invalid
    n_splits = _resolve_kv_splits(policy, q.shape[0],
                                  effective_kv_len(k_cache.shape[1], window),
                                  q.shape[2], block=policy.decode_k_chunk)
    if backend in ("pallas", "pallas_interpret"):
        from repro.kernels import decode_attention as da
        return da.decode_attention_pallas(
            q, k_cache, v_cache, pos, window=window, logit_cap=logit_cap,
            scale=scale, block_k=policy.decode_k_chunk, n_splits=n_splits,
            k_scale=k_scale, v_scale=v_scale,
            interpret=backend == "pallas_interpret")
    if k_pos is None:
        k_pos = ring_positions(pos, k_cache.shape[1])
    if backend == "ref":
        return _ref.decode_attention_ref(q, k_cache, v_cache, k_pos, pos,
                                         window=window, logit_cap=logit_cap,
                                         scale=scale,
                                         k_scale=k_scale, v_scale=v_scale)
    if backend == "jnp":
        return decode_attention_jnp(q, k_cache, v_cache, k_pos, pos,
                                    window=window, logit_cap=logit_cap,
                                    scale=scale, n_splits=n_splits,
                                    k_scale=k_scale, v_scale=v_scale)
    raise ValueError(f"unknown decode backend {backend!r}")


def verify_attention(
    q: jax.Array,                  # (B, Q, Hq, D)   Q = K+1 fed tokens
    k_cache: jax.Array,            # (B, C, Hkv, D)  ring, committed thru pos-1
    v_cache: jax.Array,            # (B, C, Hkv, Dv)
    k_new: jax.Array,              # (B, Q, Hkv, D)  in-flight candidate rows
    v_new: jax.Array,              # (B, Q, Hkv, Dv)
    pos: jax.Array,                # () absolute position of q[:, 0]
    *,
    k_pos: jax.Array | None = None,   # (C,) slot positions; None -> canonical ring
    window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    k_scale: jax.Array | None = None,  # (B, C, Hkv, 1) fp32; int8 caches only
    v_scale: jax.Array | None = None,
    policy: KernelPolicy = DEFAULT_POLICY,
) -> jax.Array:
    """Backend-dispatching speculative verify attention (ring layout).

    Scores ``Q = K+1`` queries at positions ``pos .. pos+K`` in ONE cache
    sweep — the decode hot path's bytes-per-token lever: the whole KV cache
    streams HBM once for K+1 candidate tokens instead of once per token.
    Shares the ``decode`` backend axis; the candidates' k/v ride along as a
    separate in-flight block so rejection never needs a cache rollback.
    ``k_scale``/``v_scale`` dequantize an int8 cache (candidates always stay
    unquantized)."""
    backend = policy.decode
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend in ("pallas", "pallas_interpret") and k_pos is not None:
        _warn_k_pos_fallback("verify_attention")
        backend = "jnp"            # custom slot layout: ring derivation invalid
    n_splits = _resolve_kv_splits(policy, q.shape[0],
                                  effective_kv_len(k_cache.shape[1], window),
                                  q.shape[2], block=policy.decode_k_chunk)
    if backend in ("pallas", "pallas_interpret"):
        from repro.kernels import decode_attention as da
        return da.verify_attention_pallas(
            q, k_cache, v_cache, k_new, v_new, pos, window=window,
            logit_cap=logit_cap, scale=scale, block_k=policy.decode_k_chunk,
            n_splits=n_splits, k_scale=k_scale, v_scale=v_scale,
            interpret=backend == "pallas_interpret")
    if k_pos is None:
        # committed prefix ends at pos - 1: that is the ring reference
        k_pos = ring_positions(pos - 1, k_cache.shape[1])
    if backend == "ref":
        return _ref.verify_attention_ref(
            q, k_cache, v_cache, k_new, v_new, k_pos, pos, window=window,
            logit_cap=logit_cap, scale=scale,
            k_scale=k_scale, v_scale=v_scale)
    if backend == "jnp":
        return verify_attention_jnp(
            q, k_cache, v_cache, k_new, v_new, k_pos, pos, window=window,
            logit_cap=logit_cap, scale=scale, n_splits=n_splits,
            k_scale=k_scale, v_scale=v_scale)
    raise ValueError(f"unknown decode backend {backend!r}")


def paged_verify_attention(
    q: jax.Array,                  # (B, Q, Hq, D)
    k_pages: jax.Array,            # (P, ps, Hkv, D)   shared page pool
    v_pages: jax.Array,            # (P, ps, Hkv, Dv)
    k_new: jax.Array,              # (B, Q, Hkv, D)    in-flight candidates
    v_new: jax.Array,              # (B, Q, Hkv, Dv)
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) absolute position of q[:, 0]
    *,
    window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    k_scale: jax.Array | None = None,  # (P, ps, Hkv, 1) fp32; int8 pools only
    v_scale: jax.Array | None = None,
    policy: KernelPolicy = DEFAULT_POLICY,
) -> jax.Array:
    """Backend-dispatching multi-query attention over the paged KV cache
    (the continuous-batching engine's layout).  ``pos`` is per-request —
    every slot scores its own Q in-flight tokens at its own depth.  Two
    callers share this entry: speculative verify (Q = K+1 candidates) and
    chunked paged prefill (Q = prompt-suffix chunk against a shared cached
    prefix; the commit side differs, the sweep is identical).
    ``k_scale``/``v_scale`` dequantize an int8 pool (candidates always stay
    unquantized)."""
    backend = policy.decode
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    ps, nb = k_pages.shape[1], block_tables.shape[1]
    n_splits = _resolve_kv_splits(policy, q.shape[0],
                                  effective_kv_len(nb * ps, window),
                                  q.shape[2], block=ps)
    if backend in ("pallas", "pallas_interpret"):
        from repro.kernels import decode_attention as da
        return da.paged_verify_attention_pallas(
            q, k_pages, v_pages, k_new, v_new, block_tables, pos,
            window=window, logit_cap=logit_cap, scale=scale,
            n_splits=n_splits, k_scale=k_scale, v_scale=v_scale,
            interpret=backend == "pallas_interpret")
    if backend == "ref":
        return _ref.paged_verify_attention_ref(
            q, k_pages, v_pages, k_new, v_new, block_tables, pos,
            window=window, logit_cap=logit_cap, scale=scale,
            k_scale=k_scale, v_scale=v_scale)
    if backend == "jnp":
        return paged_verify_attention_jnp(
            q, k_pages, v_pages, k_new, v_new, block_tables, pos,
            window=window, logit_cap=logit_cap, scale=scale,
            n_splits=n_splits, k_scale=k_scale, v_scale=v_scale)
    raise ValueError(f"unknown decode backend {backend!r}")


def attention(q, k, v, *, causal=True, window=0, logit_cap=0.0, scale=None,
              q_offset=0, policy: KernelPolicy = DEFAULT_POLICY) -> jax.Array:
    """Backend-dispatching attention entry point (training / prefill)."""
    backend = policy.attention
    if backend == "auto":
        backend = "ref" if q.shape[1] * k.shape[1] <= 512 * 512 else "chunked"
    if backend == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  logit_cap=logit_cap, scale=scale,
                                  q_offset=q_offset)
    if backend == "chunked":
        return flash_attention_jnp(q, k, v, causal=causal, window=window,
                                   logit_cap=logit_cap, scale=scale,
                                   q_offset=q_offset, q_chunk=policy.q_chunk,
                                   k_chunk=policy.k_chunk)
    if backend in ("pallas", "pallas_interpret"):
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  logit_cap=logit_cap, scale=scale,
                                  q_offset=q_offset,
                                  interpret=backend == "pallas_interpret")
    raise ValueError(f"unknown attention backend {backend!r}")


# ==========================================================================
# Mamba2 SSD
# ==========================================================================
def _segsum(a: jax.Array) -> jax.Array:
    """L[t, s] = sum_{r=s+1..t} a[r] for s <= t else -inf.  a: (..., Q)."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    L = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, L, -jnp.inf)


def ssd_chunked_jnp(
    x: jax.Array, dt: jax.Array, A: jax.Array,
    B_mat: jax.Array, C_mat: jax.Array, D: jax.Array | None = None, *,
    chunk: int = 128, initial_state: jax.Array | None = None,
    return_state: bool = False,
):
    """Chunked SSD (state-space duality) — Mamba2 Algorithm 1, pure jnp.

    Intra-chunk terms use the quadratic (attention-like) form on Q x Q
    blocks; inter-chunk state is carried by a scan over chunks.  Matches
    ``ref.ssd_ref`` to fp32 tolerance and is what the Pallas kernel tiles.
    """
    Bb, S, H, P = x.shape
    G, N = B_mat.shape[2], B_mat.shape[3]
    rep = H // G
    if S % chunk:
        return _ref.ssd_ref(x, dt, A, B_mat, C_mat, D,
                            initial_state=initial_state,
                            return_state=return_state)
    nc = S // chunk

    xf = x.astype(jnp.float32).reshape(Bb, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bb, nc, chunk, H)
    Af = A.astype(jnp.float32)
    Bf = jnp.repeat(B_mat.astype(jnp.float32), rep, axis=2).reshape(Bb, nc, chunk, H, N)
    Cf = jnp.repeat(C_mat.astype(jnp.float32), rep, axis=2).reshape(Bb, nc, chunk, H, N)

    h0 = (jnp.zeros((Bb, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def chunk_step(h, inp):
        xc, dtc, Bc, Cc = inp                      # (B,Q,H,*) for this chunk
        a = dtc * Af                               # (B,Q,H)
        a_t = a.transpose(0, 2, 1)                 # (B,H,Q)
        cs = jnp.cumsum(a_t, axis=-1)              # (B,H,Q)
        # 1. intra-chunk (diagonal block), attention-like
        L = jnp.exp(_segsum(a_t))                  # (B,H,Q,Q), lower-tri
        Gmat = jnp.einsum("bqhn,bshn->bhqs", Cc, Bc,
                          preferred_element_type=jnp.float32)
        M = Gmat * L * dtc.transpose(0, 2, 1)[:, :, None, :]
        y_diag = jnp.einsum("bhqs,bshp->bqhp", M, xc,
                            preferred_element_type=jnp.float32)
        # 2. contribution of the carried-in state
        state_decay = jnp.exp(cs)                  # (B,H,Q)
        y_off = jnp.einsum("bqhn,bhpn,bhq->bqhp", Cc, h, state_decay,
                           preferred_element_type=jnp.float32)
        # 3. next state
        total = cs[..., -1:]                       # (B,H,1)
        rem = jnp.exp(total - cs)                  # (B,H,Q)
        w = dtc * rem.transpose(0, 2, 1)           # (B,Q,H) weight per step
        dBx = jnp.einsum("bqhn,bqhp->bhpn", Bc * w[..., None], xc,
                         preferred_element_type=jnp.float32)
        h_next = jnp.exp(total)[..., None] * h + dBx
        return h_next, y_diag + y_off

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0))
    hT, ys = jax.lax.scan(chunk_step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bb, S, H, P)
    if D is not None:
        y = y + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.astype(x.dtype)
    if return_state:
        return y, hT
    return y


def ssd_decode_step(
    h: jax.Array,                  # (B, H, P, N) carried state
    x_t: jax.Array,                # (B, H, P)
    dt_t: jax.Array,               # (B, H)
    A: jax.Array,                  # (H,)
    B_t: jax.Array,                # (B, G, N)
    C_t: jax.Array,                # (B, G, N)
    D: jax.Array | None = None,
):
    """One-token SSD recurrence for decode — O(1) in context length."""
    H = x_t.shape[1]
    rep = H // B_t.shape[1]
    Bf = jnp.repeat(B_t.astype(jnp.float32), rep, axis=1)      # (B,H,N)
    Cf = jnp.repeat(C_t.astype(jnp.float32), rep, axis=1)
    dtf = dt_t.astype(jnp.float32)
    decay = jnp.exp(dtf * A.astype(jnp.float32))[..., None, None]
    upd = (dtf[..., None] * x_t.astype(jnp.float32))[..., None] * Bf[:, :, None, :]
    h_new = decay * h.astype(jnp.float32) + upd
    y = jnp.einsum("bhpn,bhn->bhp", h_new, Cf)
    if D is not None:
        y = y + D.astype(jnp.float32)[:, None] * x_t.astype(jnp.float32)
    return h_new, y.astype(x_t.dtype)


def ssd(x, dt, A, B_mat, C_mat, D=None, *, initial_state=None,
        return_state=False, policy: KernelPolicy = DEFAULT_POLICY):
    backend = policy.ssd
    if backend == "auto":
        backend = "ref" if x.shape[1] <= 64 else "chunked"
    if backend == "ref":
        return _ref.ssd_ref(x, dt, A, B_mat, C_mat, D,
                            initial_state=initial_state, return_state=return_state)
    if backend == "chunked":
        return ssd_chunked_jnp(x, dt, A, B_mat, C_mat, D, chunk=policy.ssd_chunk,
                               initial_state=initial_state, return_state=return_state)
    if backend in ("pallas", "pallas_interpret"):
        from repro.kernels import ssd_scan
        return ssd_scan.ssd_pallas(x, dt, A, B_mat, C_mat, D,
                                   chunk=policy.ssd_chunk,
                                   initial_state=initial_state,
                                   return_state=return_state,
                                   interpret=backend == "pallas_interpret")
    raise ValueError(f"unknown ssd backend {backend!r}")


# ==========================================================================
# RMSNorm
# ==========================================================================
def rmsnorm(x, scale, *, eps=1e-6, gemma_style=False,
            policy: KernelPolicy = DEFAULT_POLICY):
    backend = policy.rmsnorm
    if backend in ("auto", "ref", "chunked"):
        return _ref.rmsnorm_ref(x, scale, eps=eps, gemma_style=gemma_style)
    if backend in ("pallas", "pallas_interpret"):
        from repro.kernels import rmsnorm as rms
        return rms.rmsnorm_pallas(x, scale, eps=eps, gemma_style=gemma_style,
                                  interpret=backend == "pallas_interpret")
    raise ValueError(f"unknown rmsnorm backend {backend!r}")
