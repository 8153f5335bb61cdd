"""Pure-jnp oracles for every kernel.

These are the correctness ground truth: small, obviously-correct,
O(S^2)-memory implementations.  Pallas kernels (and the chunked jnp paths in
ops.py) are validated against these with assert_allclose sweeps in
tests/test_kernels.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _dequant(cache: jax.Array, scale: jax.Array | None) -> jax.Array:
    """Whole-pool dequant for the quantized oracles: the oracle pays
    O(pool) fp32 memory anyway, so int8 storage simply dequantizes up front
    (``q.astype(f32) * scale`` with the (..., 1) per-row scale broadcasting
    over head_dim) and the unquantized body is reused verbatim."""
    cf = cache.astype(jnp.float32)
    return cf if scale is None else cf * scale


# --------------------------------------------------------------------------
# attention oracle
# --------------------------------------------------------------------------
def attention_ref(
    q: jax.Array,                  # (B, Sq, Hq, D)
    k: jax.Array,                  # (B, Sk, Hkv, D)
    v: jax.Array,                  # (B, Sk, Hkv, Dv)
    *,
    causal: bool = True,
    window: int = 0,               # >0: sliding window (q attends to last `window` keys)
    logit_cap: float = 0.0,        # gemma2 tanh softcap
    scale: float | None = None,
    q_offset: int = 0,             # absolute position of q[0] (decode/chunked prefill)
    k_len: jax.Array | None = None,  # valid prefix length of k/v (ragged decode)
) -> jax.Array:
    """Naive GQA attention with all the assigned-arch flavours. fp32 math."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5

    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)

    q_pos = q_offset + jnp.arange(Sq)[:, None]           # (Sq, 1)
    k_pos = jnp.arange(Sk)[None, :]                      # (1, Sk)
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    if k_len is not None:
        mask &= k_pos < jnp.asarray(k_len).reshape(())
    s = jnp.where(mask[None, None, None], s, NEG_INF)

    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return o.reshape(B, Sq, Hq, Dv).astype(q.dtype)


# --------------------------------------------------------------------------
# decode-attention oracle — single token vs a ring-buffer KV cache
# --------------------------------------------------------------------------
def decode_attention_ref(
    q: jax.Array,                  # (B, 1, Hq, D)
    k_cache: jax.Array,            # (B, C, Hkv, D)
    v_cache: jax.Array,            # (B, C, Hkv, Dv)
    k_pos: jax.Array,              # (C,) absolute position per slot (<0 invalid)
    pos: jax.Array,                # () absolute position of q
    *, window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    k_scale: jax.Array | None = None,  # (B, C, Hkv, 1) fp32; int8 caches only
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Naive decode oracle: whole-cache fp32 math, explicit slot positions.
    Ground truth for the chunked-jnp path and the split-K Pallas kernel.
    ``k_scale``/``v_scale`` make it the QUANTIZED oracle: the int8 cache is
    dequantized up front and the identical fp32 body runs."""
    B, _, Hq, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, _dequant(k_cache, k_scale)) * scale
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    valid = (k_pos >= 0) & (k_pos <= pos)
    if window > 0:
        valid &= k_pos > pos - window
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, _dequant(v_cache, v_scale))
    return o.reshape(B, 1, Hq, v_cache.shape[-1]).astype(q.dtype)


# --------------------------------------------------------------------------
# two-stage split-KV oracles — stage-1 partial/LSE contract + stage-2 merge
# --------------------------------------------------------------------------
def merge_kv_splits_ref(partial: jax.Array, lse: jax.Array) -> jax.Array:
    """Stage-2 oracle: merge per-split normalized partials by their
    log-sum-exp weights.  ``partial (..., S, R, Dv)`` + ``lse (..., S, R)``
    -> ``(..., R, Dv)``.  Splits with ``lse == NEG_INF`` (no valid key)
    get weight ~0 and drop out."""
    m = jnp.max(lse, axis=-2, keepdims=True)                  # (..., 1, R)
    w = jnp.exp(lse - m)                                      # (..., S, R)
    den = jnp.maximum(jnp.sum(w, axis=-2), 1e-30)             # (..., R)
    acc = jnp.sum(partial * w[..., None], axis=-3)            # (..., R, Dv)
    return acc / den[..., None]


def _split_partials(s, vf, *, n_units, unit, n_splits):
    """Shared stage-1 oracle body: masked scores ``s (B, Hkv, G, K)`` over
    ``n_units`` blocks of ``unit`` keys each, values ``vf (B, K, Hkv, Dv)``.
    Returns ``(partial (B, Hq, S, 1, Dv), lse (B, Hq, S, 1))`` in the
    Pallas partials layout (head order = kv-head-major, as ``h // G``)."""
    B, Hkv, G, _ = s.shape
    Dv = vf.shape[-1]
    S = max(1, min(int(n_splits), n_units))
    upb = -(-n_units // S)                        # units per split (ceil)
    parts, lses = [], []
    for si in range(S):
        lo = si * upb * unit
        hi = min((si + 1) * upb, n_units) * unit
        if lo >= hi:                              # ragged tail: empty split
            parts.append(jnp.zeros((B, Hkv, G, Dv), jnp.float32))
            lses.append(jnp.full((B, Hkv, G), NEG_INF, jnp.float32))
            continue
        ss = s[..., lo:hi]
        m = jnp.max(ss, axis=-1)                  # (B, Hkv, G)
        p = jnp.exp(ss - m[..., None])
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bhgk,bkhd->bhgd", p,
                         vf[:, lo:hi].astype(jnp.float32))
        # a split whose every key is masked never runs in the Pallas kernel
        # (l stays 0 there): mirror its zero partial / NEG_INF lse here
        empty = m <= 0.5 * NEG_INF
        part = jnp.where(empty[..., None], 0.0,
                         acc / jnp.maximum(l, 1e-30)[..., None])
        lse = jnp.where(empty, NEG_INF,
                        m + jnp.log(jnp.maximum(l, 1e-30)))
        parts.append(part)
        lses.append(lse)
    Hq = Hkv * G
    partial = jnp.stack(parts, axis=3).reshape(B, Hq, S, 1, Dv)
    lse = jnp.stack(lses, axis=3).reshape(B, Hq, S, 1)
    return partial, lse


def decode_attention_split_ref(
    q: jax.Array,                  # (B, 1, Hq, D)
    k_cache: jax.Array,            # (B, C, Hkv, D)
    v_cache: jax.Array,            # (B, C, Hkv, Dv)
    k_pos: jax.Array,              # (C,) absolute position per slot (<0 invalid)
    pos: jax.Array,                # () absolute position of q
    *, n_splits: int, block_k: int = 256,
    window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    k_scale: jax.Array | None = None,  # (B, C, Hkv, 1) fp32; int8 caches only
    v_scale: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Stage-1 oracle for ``decode_attention_pallas_partials``: same
    k-block partitioning (including the divisor-of-C ``block_k``
    adjustment), whole-cache fp32 math per split.  Returns
    ``(partial (B, Hq, S, 1, Dv), lse (B, Hq, S, 1))``."""
    B, _, Hq, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    block_k = min(block_k, C)
    if C % block_k:
        block_k = next(b for b in range(block_k, 0, -1) if C % b == 0)
    n_k = C // block_k
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, _dequant(k_cache, k_scale)) * scale
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    valid = (k_pos >= 0) & (k_pos <= pos)
    if window > 0:
        valid &= k_pos > pos - window
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    return _split_partials(s, _dequant(v_cache, v_scale), n_units=n_k,
                           unit=block_k, n_splits=n_splits)


def paged_decode_attention_split_ref(
    q: jax.Array,                  # (B, 1, Hq, D)
    k_pages: jax.Array,            # (P, ps, Hkv, D)   shared page pool
    v_pages: jax.Array,            # (P, ps, Hkv, Dv)
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) per-request absolute position of q
    *, n_splits: int, block_pages: int = 1,
    window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    k_scale: jax.Array | None = None,  # (P, ps, Hkv, 1) fp32; int8 pools only
    v_scale: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Stage-1 oracle for ``paged_decode_attention_pallas_partials``: pages
    gathered into logical order, split over blocks of ``block_pages`` pages
    (the kernel's grid step, ``decode_attention.pages_per_block``)."""
    B, _, Hq, D = q.shape
    ps, Hkv = k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    Dv = v_pages.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    kg = _dequant(k_pages, k_scale)[block_tables].reshape(B, nb * ps, Hkv, D)
    vg = _dequant(v_pages, v_scale)[block_tables].reshape(B, nb * ps, Hkv, Dv)
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, kg) * scale
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    k_pos = jnp.arange(nb * ps)[None, :]
    posb = jnp.asarray(pos).reshape(B, 1)
    valid = k_pos <= posb
    if window > 0:
        valid &= k_pos > posb - window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    n_blk = -(-nb // block_pages)
    pad = n_blk * block_pages - nb           # the last block's missing pages
    s = jnp.pad(s, ((0, 0),) * 3 + ((0, pad * ps),), constant_values=NEG_INF)
    vg = jnp.pad(vg, ((0, 0), (0, pad * ps), (0, 0), (0, 0)))
    return _split_partials(s, vg, n_units=n_blk, unit=block_pages * ps,
                           n_splits=n_splits)


# --------------------------------------------------------------------------
# MLA compressed-latent paged decode oracles — absorbed-matmul form
# --------------------------------------------------------------------------
def mla_decode_paged_ref(
    q_lat: jax.Array,              # (B, 1, Hq, R) latent queries: [q_abs | q_rope]
    lat_pages: jax.Array,          # (P, ps, R)    latent page pool, R = r_kv + d_rope
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) per-request absolute position of q
    *, r_kv: int, scale: float, logit_cap: float = 0.0,
) -> jax.Array:
    """Naive MLA paged decode oracle in absorbed-matmul form.  One latent
    row per token is shared by every q head (Hkv = 1, G = Hq): the query is
    already projected into latent space (``q_abs = q_nope @ W_uk`` for the
    compressed block, raw ``q_rope`` for the rope sub-block), so a single
    dot of ``q_lat`` against the full latent row computes
    ``q_abs . c_kv + q_rope . k_rope`` in one pass, and the value read is
    the ``[:r_kv]`` slice of the *same* row — the one-DMA-serves-both trick
    the Pallas kernel exploits.  Returns latent outputs ``(B, 1, Hq, r_kv)``
    (the W_uv / W_o expansion happens outside, per the absorbed form).
    ``scale`` is mandatory: MLA scales by the *decompressed* head dim
    ``(d_nope + d_rope) ** -0.5``, not ``R ** -0.5``."""
    B, _, Hq, R = q_lat.shape
    ps = lat_pages.shape[1]
    nb = block_tables.shape[1]
    latg = lat_pages.astype(jnp.float32)[block_tables].reshape(B, nb * ps, R)
    qf = q_lat.astype(jnp.float32).reshape(B, 1, Hq, R)      # (B, Hkv=1, G, R)
    s = jnp.einsum("bhgd,bkd->bhgk", qf, latg) * scale
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    k_pos = jnp.arange(nb * ps)[None, :]
    posb = jnp.asarray(pos).reshape(B, 1)
    valid = k_pos <= posb
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkd->bhgd", p, latg[..., :r_kv])
    return o.reshape(B, 1, Hq, r_kv).astype(q_lat.dtype)


def mla_decode_split_ref(
    q_lat: jax.Array,              # (B, 1, Hq, R)
    lat_pages: jax.Array,          # (P, ps, R)
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,)
    *, r_kv: int, n_splits: int, scale: float, logit_cap: float = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """Stage-1 oracle for ``mla_paged_decode_attention_pallas_partials``:
    same latent gather as :func:`mla_decode_paged_ref`, split over pages
    (the DMA unit) with the shared ``_split_partials`` body at Hkv = 1,
    G = Hq, Dv = r_kv.  Returns ``(partial (B, Hq, S, 1, r_kv),
    lse (B, Hq, S, 1))`` — merged by the SAME stage-2
    ``merge_kv_splits_pallas`` kernel as every other sweep family."""
    B, _, Hq, R = q_lat.shape
    ps = lat_pages.shape[1]
    nb = block_tables.shape[1]
    latg = lat_pages.astype(jnp.float32)[block_tables].reshape(B, nb * ps, R)
    qf = q_lat.astype(jnp.float32).reshape(B, 1, Hq, R)
    s = jnp.einsum("bhgd,bkd->bhgk", qf, latg) * scale
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    k_pos = jnp.arange(nb * ps)[None, :]
    posb = jnp.asarray(pos).reshape(B, 1)
    s = jnp.where((k_pos <= posb)[:, None, None], s, NEG_INF)
    vf = latg[..., :r_kv][:, :, None, :]                      # (B, K, 1, r_kv)
    return _split_partials(s, vf, n_units=nb, unit=ps, n_splits=n_splits)


# --------------------------------------------------------------------------
# verify-attention oracle — K+1 speculative queries vs a ring-buffer cache
# --------------------------------------------------------------------------
def verify_attention_ref(
    q: jax.Array,                  # (B, Q, Hq, D)   Q = K+1 fed tokens
    k_cache: jax.Array,            # (B, C, Hkv, D)  committed through pos-1
    v_cache: jax.Array,            # (B, C, Hkv, Dv)
    k_new: jax.Array,              # (B, Q, Hkv, D)  in-flight candidate rows
    v_new: jax.Array,              # (B, Q, Hkv, Dv)
    k_pos: jax.Array,              # (C,) absolute position per slot (<0 invalid)
    pos: jax.Array,                # () absolute position of q[:, 0]
    *, window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    k_scale: jax.Array | None = None,  # (B, C, Hkv, 1) fp32; int8 caches only
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Speculative verify oracle: query i sits at absolute position pos + i
    and attends to (a) the committed cache and (b) candidates j <= i of the
    in-flight block — the candidates' k/v never touch the cache, so a
    rejected suffix needs no rollback.  ``k_scale``/``v_scale`` dequantize
    an int8 cache up front (candidates always stay unquantized).

    Ring-eviction semantics: the sequential decode loop would have
    *overwritten* slots holding positions <= (pos + i) - C by the time it
    reached query i, so those entries are masked here (``k_pos > q_pos - C``)
    even though the verify pass left them physically intact.  This is what
    makes greedy speculative decode bit-identical to the plain loop across
    ring wrap-around."""
    B, Q, Hq, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    qf = q.astype(jnp.float32).reshape(B, Q, Hkv, G, D)
    q_pos = pos + jnp.arange(Q)[:, None]                     # (Q, 1)

    # (a) committed cache: (B, Hkv, G, Q, C)
    s_c = jnp.einsum("bqhgd,bkhd->bhgqk", qf,
                     _dequant(k_cache, k_scale)) * scale
    valid_c = (k_pos[None, :] >= 0) & (k_pos[None, :] <= q_pos) \
        & (k_pos[None, :] > q_pos - C)
    if window > 0:
        valid_c &= k_pos[None, :] > q_pos - window

    # (b) in-flight candidates: causal within the fed block
    s_n = jnp.einsum("bqhgd,bkhd->bhgqk", qf,
                     k_new.astype(jnp.float32)) * scale
    n_pos = pos + jnp.arange(Q)[None, :]                     # (1, Q)
    valid_n = n_pos <= q_pos
    if window > 0:
        valid_n &= n_pos > q_pos - window

    s = jnp.concatenate([s_c, s_n], axis=-1)
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    valid = jnp.concatenate([valid_c, valid_n], axis=-1)
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    vf = jnp.concatenate([_dequant(v_cache, v_scale),
                          v_new.astype(jnp.float32)], axis=1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return o.reshape(B, Q, Hq, Dv).astype(q.dtype)


# --------------------------------------------------------------------------
# paged verify-attention oracle — K+1 speculative queries vs a paged cache
# --------------------------------------------------------------------------
def paged_verify_attention_ref(
    q: jax.Array,                  # (B, Q, Hq, D)
    k_pages: jax.Array,            # (P, ps, Hkv, D)   shared page pool
    v_pages: jax.Array,            # (P, ps, Hkv, Dv)
    k_new: jax.Array,              # (B, Q, Hkv, D)    in-flight candidates
    v_new: jax.Array,              # (B, Q, Hkv, Dv)
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) absolute position of q[:, 0]
    *, window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    k_scale: jax.Array | None = None,  # (P, ps, Hkv, 1) fp32; int8 pools only
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Paged analogue of :func:`verify_attention_ref`: the pool is committed
    through ``pos[b] - 1`` (linear layout, no eviction), candidates stay
    in-flight.  ``pos`` is per-request — the batch is ragged.
    ``k_scale``/``v_scale`` dequantize an int8 pool up front."""
    B, Q, Hq, D = q.shape
    ps, Hkv = k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    Dv = v_pages.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    kg = _dequant(k_pages, k_scale)[block_tables].reshape(B, nb * ps, Hkv, D)
    vg = _dequant(v_pages, v_scale)[block_tables].reshape(B, nb * ps, Hkv, Dv)
    qf = q.astype(jnp.float32).reshape(B, Q, Hkv, G, D)
    q_pos = pos.reshape(B, 1, 1) + jnp.arange(Q)[None, :, None]  # (B, Q, 1)

    s_c = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kg) * scale
    k_pos = jnp.arange(nb * ps)[None, None, :]               # (1, 1, K)
    valid_c = k_pos < pos.reshape(B, 1, 1)                   # committed only
    if window > 0:
        valid_c = valid_c & (k_pos > q_pos - window)
    valid_c = jnp.broadcast_to(valid_c, (B, Q, nb * ps))

    s_n = jnp.einsum("bqhgd,bkhd->bhgqk", qf,
                     k_new.astype(jnp.float32)) * scale
    n_pos = pos.reshape(B, 1, 1) + jnp.arange(Q)[None, None, :]
    valid_n = n_pos <= q_pos
    if window > 0:
        valid_n &= n_pos > q_pos - window

    s = jnp.concatenate([s_c, s_n], axis=-1)
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    valid = jnp.concatenate([valid_c, valid_n], axis=-1)     # (B, Q, K+Q)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    vf = jnp.concatenate([vg, jnp.asarray(v_new, jnp.float32)], axis=1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return o.reshape(B, Q, Hq, Dv).astype(q.dtype)


# --------------------------------------------------------------------------
# paged decode-attention oracle — single token vs a block-table KV cache
# --------------------------------------------------------------------------
def paged_decode_attention_ref(
    q: jax.Array,                  # (B, 1, Hq, D)
    k_pages: jax.Array,            # (P, ps, Hkv, D)   shared page pool
    v_pages: jax.Array,            # (P, ps, Hkv, Dv)
    block_tables: jax.Array,       # (B, nb) int32 page index per logical block
    pos: jax.Array,                # (B,) per-request absolute position of q
    *, window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    k_scale: jax.Array | None = None,  # (P, ps, Hkv, 1) fp32; int8 pools only
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Naive paged decode oracle: gather every request's pages into a
    contiguous (B, nb*ps, Hkv, *) view, then whole-cache fp32 math.  Pages
    are laid out linearly (logical block j holds positions [j*ps, (j+1)*ps)),
    so validity is simply k_pos <= pos[b] (+ sliding window).  Ground truth
    for the chunked-jnp path and the block-table-gather Pallas kernel.
    ``k_scale``/``v_scale`` make it the quantized oracle (dequant up front,
    identical body)."""
    B, _, Hq, D = q.shape
    ps, Hkv = k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    kg = _dequant(k_pages, k_scale)[block_tables].reshape(B, nb * ps, Hkv, D)
    vg = _dequant(v_pages, v_scale)[block_tables].reshape(
        B, nb * ps, Hkv, v_pages.shape[-1])
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, kg) * scale
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    k_pos = jnp.arange(nb * ps)[None, :]                     # (1, K)
    posb = jnp.asarray(pos).reshape(B, 1)
    valid = k_pos <= posb
    if window > 0:
        valid &= k_pos > posb - window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, vg)
    return o.reshape(B, 1, Hq, v_pages.shape[-1]).astype(q.dtype)


# --------------------------------------------------------------------------
# Mamba2 SSD oracle — sequential recurrence over time
# --------------------------------------------------------------------------
def ssd_ref(
    x: jax.Array,                  # (B, S, H, P)   inputs per head
    dt: jax.Array,                 # (B, S, H)      softplus'd timestep (>0)
    A: jax.Array,                  # (H,)           negative decay rate
    B_mat: jax.Array,              # (B, S, G, N)   input gates (G groups)
    C_mat: jax.Array,              # (B, S, G, N)   output gates
    D: jax.Array | None = None,    # (H,)           skip connection
    *,
    initial_state: jax.Array | None = None,   # (B, H, P, N)
    return_state: bool = False,
):
    """Exact recurrence:  h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;
    y_t = C_t h_t + D x_t.  Heads are grouped over B/C like GQA (H % G == 0).
    """
    Bb, S, H, P = x.shape
    G, N = B_mat.shape[2], B_mat.shape[3]
    rep = H // G

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = jnp.repeat(B_mat.astype(jnp.float32), rep, axis=2)   # (B, S, H, N)
    Cf = jnp.repeat(C_mat.astype(jnp.float32), rep, axis=2)

    h0 = (jnp.zeros((Bb, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(h, t):
        xt, dtt, Bt, Ct = t
        decay = jnp.exp(dtt * Af)[..., None, None]            # (B, H, 1, 1)
        upd = (dtt[..., None] * xt)[..., None] * Bt[:, :, None, :]  # (B,H,P,N)
        h = decay * h + upd
        y = jnp.einsum("bhpn,bhn->bhp", h, Ct)
        return h, y

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0))
    hT, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1)                                # (B, S, H, P)
    if D is not None:
        y = y + D.astype(jnp.float32)[:, None] * xf
    y = y.astype(x.dtype)
    if return_state:
        return y, hT.astype(jnp.float32)
    return y


# --------------------------------------------------------------------------
# RMSNorm oracle
# --------------------------------------------------------------------------
def rmsnorm_ref(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
                gemma_style: bool = False) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    w = scale.astype(jnp.float32)
    if gemma_style:
        w = 1.0 + w
    return (y * w).astype(x.dtype)
