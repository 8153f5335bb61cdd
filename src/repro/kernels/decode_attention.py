"""Single-token decode attention as split-K Pallas TPU kernels — one for the
canonical ring-buffer cache, one for a paged (block-table) cache.

Decode is the memory-bound end of the serving stack (PAPER.md Sec IV: the
whole KV cache streams HBM -> VMEM once per generated token, against one
query row of compute), so the kernel's only job is to touch each cache byte
exactly once, in its storage dtype, and keep every reduction in on-chip
fp32 scratch:

  * grid = (batch, q_heads, k_blocks) — split-K over KV-cache blocks: for a
    fixed (b, h) the kernel revisits the same single-row output tile while
    streaming ``decode_k_chunk``-sized k/v blocks; the online-softmax
    partial state (m, l, acc) lives in fp32 VMEM scratch across those
    revolutions, exactly as in ``flash_attention.py``.
  * GQA is folded into the k/v index_map (q head h reads kv head
    h // (Hq // Hkv)) — no kv replication in HBM.
  * the cache is a *ring buffer*: slot s holds absolute position
    ``pos - ((pos - s) mod C)``.  That mapping is recomputed from a
    block-relative iota inside the kernel, so validity (slot not yet
    written => negative position) and the sliding window are masked without
    materialising a position array in HBM.
  * ``pos`` arrives via scalar prefetch (SMEM) so the masks are dynamic;
    blocks whose slots are wholly past ``pos`` (ring not yet wrapped) are
    predicated off with ``pl.when`` — no MXU work, and on real hardware a
    grid prune would skip their DMA too.
  * k/v blocks are cast to fp32 only inside VMEM (block-local); the HBM
    cache stays in storage dtype — the whole-cache fp32 cast this kernel
    replaces tripled decode HBM traffic.

The paged single-token sweep (``paged_decode_attention_pallas``) tiles
differently: grid ``(batch, page_blocks)``, each step a block of whole
pages — every kv head — read from the pool as stored and folded into every
query head of its slot (section "Paged single-token decode" below).

Both layouts also carry a *multi-query verify* variant for speculative
decoding (``verify_attention_pallas`` / ``paged_verify_attention_pallas``):
``q_len = K+1`` query rows share ONE cache sweep, the causal offset masks
fold into the same iota/pos machinery, and the fed block's own k/v arrive
as a separate in-flight input folded at the last grid step — speculative
candidates never land in HBM, so rejection needs no cache rollback.

Every kernel family also has a *two-stage* form (``n_splits > 1``), the
flash-decoding shape for deep caches at low batch: stage 1 adds a
``num_kv_splits`` grid axis — each split independently sweeps its
contiguous slice of k-blocks/pages and writes a *normalized* partial
output plus the slice's log-sum-exp, with no cross-split scratch
dependency, so splits can run on different cores — and stage 2 is ONE
shared LSE-merge kernel (``merge_kv_splits_pallas``) doing the
numerically-exact online-softmax reduction over splits:

    out = sum_s partial_s * exp(lse_s - m*) / sum_s exp(lse_s - m*)

``n_splits = 1`` bypasses stage 2 entirely and is bit-for-bit today's
single-kernel sweep.  The split count is chosen by
``ops.choose_kv_splits`` (grid-occupancy heuristic) unless forced via
``KernelPolicy.kv_splits``.  See docs/decode_path.md ("Two-stage
split-KV").

Validated in interpret mode against ``kernels/ref.decode_attention_ref``
and ``ops.decode_attention_jnp`` (tests/test_kernels.py,
tests/test_split_kv.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _online_softmax_update(q, k, v, valid, m_ref, l_ref, acc_ref, *,
                           scale: float, logit_cap: float):
    """One k/v block's online-softmax update into the fp32 VMEM
    accumulators — the numerically delicate core shared by every kernel in
    this module (single-token and multi-query, ring and paged).

    q: (R, D) query rows; k: (bk, D); v: (bk, Dv); valid: (R, bk);
    m/l: (R,); acc: (R, Dv)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _load_kv(ref, scale_ref):
    """Fused-dequant block load — the ONLY place quantization touches the
    sweep's critical path.  The k/v block is cast to fp32 inside VMEM
    (block-local, as always); for int8 pools the per-row fp32 scale block
    ``(bk, 1)`` rides the same index_map as its pool and multiplies in,
    broadcasting over head_dim.  ``scale_ref is None`` is a Python-level
    branch resolved at trace time: the unquantized kernels' traces are
    byte-for-byte what they were before int8 support existed."""
    blk = ref[0, 0].astype(jnp.float32)
    if scale_ref is not None:
        blk = blk * scale_ref[0, 0]
    return blk


def _fold_candidates(q_ref, kn_ref, vn_ref, m_ref, l_ref, acc_ref, *,
                     scale: float, window: int, logit_cap: float, q_len: int):
    """Fold the in-flight candidate block into the online-softmax scratch
    (causal within the fed tokens — query row i attends to candidates
    j <= i).  Shared by the single-stage verify epilogue and the two-stage
    verify kernels (which fold candidates into the LAST split only, keeping
    stage 2 a layout-agnostic LSE merge)."""
    ri = jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 0)
    cj = jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 1)
    cand_valid = cj <= ri
    if window > 0:
        cand_valid = jnp.logical_and(cand_valid, cj > ri - window)
    _online_softmax_update(
        q_ref[0, 0].astype(jnp.float32),
        kn_ref[0, 0].astype(jnp.float32),
        vn_ref[0, 0].astype(jnp.float32),
        cand_valid, m_ref, l_ref, acc_ref, scale=scale, logit_cap=logit_cap)


def _fold_candidates_and_finish(q_ref, kn_ref, vn_ref, o_ref, m_ref, l_ref,
                                acc_ref, *, scale: float, window: int,
                                logit_cap: float, q_len: int):
    """Verify-kernel epilogue, shared by the ring and paged variants: fold
    the in-flight candidate block, then normalize into the output tile."""
    _fold_candidates(q_ref, kn_ref, vn_ref, m_ref, l_ref, acc_ref,
                     scale=scale, window=window, logit_cap=logit_cap,
                     q_len=q_len)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _write_partials(part_ref, lse_ref, m_ref, l_ref, acc_ref):
    """Stage-1 epilogue: flush this split's scratch as a *normalized*
    partial output plus its log-sum-exp.

    ``partial = acc / max(l, eps)`` and ``lse = m + log(l)`` make the
    stage-2 merge exact: ``partial_s * l_s e^{m_s} = acc_s e^{m_s}``, so
    weighting partials by ``softmax(lse)`` recovers the single-sweep
    softmax identically.  A split whose blocks were all masked (ring not
    yet wrapped, window, or the clamp padding of a non-divisible split
    count) still has ``l == 0`` — its lse is pinned to NEG_INF so the
    merge weighs it to exactly zero instead of NaN-ing on log(0).

    The lse block is one ``(1, R)`` row: the chip's tiling rule requires
    a block's last two dims to equal the array's (or divide (8, 128)), so
    the lse array carries a unit dim before its rows and the wrapper drops
    it."""
    l = l_ref[...]
    part_ref[0, 0, 0] = acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
    lse_ref[0, 0, 0] = jnp.where(
        l > 0.0, m_ref[...] + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)[None, :]


def _lse_merge_kernel(part_ref, lse_ref, o_ref):
    """Stage 2: numerically-exact online-softmax reduction over splits.

    One (flattened batch*head) tile per grid step: renormalize every
    split's partial by its share of the global denominator.  All-empty
    rows (every lse == NEG_INF) degrade to a uniform average of partials —
    finite garbage, same contract as the single-stage kernels' masked-row
    behaviour."""
    lse = lse_ref[0]                                     # (S, R)
    m = jnp.max(lse, axis=0)                             # (R,)
    w = jnp.exp(lse - m[None, :])                        # (S, R)
    den = jnp.maximum(jnp.sum(w, axis=0), 1e-30)         # (R,)
    acc = jnp.sum(part_ref[0] * w[..., None], axis=0)    # (R, Dv)
    o_ref[0] = (acc / den[:, None]).astype(o_ref.dtype)


def merge_kv_splits_pallas(partial: jax.Array, lse: jax.Array, *,
                           out_dtype, interpret: bool = False) -> jax.Array:
    """Merge stage-1 split partials: ``partial (..., S, R, Dv)`` fp32 +
    ``lse (..., S, R)`` fp32 -> ``(..., R, Dv)`` in ``out_dtype``.

    The ONE stage-2 kernel shared by all four sweep families (ring/paged x
    decode/verify) and by the chunked-prefill path that reuses the paged
    verify sweep — the merge is layout-agnostic because stage 1 already
    folded every layout quirk (ring arithmetic, block tables, in-flight
    candidates) into the partial/lse contract."""
    lead = partial.shape[:-3]
    S, R, Dv = partial.shape[-3:]
    pf = partial.reshape((-1, S, R, Dv))
    lf = lse.reshape((-1, S, R))
    N = pf.shape[0]
    out = pl.pallas_call(
        _lse_merge_kernel,
        name="split_kv_merge",
        grid=(N,),
        in_specs=[pl.BlockSpec((1, S, R, Dv), lambda n: (n, 0, 0, 0)),
                  pl.BlockSpec((1, S, R), lambda n: (n, 0, 0))],
        out_specs=pl.BlockSpec((1, R, Dv), lambda n: (n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, R, Dv), out_dtype),
        interpret=interpret,
    )(pf, lf)
    return out.reshape(lead + (R, Dv))


def _split_blocks(n_blocks: int, n_splits: int) -> tuple[int, int]:
    """Clamp the split count to the block count and size each split's
    contiguous block slice (ceil — the last split may sweep fewer blocks
    when the counts don't divide)."""
    s = max(1, min(int(n_splits), n_blocks))
    return s, -(-n_blocks // s)


def _decode_kernel(pos_ref, *refs, scale: float, window: int,
                   logit_cap: float, block_k: int, n_k: int, cache_len: int,
                   quantized: bool = False, batch_pos: bool = False):
    if quantized:
        (q_ref, k_ref, ks_ref, v_ref, vs_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # batch_pos: ragged batch of private ring buffers (windowed paged
    # layers) — each batch row decodes at its own position
    pos = pos_ref[pl.program_id(0)] if batch_pos else pos_ref[0]
    # ring invariant: slot s holds absolute position pos - ((pos - s) mod C);
    # slots not yet written resolve to negative positions and mask off.
    slot = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    k_pos = pos - jnp.remainder(pos - slot, cache_len)
    valid = k_pos >= 0
    if window > 0:
        valid = jnp.logical_and(valid, k_pos > pos - window)

    # blocks with no valid slot (wholly past pos, or wholly outside the
    # window) contribute nothing — skip their MXU work entirely
    @pl.when(jnp.any(valid))
    def _compute():
        _online_softmax_update(
            q_ref[0, 0].astype(jnp.float32),                 # (1, D)
            _load_kv(k_ref, ks_ref),                         # (bk, D)
            _load_kv(v_ref, vs_ref),                         # (bk, Dv)
            valid, m_ref, l_ref, acc_ref, scale=scale, logit_cap=logit_cap)

    @pl.when(ik == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _decode_partials_kernel(pos_ref, *refs, scale: float,
                            window: int, logit_cap: float, block_k: int,
                            n_k: int, kpb: int, cache_len: int,
                            quantized: bool = False, batch_pos: bool = False):
    """Stage 1 of the two-stage ring decode sweep: grid
    ``(B, Hq, n_splits, kpb)``.  Split ``s`` owns global k-blocks
    ``[s*kpb, (s+1)*kpb)``; its scratch is private (init at local block 0,
    flushed as (partial, lse) at local block kpb-1) so splits have no
    cross-split dependency.  Blocks past ``n_k`` (non-divisible split
    counts — the index_map clamps their DMA to the last real block) mask
    off wholly."""
    if quantized:
        (q_ref, k_ref, ks_ref, v_ref, vs_ref,
         part_ref, lse_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref,
         part_ref, lse_ref, m_ref, l_ref, acc_ref) = refs
        ks_ref = vs_ref = None
    isp, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[pl.program_id(0)] if batch_pos else pos_ref[0]
    g = isp * kpb + ik                       # global k-block index
    slot = g * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    k_pos = pos - jnp.remainder(pos - slot, cache_len)
    valid = (k_pos >= 0) & (g < n_k)
    if window > 0:
        valid = jnp.logical_and(valid, k_pos > pos - window)

    @pl.when(jnp.any(valid))
    def _compute():
        _online_softmax_update(
            q_ref[0, 0].astype(jnp.float32),
            _load_kv(k_ref, ks_ref),
            _load_kv(v_ref, vs_ref),
            valid, m_ref, l_ref, acc_ref, scale=scale, logit_cap=logit_cap)

    @pl.when(ik == kpb - 1)
    def _flush():
        _write_partials(part_ref, lse_ref, m_ref, l_ref, acc_ref)


def decode_attention_pallas_partials(
    q: jax.Array,                  # (B, 1, Hq, D)
    k_cache: jax.Array,            # (B, C, Hkv, D)   ring buffer, storage dtype
    v_cache: jax.Array,            # (B, C, Hkv, Dv)
    pos: jax.Array,                # () int32 absolute position of q
    *,
    n_splits: int, window: int = 0, logit_cap: float = 0.0,
    scale: float | None = None, block_k: int = 256, interpret: bool = False,
    k_scale: jax.Array | None = None,  # (B, C, Hkv, 1) fp32; int8 caches only
    v_scale: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Stage 1 only: per-split partial sweep over the ring cache.

    Returns ``(partial (B, Hq, S, 1, Dv) fp32, lse (B, Hq, S, 1) fp32)``
    — the two-stage contract validated against
    ``ref.decode_attention_split_ref``."""
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), \
        "k_scale and v_scale must be given together"
    B, _, Hq, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    block_k = min(block_k, C)
    if C % block_k:
        block_k = next(b for b in range(block_k, 0, -1) if C % b == 0)
    n_k = C // block_k
    n_splits, kpb = _split_blocks(n_k, n_splits)

    qt = q.transpose(0, 2, 1, 3)                 # (B, Hq, 1, D)
    kt = k_cache.transpose(0, 2, 1, 3)           # (B, Hkv, C, D)
    vt = v_cache.transpose(0, 2, 1, 3)           # (B, Hkv, C, Dv)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(-1)
    if pos_arr.shape[0] not in (1, B):
        raise ValueError(f"pos must be scalar or ({B},), got {pos_arr.shape}")

    kernel = functools.partial(
        _decode_partials_kernel, scale=scale, window=window,
        logit_cap=logit_cap, block_k=block_k, n_k=n_k, kpb=kpb, cache_len=C,
        quantized=quantized, batch_pos=pos_arr.shape[0] > 1)

    def kv_index(b, h, s, ik, pos_ref, G=G, kpb=kpb, n_k=n_k):
        # clamp out-of-range blocks of the ragged last split to a real
        # block: its DMA lands somewhere valid and the kernel masks it off
        return (b, h // G, jnp.minimum(s * kpb + ik, n_k - 1), 0)

    in_specs = [pl.BlockSpec((1, 1, 1, D),
                             lambda b, h, s, ik, pos_ref: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, D), kv_index)]
    inputs = [qt, kt]
    if quantized:              # scale blocks ride the k/v index_map
        in_specs.append(pl.BlockSpec((1, 1, block_k, 1), kv_index))
        inputs.append(k_scale.transpose(0, 2, 1, 3))     # (B, Hkv, C, 1)
    in_specs.append(pl.BlockSpec((1, 1, block_k, Dv), kv_index))
    inputs.append(vt)
    if quantized:
        in_specs.append(pl.BlockSpec((1, 1, block_k, 1), kv_index))
        inputs.append(v_scale.transpose(0, 2, 1, 3))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, n_splits, kpb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, 1, 1, Dv),
                         lambda b, h, s, ik, pos_ref: (b, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, 1),
                         lambda b, h, s, ik, pos_ref: (b, h, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),       # running max m
            pltpu.VMEM((1,), jnp.float32),       # running denom l
            pltpu.VMEM((1, Dv), jnp.float32),    # running numerator
        ],
    )
    partial, lse = pl.pallas_call(
        kernel,
        name="ring_decode_partials",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hq, n_splits, 1, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hq, n_splits, 1, 1), jnp.float32)],
        interpret=interpret,
    )(pos_arr, *inputs)
    return partial, lse[:, :, :, 0]


def decode_attention_pallas(
    q: jax.Array,                  # (B, 1, Hq, D)
    k_cache: jax.Array,            # (B, C, Hkv, D)   ring buffer, storage dtype
    v_cache: jax.Array,            # (B, C, Hkv, Dv)
    pos: jax.Array,                # () int32 absolute position of q
    *,
    window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    block_k: int = 256, n_splits: int = 1, interpret: bool = False,
    k_scale: jax.Array | None = None,  # (B, C, Hkv, 1) fp32; int8 caches only
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Split-K decode attention against the canonical ring-buffer cache
    (slot = p % C).  Assumes that invariant — callers with an arbitrary
    ``k_pos`` layout must use the jnp/ref paths.  ``n_splits > 1`` runs
    the two-stage pipeline (parallel partial sweeps + LSE merge);
    ``n_splits = 1`` is the original single-kernel sweep, unchanged.
    ``k_scale``/``v_scale`` (per-row fp32) flag an int8 cache: the dequant
    fuses into the block load (``_load_kv``), nothing else changes.
    ``pos`` may be scalar (one shared position — the fused serve loop) or
    ``(B,)`` (ragged batch of private ring buffers — the paged engine's
    windowed layers, where each slot's ring is at its own position)."""
    if n_splits > 1:
        partial, lse = decode_attention_pallas_partials(
            q, k_cache, v_cache, pos, n_splits=n_splits, window=window,
            logit_cap=logit_cap, scale=scale, block_k=block_k,
            k_scale=k_scale, v_scale=v_scale, interpret=interpret)
        out = merge_kv_splits_pallas(partial, lse, out_dtype=q.dtype,
                                     interpret=interpret)   # (B, Hq, 1, Dv)
        return out.transpose(0, 2, 1, 3)                    # (B, 1, Hq, Dv)
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), \
        "k_scale and v_scale must be given together"
    B, _, Hq, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    block_k = min(block_k, C)
    if C % block_k:
        # largest divisor of C that still fits the requested block: keeps the
        # split-K streaming (and its VMEM budget) instead of degrading to one
        # whole-cache block
        block_k = next(b for b in range(block_k, 0, -1) if C % b == 0)
    n_k = C // block_k

    qt = q.transpose(0, 2, 1, 3)                 # (B, Hq, 1, D)
    kt = k_cache.transpose(0, 2, 1, 3)           # (B, Hkv, C, D)
    vt = v_cache.transpose(0, 2, 1, 3)           # (B, Hkv, C, Dv)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(-1)
    if pos_arr.shape[0] not in (1, B):
        raise ValueError(f"pos must be scalar or ({B},), got {pos_arr.shape}")

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, logit_cap=logit_cap,
        block_k=block_k, n_k=n_k, cache_len=C, quantized=quantized,
        batch_pos=pos_arr.shape[0] > 1)

    def kv_index(b, h, ik, pos_ref, G=G):
        return (b, h // G, ik, 0)

    in_specs = [pl.BlockSpec((1, 1, 1, D),
                             lambda b, h, ik, pos_ref: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, D), kv_index)]
    inputs = [qt, kt]
    if quantized:              # scale blocks ride the k/v index_map
        in_specs.append(pl.BlockSpec((1, 1, block_k, 1), kv_index))
        inputs.append(k_scale.transpose(0, 2, 1, 3))     # (B, Hkv, C, 1)
    in_specs.append(pl.BlockSpec((1, 1, block_k, Dv), kv_index))
    inputs.append(vt)
    if quantized:
        in_specs.append(pl.BlockSpec((1, 1, block_k, 1), kv_index))
        inputs.append(v_scale.transpose(0, 2, 1, 3))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, 1, Dv),
                               lambda b, h, ik, pos_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),       # running max m
            pltpu.VMEM((1,), jnp.float32),       # running denom l
            pltpu.VMEM((1, Dv), jnp.float32),    # running numerator
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="ring_decode_sweep",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, Dv), q.dtype),
        interpret=interpret,
    )(pos_arr, *inputs)
    return out.transpose(0, 2, 1, 3)             # (B, 1, Hq, Dv)


def _verify_kernel(pos_ref, *refs, scale: float, window: int,
                   logit_cap: float, block_k: int, n_k: int, cache_len: int,
                   q_len: int, quantized: bool = False):
    """Multi-query speculative verify against the ring cache.

    Same split-K streaming as ``_decode_kernel`` but with ``q_len = K+1``
    query rows sharing one cache sweep — the online-softmax state is per
    query row.  Query row i sits at absolute position ``pos + i``; the
    cache is committed through ``pos - 1`` and the fed block's own k/v
    arrive as a separate in-flight input (``kn/vn``) folded in at the last
    grid step, so nothing speculative ever lands in HBM.  Ring-eviction
    semantics (``k_pos > q_pos - C``) mask the entries the sequential loop
    would already have overwritten by query i.  In quantized mode only the
    CACHE carries scales — the in-flight candidates stay unquantized."""
    if quantized:
        (q_ref, k_ref, ks_ref, v_ref, vs_ref, kn_ref, vn_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, kn_ref, vn_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
        ks_ref = vs_ref = None
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[0]
    qi = jax.lax.broadcasted_iota(jnp.int32, (q_len, block_k), 0)
    q_pos = pos + qi
    slot = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (q_len, block_k), 1)
    last = pos - 1                    # committed through pos - 1
    k_pos = last - jnp.remainder(last - slot, cache_len)
    valid = (k_pos >= 0) & (k_pos > q_pos - cache_len)
    if window > 0:
        valid = jnp.logical_and(valid, k_pos > q_pos - window)

    @pl.when(jnp.any(valid))
    def _compute():
        _online_softmax_update(
            q_ref[0, 0].astype(jnp.float32),                 # (Q, D)
            _load_kv(k_ref, ks_ref),
            _load_kv(v_ref, vs_ref),
            valid, m_ref, l_ref, acc_ref, scale=scale, logit_cap=logit_cap)

    @pl.when(ik == n_k - 1)
    def _finish():
        _fold_candidates_and_finish(
            q_ref, kn_ref, vn_ref, o_ref, m_ref, l_ref, acc_ref,
            scale=scale, window=window, logit_cap=logit_cap, q_len=q_len)


def _verify_partials_kernel(pos_ref, *refs,
                            scale: float, window: int, logit_cap: float,
                            block_k: int, n_k: int, kpb: int, n_splits: int,
                            cache_len: int, q_len: int,
                            quantized: bool = False):
    """Stage 1 of the two-stage ring verify sweep.  Same masks as
    ``_verify_kernel``; the in-flight candidate block folds into the LAST
    split's scratch just before its flush, so stage 2 stays the generic
    LSE merge (no candidate-aware merge variant needed)."""
    if quantized:
        (q_ref, k_ref, ks_ref, v_ref, vs_ref, kn_ref, vn_ref,
         part_ref, lse_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, kn_ref, vn_ref,
         part_ref, lse_ref, m_ref, l_ref, acc_ref) = refs
        ks_ref = vs_ref = None
    isp, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[0]
    g = isp * kpb + ik
    qi = jax.lax.broadcasted_iota(jnp.int32, (q_len, block_k), 0)
    q_pos = pos + qi
    slot = g * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (q_len, block_k), 1)
    last = pos - 1                    # committed through pos - 1
    k_pos = last - jnp.remainder(last - slot, cache_len)
    valid = (k_pos >= 0) & (k_pos > q_pos - cache_len) & (g < n_k)
    if window > 0:
        valid = jnp.logical_and(valid, k_pos > q_pos - window)

    @pl.when(jnp.any(valid))
    def _compute():
        _online_softmax_update(
            q_ref[0, 0].astype(jnp.float32),                 # (Q, D)
            _load_kv(k_ref, ks_ref),
            _load_kv(v_ref, vs_ref),
            valid, m_ref, l_ref, acc_ref, scale=scale, logit_cap=logit_cap)

    @pl.when((ik == kpb - 1) & (isp == n_splits - 1))
    def _fold():
        _fold_candidates(q_ref, kn_ref, vn_ref, m_ref, l_ref, acc_ref,
                         scale=scale, window=window, logit_cap=logit_cap,
                         q_len=q_len)

    @pl.when(ik == kpb - 1)
    def _flush():
        _write_partials(part_ref, lse_ref, m_ref, l_ref, acc_ref)


def verify_attention_pallas_partials(
    q: jax.Array,                  # (B, Q, Hq, D)   Q = K+1 fed tokens
    k_cache: jax.Array,            # (B, C, Hkv, D)  committed through pos-1
    v_cache: jax.Array,            # (B, C, Hkv, Dv)
    k_new: jax.Array,              # (B, Q, Hkv, D)  in-flight candidate rows
    v_new: jax.Array,              # (B, Q, Hkv, Dv)
    pos: jax.Array,                # () int32 absolute position of q[:, 0]
    *,
    n_splits: int, window: int = 0, logit_cap: float = 0.0,
    scale: float | None = None, block_k: int = 256, interpret: bool = False,
    k_scale: jax.Array | None = None,  # (B, C, Hkv, 1) fp32; int8 caches only
    v_scale: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Stage 1 only: per-split verify sweep over the ring cache, candidates
    folded into the last split.  Returns ``(partial (B, Hq, S, Q, Dv) fp32,
    lse (B, Hq, S, Q) fp32)``."""
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), \
        "k_scale and v_scale must be given together"
    B, Q, Hq, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = Hq // Hkv
    if Q > C:
        raise ValueError(f"verify block {Q} exceeds cache capacity {C}")
    if scale is None:
        scale = D ** -0.5
    block_k = min(block_k, C)
    if C % block_k:
        block_k = next(b for b in range(block_k, 0, -1) if C % b == 0)
    n_k = C // block_k
    n_splits, kpb = _split_blocks(n_k, n_splits)

    qt = q.transpose(0, 2, 1, 3)                 # (B, Hq, Q, D)
    kt = k_cache.transpose(0, 2, 1, 3)           # (B, Hkv, C, D)
    vt = v_cache.transpose(0, 2, 1, 3)           # (B, Hkv, C, Dv)
    knt = k_new.transpose(0, 2, 1, 3)            # (B, Hkv, Q, D)
    vnt = v_new.transpose(0, 2, 1, 3)            # (B, Hkv, Q, Dv)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(
        _verify_partials_kernel, scale=scale, window=window,
        logit_cap=logit_cap, block_k=block_k, n_k=n_k, kpb=kpb,
        n_splits=n_splits, cache_len=C, q_len=Q, quantized=quantized)

    def kv_index(b, h, s, ik, pos_ref, G=G, kpb=kpb, n_k=n_k):
        return (b, h // G, jnp.minimum(s * kpb + ik, n_k - 1), 0)

    in_specs = [pl.BlockSpec((1, 1, Q, D),
                             lambda b, h, s, ik, pos_ref: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, D), kv_index)]
    inputs = [qt, kt]
    if quantized:              # scale blocks ride the k/v index_map
        in_specs.append(pl.BlockSpec((1, 1, block_k, 1), kv_index))
        inputs.append(k_scale.transpose(0, 2, 1, 3))     # (B, Hkv, C, 1)
    in_specs.append(pl.BlockSpec((1, 1, block_k, Dv), kv_index))
    inputs.append(vt)
    if quantized:
        in_specs.append(pl.BlockSpec((1, 1, block_k, 1), kv_index))
        inputs.append(v_scale.transpose(0, 2, 1, 3))
    in_specs += [
        pl.BlockSpec((1, 1, Q, D),
                     lambda b, h, s, ik, pos_ref, G=G: (b, h // G, 0, 0)),
        pl.BlockSpec((1, 1, Q, Dv),
                     lambda b, h, s, ik, pos_ref, G=G: (b, h // G, 0, 0)),
    ]
    inputs += [knt, vnt]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, n_splits, kpb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, Dv),
                         lambda b, h, s, ik, pos_ref: (b, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q),
                         lambda b, h, s, ik, pos_ref: (b, h, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Q,), jnp.float32),       # running max m, per query
            pltpu.VMEM((Q,), jnp.float32),       # running denom l
            pltpu.VMEM((Q, Dv), jnp.float32),    # running numerator
        ],
    )
    partial, lse = pl.pallas_call(
        kernel,
        name="ring_verify_partials",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hq, n_splits, Q, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hq, n_splits, 1, Q), jnp.float32)],
        interpret=interpret,
    )(pos_arr, *inputs)
    return partial, lse[:, :, :, 0]


def verify_attention_pallas(
    q: jax.Array,                  # (B, Q, Hq, D)   Q = K+1 fed tokens
    k_cache: jax.Array,            # (B, C, Hkv, D)  committed through pos-1
    v_cache: jax.Array,            # (B, C, Hkv, Dv)
    k_new: jax.Array,              # (B, Q, Hkv, D)  in-flight candidate rows
    v_new: jax.Array,              # (B, Q, Hkv, Dv)
    pos: jax.Array,                # () int32 absolute position of q[:, 0]
    *,
    window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    block_k: int = 256, n_splits: int = 1, interpret: bool = False,
    k_scale: jax.Array | None = None,  # (B, C, Hkv, 1) fp32; int8 caches only
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Split-K speculative verify attention against the canonical ring
    cache.  Assumes the ring invariant for the *committed* prefix (last
    write at ``(pos - 1) % C``); the fed block's candidates never touch the
    cache — rejection therefore needs no rollback.  ``n_splits > 1`` runs
    the two-stage pipeline; ``n_splits = 1`` is the original sweep.
    ``k_scale``/``v_scale`` flag an int8 cache (fused dequant in the block
    load); candidates are never quantized."""
    if n_splits > 1:
        partial, lse = verify_attention_pallas_partials(
            q, k_cache, v_cache, k_new, v_new, pos, n_splits=n_splits,
            window=window, logit_cap=logit_cap, scale=scale, block_k=block_k,
            k_scale=k_scale, v_scale=v_scale, interpret=interpret)
        out = merge_kv_splits_pallas(partial, lse, out_dtype=q.dtype,
                                     interpret=interpret)   # (B, Hq, Q, Dv)
        return out.transpose(0, 2, 1, 3)                    # (B, Q, Hq, Dv)
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), \
        "k_scale and v_scale must be given together"
    B, Q, Hq, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = Hq // Hkv
    if Q > C:
        raise ValueError(f"verify block {Q} exceeds cache capacity {C}")
    if scale is None:
        scale = D ** -0.5
    block_k = min(block_k, C)
    if C % block_k:
        block_k = next(b for b in range(block_k, 0, -1) if C % b == 0)
    n_k = C // block_k

    qt = q.transpose(0, 2, 1, 3)                 # (B, Hq, Q, D)
    kt = k_cache.transpose(0, 2, 1, 3)           # (B, Hkv, C, D)
    vt = v_cache.transpose(0, 2, 1, 3)           # (B, Hkv, C, Dv)
    knt = k_new.transpose(0, 2, 1, 3)            # (B, Hkv, Q, D)
    vnt = v_new.transpose(0, 2, 1, 3)            # (B, Hkv, Q, Dv)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(
        _verify_kernel, scale=scale, window=window, logit_cap=logit_cap,
        block_k=block_k, n_k=n_k, cache_len=C, q_len=Q, quantized=quantized)

    def kv_index(b, h, ik, pos_ref, G=G):
        return (b, h // G, ik, 0)

    in_specs = [pl.BlockSpec((1, 1, Q, D),
                             lambda b, h, ik, pos_ref: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, D), kv_index)]
    inputs = [qt, kt]
    if quantized:              # scale blocks ride the k/v index_map
        in_specs.append(pl.BlockSpec((1, 1, block_k, 1), kv_index))
        inputs.append(k_scale.transpose(0, 2, 1, 3))     # (B, Hkv, C, 1)
    in_specs.append(pl.BlockSpec((1, 1, block_k, Dv), kv_index))
    inputs.append(vt)
    if quantized:
        in_specs.append(pl.BlockSpec((1, 1, block_k, 1), kv_index))
        inputs.append(v_scale.transpose(0, 2, 1, 3))
    in_specs += [
        pl.BlockSpec((1, 1, Q, D),
                     lambda b, h, ik, pos_ref, G=G: (b, h // G, 0, 0)),
        pl.BlockSpec((1, 1, Q, Dv),
                     lambda b, h, ik, pos_ref, G=G: (b, h // G, 0, 0)),
    ]
    inputs += [knt, vnt]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, Q, Dv),
                               lambda b, h, ik, pos_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Q,), jnp.float32),       # running max m, per query
            pltpu.VMEM((Q,), jnp.float32),       # running denom l
            pltpu.VMEM((Q, Dv), jnp.float32),    # running numerator
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="ring_verify_sweep",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Q, Dv), q.dtype),
        interpret=interpret,
    )(pos_arr, *inputs)
    return out.transpose(0, 2, 1, 3)             # (B, Q, Hq, Dv)


def _paged_verify_kernel(bt_ref, pos_ref, *refs, scale: float,
                         window: int, logit_cap: float, page_size: int,
                         n_blocks: int, q_len: int, quantized: bool = False):
    """Paged analogue of ``_verify_kernel``: linear layout (no eviction
    mask), per-request ``pos``, block-table gather in the k/v index_map."""
    if quantized:
        (q_ref, k_ref, ks_ref, v_ref, vs_ref, kn_ref, vn_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, kn_ref, vn_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
        ks_ref = vs_ref = None
    ib, ij = pl.program_id(0), pl.program_id(2)

    @pl.when(ij == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[ib]
    qi = jax.lax.broadcasted_iota(jnp.int32, (q_len, page_size), 0)
    q_pos = pos + qi
    k_pos = ij * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (q_len, page_size), 1)
    valid = k_pos < pos                # committed rows only
    if window > 0:
        valid = jnp.logical_and(valid, k_pos > q_pos - window)

    @pl.when(jnp.any(valid))
    def _compute():
        _online_softmax_update(
            q_ref[0, 0].astype(jnp.float32),                 # (Q, D)
            _load_kv(k_ref, ks_ref),
            _load_kv(v_ref, vs_ref),
            valid, m_ref, l_ref, acc_ref, scale=scale, logit_cap=logit_cap)

    @pl.when(ij == n_blocks - 1)
    def _finish():
        _fold_candidates_and_finish(
            q_ref, kn_ref, vn_ref, o_ref, m_ref, l_ref, acc_ref,
            scale=scale, window=window, logit_cap=logit_cap, q_len=q_len)


def _paged_verify_partials_kernel(bt_ref, pos_ref, *refs, scale: float,
                                  window: int, logit_cap: float,
                                  page_size: int, n_blocks: int, ppb: int,
                                  n_splits: int, q_len: int,
                                  quantized: bool = False):
    """Stage 1 of the two-stage paged verify sweep.  Same masks as
    ``_paged_verify_kernel``; candidates fold into the LAST split only."""
    if quantized:
        (q_ref, k_ref, ks_ref, v_ref, vs_ref, kn_ref, vn_ref,
         part_ref, lse_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, kn_ref, vn_ref,
         part_ref, lse_ref, m_ref, l_ref, acc_ref) = refs
        ks_ref = vs_ref = None
    ib = pl.program_id(0)
    isp, ij = pl.program_id(2), pl.program_id(3)

    @pl.when(ij == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[ib]
    gj = isp * ppb + ij
    qi = jax.lax.broadcasted_iota(jnp.int32, (q_len, page_size), 0)
    q_pos = pos + qi
    k_pos = gj * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (q_len, page_size), 1)
    valid = (k_pos < pos) & (gj < n_blocks)      # committed rows only
    if window > 0:
        valid = jnp.logical_and(valid, k_pos > q_pos - window)

    @pl.when(jnp.any(valid))
    def _compute():
        _online_softmax_update(
            q_ref[0, 0].astype(jnp.float32),                 # (Q, D)
            _load_kv(k_ref, ks_ref),
            _load_kv(v_ref, vs_ref),
            valid, m_ref, l_ref, acc_ref, scale=scale, logit_cap=logit_cap)

    @pl.when((ij == ppb - 1) & (isp == n_splits - 1))
    def _fold():
        _fold_candidates(q_ref, kn_ref, vn_ref, m_ref, l_ref, acc_ref,
                         scale=scale, window=window, logit_cap=logit_cap,
                         q_len=q_len)

    @pl.when(ij == ppb - 1)
    def _flush():
        _write_partials(part_ref, lse_ref, m_ref, l_ref, acc_ref)


def paged_verify_attention_pallas_partials(
    q: jax.Array,                  # (B, Q, Hq, D)
    k_pages: jax.Array,            # (P, ps, Hkv, D)   shared page pool
    v_pages: jax.Array,            # (P, ps, Hkv, Dv)
    k_new: jax.Array,              # (B, Q, Hkv, D)    in-flight candidates
    v_new: jax.Array,              # (B, Q, Hkv, Dv)
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) absolute position of q[:, 0]
    *,
    n_splits: int, window: int = 0, logit_cap: float = 0.0,
    scale: float | None = None, interpret: bool = False,
    k_scale: jax.Array | None = None,  # (P, ps, Hkv, 1) fp32; int8 pools only
    v_scale: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Stage 1 only: per-split paged verify sweep, candidates folded into
    the last split.  Returns ``(partial (B, Hq, S, Q, Dv) fp32,
    lse (B, Hq, S, Q) fp32)``."""
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), \
        "k_scale and v_scale must be given together"
    B, Q, Hq, D = q.shape
    ps, Hkv = k_pages.shape[1], k_pages.shape[2]
    Dv = v_pages.shape[-1]
    nb = block_tables.shape[1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    n_splits, ppb = _split_blocks(nb, n_splits)

    qt = q.transpose(0, 2, 1, 3)                 # (B, Hq, Q, D)
    kt = k_pages.transpose(0, 2, 1, 3)           # (P, Hkv, ps, D)
    vt = v_pages.transpose(0, 2, 1, 3)           # (P, Hkv, ps, Dv)
    knt = k_new.transpose(0, 2, 1, 3)            # (B, Hkv, Q, D)
    vnt = v_new.transpose(0, 2, 1, 3)            # (B, Hkv, Q, Dv)
    bt = jnp.asarray(block_tables, jnp.int32)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(B)

    kernel = functools.partial(
        _paged_verify_partials_kernel, scale=scale, window=window,
        logit_cap=logit_cap, page_size=ps, n_blocks=nb, ppb=ppb,
        n_splits=n_splits, q_len=Q, quantized=quantized)

    def kv_index(b, h, s, j, bt_ref, pos_ref, G=G, ppb=ppb, nb=nb):
        return (bt_ref[b, jnp.minimum(s * ppb + j, nb - 1)], h // G, 0, 0)

    in_specs = [pl.BlockSpec((1, 1, Q, D),
                             lambda b, h, s, j, bt_ref, pos_ref:
                             (b, h, 0, 0)),
                pl.BlockSpec((1, 1, ps, D), kv_index)]
    inputs = [qt, kt]
    if quantized:              # scale blocks ride the k/v index_map
        in_specs.append(pl.BlockSpec((1, 1, ps, 1), kv_index))
        inputs.append(k_scale.transpose(0, 2, 1, 3))     # (P, Hkv, ps, 1)
    in_specs.append(pl.BlockSpec((1, 1, ps, Dv), kv_index))
    inputs.append(vt)
    if quantized:
        in_specs.append(pl.BlockSpec((1, 1, ps, 1), kv_index))
        inputs.append(v_scale.transpose(0, 2, 1, 3))
    in_specs += [
        pl.BlockSpec((1, 1, Q, D),
                     lambda b, h, s, j, bt_ref, pos_ref, G=G:
                     (b, h // G, 0, 0)),
        pl.BlockSpec((1, 1, Q, Dv),
                     lambda b, h, s, j, bt_ref, pos_ref, G=G:
                     (b, h // G, 0, 0)),
    ]
    inputs += [knt, vnt]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # block table + positions
        grid=(B, Hq, n_splits, ppb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, Dv),
                         lambda b, h, s, j, bt_ref, pos_ref: (b, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q),
                         lambda b, h, s, j, bt_ref, pos_ref: (b, h, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Q,), jnp.float32),       # running max m, per query
            pltpu.VMEM((Q,), jnp.float32),       # running denom l
            pltpu.VMEM((Q, Dv), jnp.float32),    # running numerator
        ],
    )
    partial, lse = pl.pallas_call(
        kernel,
        name="paged_verify_partials",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hq, n_splits, Q, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hq, n_splits, 1, Q), jnp.float32)],
        interpret=interpret,
    )(bt, pos_arr, *inputs)
    return partial, lse[:, :, :, 0]


def paged_verify_attention_pallas(
    q: jax.Array,                  # (B, Q, Hq, D)
    k_pages: jax.Array,            # (P, ps, Hkv, D)   shared page pool
    v_pages: jax.Array,            # (P, ps, Hkv, Dv)
    k_new: jax.Array,              # (B, Q, Hkv, D)    in-flight candidates
    v_new: jax.Array,              # (B, Q, Hkv, Dv)
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) absolute position of q[:, 0]
    *,
    window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    n_splits: int = 1, interpret: bool = False,
    k_scale: jax.Array | None = None,  # (P, ps, Hkv, 1) fp32; int8 pools only
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Split-K speculative verify attention over a paged KV cache: same
    block-table gather as ``paged_decode_attention_pallas``, ``q_len = K+1``
    query rows per (b, h) tile, in-flight candidates folded at the last
    grid step.  ``pos`` is per-request (ragged batch).  ``n_splits > 1``
    runs the two-stage pipeline; ``n_splits = 1`` is the original sweep.
    ``k_scale``/``v_scale`` flag an int8 pool (fused dequant in the block
    load); candidates are never quantized."""
    if n_splits > 1:
        partial, lse = paged_verify_attention_pallas_partials(
            q, k_pages, v_pages, k_new, v_new, block_tables, pos,
            n_splits=n_splits, window=window, logit_cap=logit_cap,
            scale=scale, k_scale=k_scale, v_scale=v_scale,
            interpret=interpret)
        out = merge_kv_splits_pallas(partial, lse, out_dtype=q.dtype,
                                     interpret=interpret)   # (B, Hq, Q, Dv)
        return out.transpose(0, 2, 1, 3)                    # (B, Q, Hq, Dv)
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), \
        "k_scale and v_scale must be given together"
    B, Q, Hq, D = q.shape
    ps, Hkv = k_pages.shape[1], k_pages.shape[2]
    Dv = v_pages.shape[-1]
    nb = block_tables.shape[1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5

    qt = q.transpose(0, 2, 1, 3)                 # (B, Hq, Q, D)
    kt = k_pages.transpose(0, 2, 1, 3)           # (P, Hkv, ps, D)
    vt = v_pages.transpose(0, 2, 1, 3)           # (P, Hkv, ps, Dv)
    knt = k_new.transpose(0, 2, 1, 3)            # (B, Hkv, Q, D)
    vnt = v_new.transpose(0, 2, 1, 3)            # (B, Hkv, Q, Dv)
    bt = jnp.asarray(block_tables, jnp.int32)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(B)

    kernel = functools.partial(
        _paged_verify_kernel, scale=scale, window=window, logit_cap=logit_cap,
        page_size=ps, n_blocks=nb, q_len=Q, quantized=quantized)

    def kv_index(b, h, j, bt_ref, pos_ref, G=G):
        return (bt_ref[b, j], h // G, 0, 0)

    in_specs = [pl.BlockSpec((1, 1, Q, D),
                             lambda b, h, j, bt_ref, pos_ref: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, ps, D), kv_index)]
    inputs = [qt, kt]
    if quantized:              # scale blocks ride the k/v index_map
        in_specs.append(pl.BlockSpec((1, 1, ps, 1), kv_index))
        inputs.append(k_scale.transpose(0, 2, 1, 3))     # (P, Hkv, ps, 1)
    in_specs.append(pl.BlockSpec((1, 1, ps, Dv), kv_index))
    inputs.append(vt)
    if quantized:
        in_specs.append(pl.BlockSpec((1, 1, ps, 1), kv_index))
        inputs.append(v_scale.transpose(0, 2, 1, 3))
    in_specs += [
        pl.BlockSpec((1, 1, Q, D),
                     lambda b, h, j, bt_ref, pos_ref, G=G: (b, h // G, 0, 0)),
        pl.BlockSpec((1, 1, Q, Dv),
                     lambda b, h, j, bt_ref, pos_ref, G=G: (b, h // G, 0, 0)),
    ]
    inputs += [knt, vnt]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # block table + positions
        grid=(B, Hq, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, Q, Dv),
                               lambda b, h, j, bt_ref, pos_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Q,), jnp.float32),       # running max m, per query
            pltpu.VMEM((Q,), jnp.float32),       # running denom l
            pltpu.VMEM((Q, Dv), jnp.float32),    # running numerator
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_verify_sweep",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Q, Dv), q.dtype),
        interpret=interpret,
    )(bt, pos_arr, *inputs)
    return out.transpose(0, 2, 1, 3)             # (B, Q, Hq, Dv)


# --------------------------------------------------------------------------
# Paged single-token decode — one grid step per slot x block of pages
# --------------------------------------------------------------------------
#
# The pool is read as it is stored, ``(P, ps, Hkv, D)``: a grid step covers
# ``ppc`` consecutive table entries of one slot, each a whole page (every kv
# head) fetched by its own BlockSpec operand through the block table, and
# folds them into every query head of that slot.  ``ppc`` follows from the
# page's VMEM footprint (``pages_per_block``).  The index maps clamp a
# slot's blocks to those holding keys it may attend, so grid steps outside
# them repeat the previous step's pages and issue no DMA.

_KV_TILE_BYTES = 1 << 20        # VMEM for one pool's block of pages
_MAX_BLOCK_TOKENS = 512


def _vmem_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a ``(rows, cols)`` slab: rows padded to the dtype's
    sublane tile (8 rows of 32 bits, 16 of 16, 32 of 8), columns to 128
    lanes."""
    sub = 32 // itemsize
    return -(-rows // sub) * sub * (-(-cols // 128) * 128) * itemsize


def pages_per_block(page_size: int, n_kv_heads: int, head_dim: int,
                    itemsize: int, n_pages: int, *,
                    quantized: bool = False) -> int:
    """Pages a paged decode grid step covers: as many as keep one pool's
    block of pages (all kv heads, int8 scales included) within
    ``_KV_TILE_BYTES`` of VMEM and within ``_MAX_BLOCK_TOKENS`` tokens,
    never more than the table holds, at least one."""
    token = _vmem_bytes(n_kv_heads, head_dim, itemsize)
    if quantized:
        token += _vmem_bytes(n_kv_heads, 1, 4)
    ppc = min(_KV_TILE_BYTES // (page_size * token),
              _MAX_BLOCK_TOKENS // page_size, n_pages)
    return max(1, ppc)


def paged_decode_grid(batch: int, n_pages: int, ppc: int,
                      n_splits: int | None = None) -> tuple[int, ...]:
    """Grid of the paged decode sweep: ``(B, blocks)`` single-stage
    (``n_splits is None``), or ``(B, S, blocks per split)`` for the
    partials twin, the split axis cutting page blocks (``S`` clamped to the
    block count)."""
    n_blk = -(-n_pages // ppc)
    if n_splits is None:
        return (batch, n_blk)
    s, bps = _split_blocks(n_blk, n_splits)
    return (batch, s, bps)


def _block_range(pos, *, window: int, block_tokens: int):
    """First and last page block holding a key the query at ``pos`` may
    attend (the window's first key .. ``pos``); at least one block."""
    lo = jnp.maximum(pos - window + 1, 0) // block_tokens if window > 0 else 0
    return lo, jnp.maximum(pos // block_tokens, lo)


def _paged_decode_kernel(bt_ref, pos_ref, q_ref, *refs, scale: float,
                         window: int, logit_cap: float, page_size: int,
                         ppc: int, bps: int, quantized: bool, partials: bool):
    """Paged decode sweep over a slot's page blocks, every head at once.

    Single-stage grid ``(B, blocks)``; partials grid ``(B, S, bps)`` where
    split ``s`` owns blocks ``[s*bps, (s+1)*bps)``.  Block ``g`` of slot
    ``b`` holds positions ``[g*T, (g+1)*T)``, ``T = ppc*ps``; its ``ppc``
    pages arrive as ``ppc`` refs per pool, ``(ps, Hkv, D)`` each.  The
    block is stacked to ``(T, Hkv, D)``, turned kv-head-major in VMEM, and
    folded into the slot's ``(Hkv, G)`` query rows by one batched matmul
    per side — q (``(Hkv, G, D)``) and the running m/l/acc stay resident
    across the slot's blocks.  Blocks outside ``_block_range`` skip their
    compute (and, by the index maps, their DMA)."""
    n_pools = 4 if quantized else 2
    pools = [refs[i * ppc:(i + 1) * ppc] for i in range(n_pools)]
    outs, (m_ref, l_ref, acc_ref) = refs[n_pools * ppc:-3], refs[-3:]
    if quantized:
        k_refs, ks_refs, v_refs, vs_refs = pools
    else:
        (k_refs, v_refs), ks_refs, vs_refs = pools, None, None
    T = ppc * page_size
    Hkv, G = q_ref.shape[1:3]
    b = pl.program_id(0)
    if partials:
        j = pl.program_id(2)
        g, last = pl.program_id(1) * bps + j, bps - 1
    else:
        j = g = pl.program_id(1)
        last = pl.num_programs(1) - 1

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]
    lo, hi = _block_range(pos, window=window, block_tokens=T)
    k_pos = g * T + jax.lax.broadcasted_iota(jnp.int32, (1, 1, T), 2)
    valid = k_pos <= pos
    if window > 0:
        valid = jnp.logical_and(valid, k_pos > pos - window)

    @pl.when((g >= lo) & (g <= hi) & jnp.any(valid))
    def _compute():
        # MXU operands in the pool's float dtype (fp32 after int8 dequant),
        # fp32 accumulation and softmax.  bf16 products are exact in fp32,
        # so a bf16 pool's matmuls run at the default precision whatever
        # precision the caller asks of fp32 matmuls, and the fp32 softmax
        # weights enter p @ v as two bf16 terms (high and low part)
        dt = k_refs[0].dtype if not quantized else jnp.float32
        prec = jax.lax.Precision.DEFAULT if dt == jnp.bfloat16 else None

        def block(refs, scale_refs):
            x = jnp.concatenate([r[0] for r in refs], axis=0)   # (T, Hkv, D)
            if scale_refs is not None:
                x = x.astype(jnp.float32) * jnp.concatenate(
                    [r[0] for r in scale_refs], axis=0)
            return jnp.swapaxes(x.astype(dt), 0, 1)             # (Hkv, T, D)

        k, v = block(k_refs, ks_refs), block(v_refs, vs_refs)
        pv = lambda w: jnp.einsum("hgt,htd->hgd", w.astype(dt), v,
                                  precision=prec,
                                  preferred_element_type=jnp.float32)
        s = jnp.einsum("hgd,htd->hgt", q_ref[0].astype(dt), k,
                       precision=prec,
                       preferred_element_type=jnp.float32) * scale
        if logit_cap > 0.0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]                                     # (Hkv, G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=2, keepdims=True)
        acc = acc_ref[...] * corr + pv(p)
        if dt == jnp.bfloat16:
            acc += pv(p - p.astype(dt).astype(jnp.float32))
        acc_ref[...] = acc
        m_ref[...] = m_new

    @pl.when(j == last)
    def _finish():
        # kv-head-major rows are q heads h*G .. h*G+G-1
        for h in range(Hkv):
            rows = slice(h * G, (h + 1) * G)
            l = l_ref[h]                                        # (G, 1)
            out = acc_ref[h] / jnp.maximum(l, 1e-30)            # (G, Dv)
            if partials:
                # split partial and its lse (pinned to NEG_INF where no key
                # was valid, so the merge weighs it to zero)
                outs[0][0, 0, rows] = out
                outs[1][0, 0, rows] = jnp.where(
                    l > 0.0, m_ref[h] + jnp.log(jnp.maximum(l, 1e-30)),
                    NEG_INF)
            else:
                outs[0][0, rows] = out[:, None, :].astype(outs[0].dtype)


def _paged_decode_call(q, k_pages, v_pages, block_tables, pos, *, n_splits,
                       window, logit_cap, scale, interpret, k_scale, v_scale):
    """The paged decode ``pallas_call``: single-stage (``n_splits`` None,
    output ``(B, Hq, 1, Dv)`` in ``q``'s dtype) or stage 1 of the two-stage
    sweep (fp32 ``partial (B, S, Hq, Dv)`` and ``lse (B, S, Hq, 1)``)."""
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), \
        "k_scale and v_scale must be given together"
    B, _, Hq, D = q.shape
    ps, Hkv = k_pages.shape[1:3]
    Dv = v_pages.shape[-1]
    nb = block_tables.shape[1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    ppc = pages_per_block(ps, Hkv, max(D, Dv), k_pages.dtype.itemsize, nb,
                          quantized=quantized)
    grid = paged_decode_grid(B, nb, ppc, n_splits)
    partials, bps, T = len(grid) == 3, grid[-1], ppc * ps

    def page_spec(i, width):
        """Page ``i`` of the grid step's block.  The block is clamped into
        the slot's ``_block_range``; a page past ``pos`` repeats the page
        this operand held one block earlier (or the slot's last page in its
        first block), so steps outside the range, and the tail of the last
        block, fetch nothing new."""
        def index(b, *rest):
            *ids, bt_ref, pos_ref = rest
            g = ids[0] * bps + ids[1] if partials else ids[0]
            p = pos_ref[b]
            lo, hi = _block_range(p, window=window, block_tokens=T)
            g = jnp.clip(g, lo, hi)
            jp, last = g * ppc + i, p // ps
            jp = jnp.where(jp <= last, jp,
                           jnp.where(g > lo, jp - ppc, last))
            return (bt_ref[b, jnp.clip(jp, 0, nb - 1)], 0, 0, 0)
        return pl.BlockSpec((1, ps, Hkv, width), index)

    pools = ([(k_pages, D), (k_scale, 1), (v_pages, Dv), (v_scale, 1)]
             if quantized else [(k_pages, D), (v_pages, Dv)])
    if partials:
        S = grid[1]
        at_split = lambda *shape: pl.BlockSpec(
            (1, 1) + shape, lambda b, s, j, *_: (b, s) + (0,) * len(shape))
        out_specs = [at_split(Hq, Dv), at_split(Hq, 1)]
        out_shape = [jax.ShapeDtypeStruct((B, S, Hq, Dv), jnp.float32),
                     jax.ShapeDtypeStruct((B, S, Hq, 1), jnp.float32)]
    else:
        out_specs = pl.BlockSpec((1, Hq, 1, Dv),
                                 lambda b, j, *_: (b, 0, 0, 0))
        out_shape = jax.ShapeDtypeStruct((B, Hq, 1, Dv), q.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # block table + positions
        grid=grid,
        in_specs=[pl.BlockSpec((1, Hkv, G, D),
                               lambda b, *rest: (b, 0, 0, 0))]
        + [page_spec(i, w) for _, w in pools for i in range(ppc)],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),    # running max m
            pltpu.VMEM((Hkv, G, 1), jnp.float32),    # running denom l
            pltpu.VMEM((Hkv, G, Dv), jnp.float32),   # running numerator
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, window=window,
        logit_cap=logit_cap, page_size=ps, ppc=ppc, bps=bps,
        quantized=quantized, partials=partials)
    return pl.pallas_call(
        kernel,
        name="paged_decode_partials" if partials else "paged_decode_sweep",
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(pos, jnp.int32).reshape(B), q.reshape(B, Hkv, G, D),
      *(x for x, _ in pools for _ in range(ppc)))


def paged_decode_attention_pallas_partials(
    q: jax.Array,                  # (B, 1, Hq, D)
    k_pages: jax.Array,            # (P, ps, Hkv, D)   shared page pool
    v_pages: jax.Array,            # (P, ps, Hkv, Dv)
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) per-request absolute position of q
    *,
    n_splits: int, window: int = 0, logit_cap: float = 0.0,
    scale: float | None = None, interpret: bool = False,
    k_scale: jax.Array | None = None,  # (P, ps, Hkv, 1) fp32; int8 pools only
    v_scale: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Stage 1 only: per-split paged decode sweep, splits cutting page
    blocks.  Returns ``(partial (B, Hq, S, 1, Dv) fp32, lse (B, Hq, S, 1)
    fp32)``."""
    partial, lse = _paged_decode_call(
        q, k_pages, v_pages, block_tables, pos, n_splits=n_splits,
        window=window, logit_cap=logit_cap, scale=scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale)
    return (partial.transpose(0, 2, 1, 3)[:, :, :, None, :],
            lse.transpose(0, 2, 1, 3))


def paged_decode_attention_pallas(
    q: jax.Array,                  # (B, 1, Hq, D)
    k_pages: jax.Array,            # (P, ps, Hkv, D)   shared page pool
    v_pages: jax.Array,            # (P, ps, Hkv, Dv)
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) per-request absolute position of q
    *,
    window: int = 0, logit_cap: float = 0.0, scale: float | None = None,
    n_splits: int = 1, interpret: bool = False,
    k_scale: jax.Array | None = None,  # (P, ps, Hkv, 1) fp32; int8 pools only
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Decode attention over a paged KV cache, read from the pool as stored.

    Grid step ``(b, g)`` covers ``ppc`` consecutive table entries of slot
    ``b`` for every kv head and query head (GQA folds in as ``G = Hq //
    Hkv`` query rows per kv head), gathering the pages through the
    scalar-prefetched block table with one DMA each.  The pool is shared
    across requests — a request's pages need not be contiguous, only its
    table row must list them in logical order.  ``pos`` is per-request
    (ragged batch), so masks and the block range are per slot.
    ``n_splits > 1`` runs the two-stage pipeline; ``n_splits = 1`` is the
    single sweep.  ``k_scale``/``v_scale`` flag an int8 pool: the per-row
    fp32 scales ride the same page DMAs and the dequant multiply is fused
    into the block load."""
    if n_splits > 1:
        partial, lse = paged_decode_attention_pallas_partials(
            q, k_pages, v_pages, block_tables, pos, n_splits=n_splits,
            window=window, logit_cap=logit_cap, scale=scale,
            k_scale=k_scale, v_scale=v_scale, interpret=interpret)
        out = merge_kv_splits_pallas(partial, lse, out_dtype=q.dtype,
                                     interpret=interpret)   # (B, Hq, 1, Dv)
        return out.transpose(0, 2, 1, 3)                    # (B, 1, Hq, Dv)
    out = _paged_decode_call(
        q, k_pages, v_pages, block_tables, pos, n_splits=None, window=window,
        logit_cap=logit_cap, scale=scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale)
    return out.transpose(0, 2, 1, 3)             # (B, 1, Hq, Dv)


# --------------------------------------------------------------------------
# MLA compressed-latent paged decode — absorbed-matmul form
# --------------------------------------------------------------------------
#
# DeepSeek-style MLA caches ONE latent row per token — ``[c_kv | k_rope]``
# of width R = kv_lora_rank + rope_head_dim — shared by every q head
# (~5x fewer KV bytes than the GQA layout at DeepSeek-V2 shapes).  In the
# absorbed-matmul form the query is projected into latent space before the
# sweep (``q_abs = q_nope @ W_uk`` for the compressed block, raw ``q_rope``
# for the rope sub-block), so
#
#     q_abs . c_kv + q_rope . k_rope  =  [q_abs | q_rope] . [c_kv | k_rope]
#
# — one dot of the latent query against the full latent row — and the
# *value* read is the ``[:r_kv]`` slice of the SAME row.  One DMA per page
# therefore serves both k and v for all heads at once, which is why the
# grid here is (B, pages) with every q head in a single tile (the
# multi-row ``_online_softmax_update`` shape the verify kernels use, with
# q_len = Hq) instead of the GQA kernels' (B, Hq, pages): the occupancy
# unit is the page DMA, shared across 128 heads.  This is the aiter-style
# two-stage decomposition: stage-1 split-KV sweep over block-table pages
# emitting per-split ``(partial, lse)``, stage-2 the SAME
# ``merge_kv_splits_pallas`` LSE-merge every other sweep family uses.
# Validated against ``ref.mla_decode_split_ref`` / ``ref.mla_decode_paged_ref``.

def _mla_paged_decode_kernel(bt_ref, pos_ref, q_ref, lat_ref, o_ref,
                             m_ref, l_ref, acc_ref, *, scale: float,
                             logit_cap: float, page_size: int, n_blocks: int,
                             r_kv: int, n_heads: int):
    ib, ij = pl.program_id(0), pl.program_id(1)

    @pl.when(ij == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[ib]
    k_pos = ij * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (n_heads, page_size), 1)
    valid = k_pos <= pos

    @pl.when(jnp.any(valid))
    def _compute():
        lat = lat_ref[0].astype(jnp.float32)             # (ps, R) — one DMA
        _online_softmax_update(
            q_ref[0].astype(jnp.float32),                # (Hq, R)
            lat,                                         # k = full latent row
            lat[:, :r_kv],                               # v = its c_kv slice
            valid, m_ref, l_ref, acc_ref, scale=scale, logit_cap=logit_cap)

    @pl.when(ij == n_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _mla_paged_decode_partials_kernel(bt_ref, pos_ref, q_ref, lat_ref,
                                      part_ref, lse_ref, m_ref, l_ref,
                                      acc_ref, *, scale: float,
                                      logit_cap: float, page_size: int,
                                      n_blocks: int, ppb: int, r_kv: int,
                                      n_heads: int):
    """Stage 1 of the two-stage MLA paged sweep: grid (B, n_splits, ppb),
    same masks as ``_mla_paged_decode_kernel``, each split flushing
    normalized per-head partials + LSE for the shared stage-2 merge."""
    ib, isp, ij = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ij == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[ib]
    gj = isp * ppb + ij
    k_pos = gj * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (n_heads, page_size), 1)
    valid = (k_pos <= pos) & (gj < n_blocks)

    @pl.when(jnp.any(valid))
    def _compute():
        lat = lat_ref[0].astype(jnp.float32)             # (ps, R)
        _online_softmax_update(
            q_ref[0].astype(jnp.float32),                # (Hq, R)
            lat, lat[:, :r_kv],
            valid, m_ref, l_ref, acc_ref, scale=scale, logit_cap=logit_cap)

    @pl.when(ij == ppb - 1)
    def _flush():
        l = l_ref[...]
        part_ref[0, 0] = acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
        lse_ref[0, 0] = jnp.where(
            l > 0.0, m_ref[...] + jnp.log(jnp.maximum(l, 1e-30)),
            NEG_INF)[None, :]


def mla_paged_decode_attention_pallas_partials(
    q_lat: jax.Array,              # (B, 1, Hq, R) latent queries [q_abs|q_rope]
    lat_pages: jax.Array,          # (P, ps, R)    latent page pool
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) per-request absolute position of q
    *,
    r_kv: int, n_splits: int, scale: float, logit_cap: float = 0.0,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Stage 1 only: per-split MLA latent sweep.  Returns
    ``(partial (B, Hq, S, 1, r_kv) fp32, lse (B, Hq, S, 1) fp32)`` — the
    same partials layout as every other decode family, so the identical
    stage-2 merge applies.  ``scale`` is mandatory (MLA scales by the
    decompressed head dim, not R)."""
    B, _, Hq, R = q_lat.shape
    ps = lat_pages.shape[1]
    nb = block_tables.shape[1]
    n_splits, ppb = _split_blocks(nb, n_splits)

    qt = q_lat.reshape(B, Hq, R)
    bt = jnp.asarray(block_tables, jnp.int32)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(B)

    kernel = functools.partial(
        _mla_paged_decode_partials_kernel, scale=scale, logit_cap=logit_cap,
        page_size=ps, n_blocks=nb, ppb=ppb, r_kv=r_kv, n_heads=Hq)

    def lat_index(b, s, j, bt_ref, pos_ref, ppb=ppb, nb=nb):
        return (bt_ref[b, jnp.minimum(s * ppb + j, nb - 1)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # block table + positions
        grid=(B, n_splits, ppb),
        in_specs=[
            pl.BlockSpec((1, Hq, R), lambda b, s, j, bt_ref, pos_ref:
                         (b, 0, 0)),
            pl.BlockSpec((1, ps, R), lat_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Hq, r_kv),
                         lambda b, s, j, bt_ref, pos_ref: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, Hq),      # (1, Hq) row: see _write_partials
                         lambda b, s, j, bt_ref, pos_ref: (b, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hq,), jnp.float32),      # running max m, per head
            pltpu.VMEM((Hq,), jnp.float32),      # running denom l
            pltpu.VMEM((Hq, r_kv), jnp.float32),  # running numerator
        ],
    )
    partial, lse = pl.pallas_call(
        kernel,
        name="mla_paged_decode_partials",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_splits, Hq, r_kv), jnp.float32),
            jax.ShapeDtypeStruct((B, n_splits, 1, Hq), jnp.float32)],
        interpret=interpret,
    )(bt, pos_arr, qt, lat_pages)
    # -> the canonical (B, Hq, S, 1, Dv) partials layout shared by the
    # merge contract and the ref oracle
    return (partial.transpose(0, 2, 1, 3)[:, :, :, None, :],
            lse[:, :, 0].transpose(0, 2, 1)[:, :, :, None])


def mla_paged_decode_attention_pallas(
    q_lat: jax.Array,              # (B, 1, Hq, R) latent queries [q_abs|q_rope]
    lat_pages: jax.Array,          # (P, ps, R)    latent page pool
    block_tables: jax.Array,       # (B, nb) int32
    pos: jax.Array,                # (B,) per-request absolute position of q
    *,
    r_kv: int, scale: float, logit_cap: float = 0.0, n_splits: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """Compressed-latent MLA paged decode.  Returns latent outputs
    ``(B, 1, Hq, r_kv)`` (the W_uv / W_o expansion happens outside, per the
    absorbed form).  ``n_splits > 1`` runs the two-stage pipeline with the
    shared ``merge_kv_splits_pallas``; ``n_splits = 1`` is the single
    sequential sweep, bit-for-bit the stage-1-only result."""
    B, _, Hq, R = q_lat.shape
    ps = lat_pages.shape[1]
    nb = block_tables.shape[1]
    if n_splits > 1:
        partial, lse = mla_paged_decode_attention_pallas_partials(
            q_lat, lat_pages, block_tables, pos, r_kv=r_kv,
            n_splits=n_splits, scale=scale, logit_cap=logit_cap,
            interpret=interpret)
        out = merge_kv_splits_pallas(partial, lse, out_dtype=q_lat.dtype,
                                     interpret=interpret)  # (B, Hq, 1, r_kv)
        return out.transpose(0, 2, 1, 3)                   # (B, 1, Hq, r_kv)

    qt = q_lat.reshape(B, Hq, R)
    bt = jnp.asarray(block_tables, jnp.int32)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(B)

    kernel = functools.partial(
        _mla_paged_decode_kernel, scale=scale, logit_cap=logit_cap,
        page_size=ps, n_blocks=nb, r_kv=r_kv, n_heads=Hq)

    def lat_index(b, j, bt_ref, pos_ref):
        return (bt_ref[b, j], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # block table + positions
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, Hq, R), lambda b, j, bt_ref, pos_ref: (b, 0, 0)),
            pl.BlockSpec((1, ps, R), lat_index),
        ],
        out_specs=pl.BlockSpec((1, Hq, r_kv),
                               lambda b, j, bt_ref, pos_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq,), jnp.float32),      # running max m, per head
            pltpu.VMEM((Hq,), jnp.float32),      # running denom l
            pltpu.VMEM((Hq, r_kv), jnp.float32),  # running numerator
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="mla_paged_decode_sweep",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, r_kv), q_lat.dtype),
        interpret=interpret,
    )(bt, pos_arr, qt, lat_pages)
    return out[:, None]                          # (B, 1, Hq, r_kv)
