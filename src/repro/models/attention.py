"""Attention blocks: GQA (with RoPE / sliding-window / logit softcap) and
DeepSeek-V2 MLA (multi-head latent attention, decoupled RoPE, absorbed decode).

Every init function returns a pytree whose leaves are ``ParamLeaf(array,
logical_axes)``; ``repro.runtime.sharding`` resolves logical axes ("embed",
"q_heads", "mlp", "experts", "vocab", ...) to mesh axes per architecture.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro import quant
from repro.kernels import ops
from repro.models import common
from repro.models.config import ModelConfig


# --------------------------------------------------------------------------
# param leaves with logical sharding axes
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ParamLeaf:
    array: jax.Array
    axes: tuple[str | None, ...]


jax.tree_util.register_pytree_node(
    ParamLeaf,
    lambda leaf: ((leaf.array,), leaf.axes),
    lambda axes, children: ParamLeaf(children[0], axes),
)


def pl_(key, shape, axes, std=None, dtype="float32") -> ParamLeaf:
    arr = (common.fan_in_init(key, shape, dtype=dtype) if std is None
           else common.normal_init(key, shape, std, dtype=dtype))
    return ParamLeaf(arr, axes)


def split_leaves(tree):
    """(params_with_leaves) -> (raw_param_tree, logical_axes_tree)."""
    is_leaf = lambda x: isinstance(x, ParamLeaf)
    params = jax.tree.map(lambda l: l.array, tree, is_leaf=is_leaf)
    axes = jax.tree.map(lambda l: l.axes, tree, is_leaf=is_leaf)
    return params, axes


# ==========================================================================
# GQA attention
# ==========================================================================
def init_gqa(key, cfg: ModelConfig) -> dict[str, Any]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.padded_q_heads, cfg.padded_kv_heads
    kq, kk, kv, ko = common.split_keys(key, 4)
    dt = cfg.param_dtype
    return {
        "wq": pl_(kq, (d, hq, hd), ("embed", "q_heads", None), dtype=dt),
        "wk": pl_(kk, (d, hkv, hd), ("embed", "kv_heads", None), dtype=dt),
        "wv": pl_(kv, (d, hkv, hd), ("embed", "kv_heads", None), dtype=dt),
        "wo": pl_(ko, (hq, hd, d), ("q_heads", None, "embed"), dtype=dt),
    }


def _mask_padded_heads(o, cfg: ModelConfig):
    """Zero the padded heads' outputs: their wq/wk/wv/wo slices then receive
    zero gradient, so the math is exactly the published n_heads model."""
    if not cfg.heads_padded:
        return o
    mask = (jnp.arange(cfg.padded_q_heads) < cfg.n_heads)
    return o * mask[..., None].astype(o.dtype)


def gqa_forward(params, x, positions, cfg: ModelConfig, *, window: int = 0,
                policy: ops.KernelPolicy = ops.DEFAULT_POLICY,
                constrain=None) -> jax.Array:
    """Full-sequence attention (training / prefill). x: (B, S, d)."""
    adt = x.dtype
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(adt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(adt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(adt))
    if constrain is not None:
        q = constrain(q, ("batch", None, "q_heads", None))
        k = constrain(k, ("batch", None, "kv_heads", None))
        v = constrain(v, ("batch", None, "kv_heads", None))
    q = common.apply_rope_partial(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = common.apply_rope_partial(k, positions, cfg.rope_theta, cfg.rope_fraction)
    scale = cfg.query_scale or hd ** -0.5
    o = ops.attention(q, k, v, causal=True, window=window,
                      logit_cap=cfg.attn_logit_softcap, scale=scale,
                      policy=policy)
    o = _mask_padded_heads(o, cfg)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(adt))
    if constrain is not None:
        out = constrain(out, ("batch", None, "embed_act"))
    return out


def gqa_prefill(params, x, positions, cfg: ModelConfig, *, window: int = 0,
                cache_len: int, policy=ops.DEFAULT_POLICY, constrain=None):
    """Prefill: same as forward but also returns (k, v) laid into a cache of
    capacity ``cache_len`` (ring layout, slot = pos % cache_len)."""
    adt = x.dtype
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(adt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(adt))
    k = common.apply_rope_partial(k, positions, cfg.rope_theta, cfg.rope_fraction)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(adt))
    q = common.apply_rope_partial(q, positions, cfg.rope_theta, cfg.rope_fraction)
    scale = cfg.query_scale or cfg.resolved_head_dim ** -0.5
    o = ops.attention(q, k, v, causal=True, window=window,
                      logit_cap=cfg.attn_logit_softcap, scale=scale,
                      policy=policy)
    o = _mask_padded_heads(o, cfg)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(adt))

    S = x.shape[1]
    if cache_len >= S:
        pad = cache_len - S
        k_c = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_c = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:  # ring: keep the last cache_len entries at slot pos % cache_len
        keep_k, keep_v = k[:, -cache_len:], v[:, -cache_len:]
        shift = S % cache_len
        k_c = jnp.roll(keep_k, shift, axis=1)
        v_c = jnp.roll(keep_v, shift, axis=1)
    return out, (k_c, v_c)


def gqa_decode(params, x, pos, cache_kv, cfg: ModelConfig, *, window: int = 0,
               policy: ops.KernelPolicy = ops.DEFAULT_POLICY, constrain=None):
    """One-token decode. x: (B, 1, d); cache_kv = (k, v) ring buffers of
    capacity C; pos: () int32 absolute position of the new token."""
    adt = x.dtype
    k_cache, v_cache = cache_kv
    C = k_cache.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(adt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(adt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(adt))
    posb = jnp.asarray(pos)[None]
    q = common.apply_rope_partial(q, posb, cfg.rope_theta, cfg.rope_fraction)
    k = common.apply_rope_partial(k, posb, cfg.rope_theta, cfg.rope_fraction)
    slot = jnp.mod(pos, C)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                           (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                           (0, slot, 0, 0))
    scale = cfg.query_scale or cfg.resolved_head_dim ** -0.5
    o = ops.decode_attention(q, k_cache, v_cache, pos, window=window,
                             logit_cap=cfg.attn_logit_softcap, scale=scale,
                             policy=policy)
    o = _mask_padded_heads(o, cfg)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(adt))
    return out, (k_cache, v_cache)


def gqa_decode_paged(params, x, pos, cache_kv, block_tables, cfg: ModelConfig,
                     *, window: int = 0,
                     policy: ops.KernelPolicy = ops.DEFAULT_POLICY,
                     constrain=None):
    """One-token decode against a paged KV cache.  x: (B, 1, d);
    cache_kv = (k_pages, v_pages) pools of shape (P, ps, Hkv, *);
    block_tables: (B, nb) physical page per logical block; pos: (B,)
    per-request absolute position of the new token (the batch is ragged —
    every slot of the continuous-batching engine sits at its own depth).

    The new k/v row is scattered into physical row
    ``block_tables[b, pos[b] // ps] * ps + pos[b] % ps`` of the flattened
    pool — slots parked on their scratch page by the engine overwrite that
    scratch harmlessly.

    ``cache_kv`` may also be a 4-tuple ``(k_pages, v_pages, k_scales,
    v_scales)`` (int8 pools, per-row fp32 scales): the new row is
    quantized per (kv-head) row before the scatter, its scale lands at
    the same physical row, and the scales ride into the attention sweep
    for fused dequant.  Returns the cache in the same arity it came."""
    adt = x.dtype
    if len(cache_kv) == 4:
        k_pages, v_pages, k_scales, v_scales = cache_kv
    else:
        k_pages, v_pages = cache_kv
        k_scales = v_scales = None
    P, ps = k_pages.shape[0], k_pages.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(adt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(adt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(adt))
    posb = jnp.asarray(pos)[:, None]                       # (B, 1)
    q = common.apply_rope_partial(q, posb, cfg.rope_theta, cfg.rope_fraction)
    k = common.apply_rope_partial(k, posb, cfg.rope_theta, cfg.rope_fraction)
    page = jnp.take_along_axis(block_tables, pos[:, None] // ps, axis=1)[:, 0]
    row = page * ps + pos % ps                             # (B,)
    k_row, v_row = k[:, 0], v[:, 0]                        # (B, Hkv, hd)
    if k_scales is not None:
        k_row, ks_row = quant.quantize_int8_rows(k_row)
        v_row, vs_row = quant.quantize_int8_rows(v_row)
        ks_flat = k_scales.reshape(P * ps, *k_scales.shape[2:])
        vs_flat = v_scales.reshape(P * ps, *v_scales.shape[2:])
        k_scales = ks_flat.at[row].set(ks_row).reshape(k_scales.shape)
        v_scales = vs_flat.at[row].set(vs_row).reshape(v_scales.shape)
    k_flat = k_pages.reshape(P * ps, *k_pages.shape[2:])
    v_flat = v_pages.reshape(P * ps, *v_pages.shape[2:])
    k_flat = k_flat.at[row].set(k_row.astype(k_flat.dtype))
    v_flat = v_flat.at[row].set(v_row.astype(v_flat.dtype))
    k_pages = k_flat.reshape(k_pages.shape)
    v_pages = v_flat.reshape(v_pages.shape)
    scale = cfg.query_scale or cfg.resolved_head_dim ** -0.5
    o = ops.paged_decode_attention(q, k_pages, v_pages, block_tables, pos,
                                   window=window,
                                   logit_cap=cfg.attn_logit_softcap,
                                   scale=scale, policy=policy,
                                   k_scale=k_scales, v_scale=v_scales)
    o = _mask_padded_heads(o, cfg)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(adt))
    if k_scales is not None:
        return out, (k_pages, v_pages, k_scales, v_scales)
    return out, (k_pages, v_pages)


def gqa_decode_ragged(params, x, pos, cache_kv, cfg: ModelConfig, *,
                      window: int = 0,
                      policy: ops.KernelPolicy = ops.DEFAULT_POLICY,
                      constrain=None):
    """One-token decode against per-slot PRIVATE ring buffers at ragged
    positions.  x: (B, 1, d); cache_kv = (k, v) of shape (B, C, Hkv, *);
    pos: (B,) per-request absolute position of the new token.

    This is the paged engine's windowed-layer decode: a sliding-window
    layer never needs more than the last ``window`` tokens, so its "page
    table" is a static identity map over ``ceil(window/ps)`` pages per
    slot and the pages form a ring of capacity C = ceil(window/ps)*ps —
    O(window) latent bytes per slot regardless of sequence depth.  Each
    batch row writes its own ring slot ``pos[b] % C``; the attention sweep
    masks per-row (``ops.decode_attention`` accepts the ragged ``pos``
    directly on both the Pallas and jnp backends)."""
    adt = x.dtype
    k_cache, v_cache = cache_kv
    B, C = k_cache.shape[0], k_cache.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(adt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(adt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(adt))
    posb = jnp.asarray(pos)[:, None]                       # (B, 1)
    q = common.apply_rope_partial(q, posb, cfg.rope_theta, cfg.rope_fraction)
    k = common.apply_rope_partial(k, posb, cfg.rope_theta, cfg.rope_fraction)
    rows = jnp.arange(B)
    slot = jnp.mod(pos, C)                                 # (B,)
    k_cache = k_cache.at[rows, slot].set(k[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[rows, slot].set(v[:, 0].astype(v_cache.dtype))
    scale = cfg.query_scale or cfg.resolved_head_dim ** -0.5
    o = ops.decode_attention(q, k_cache, v_cache, pos, window=window,
                             logit_cap=cfg.attn_logit_softcap, scale=scale,
                             policy=policy)
    o = _mask_padded_heads(o, cfg)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(adt))
    return out, (k_cache, v_cache)


def gqa_verify(params, x, pos, cache_kv, cfg: ModelConfig, *, window: int = 0,
               policy: ops.KernelPolicy = ops.DEFAULT_POLICY, constrain=None):
    """Speculative verify: score ``Q = K+1`` fed tokens in one cache sweep.

    x: (B, Q, d) — the fed block [t_last, d_1..d_K] at positions
    ``pos .. pos+Q-1``; cache_kv = (k, v) ring buffers committed through
    ``pos - 1``.  Unlike ``gqa_decode``, NOTHING is written to the cache:
    the block's own k/v are returned as *pending* rows for the runtime to
    commit once the accepted prefix is known — rejection needs no rollback,
    and a wrapped ring's history stays intact for re-drafting."""
    adt = x.dtype
    k_cache, v_cache = cache_kv
    Q = x.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(adt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(adt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(adt))
    posq = jnp.asarray(pos)[None] + jnp.arange(Q)[None, :]   # (1, Q)
    q = common.apply_rope_partial(q, posq, cfg.rope_theta, cfg.rope_fraction)
    k = common.apply_rope_partial(k, posq, cfg.rope_theta, cfg.rope_fraction)
    scale = cfg.query_scale or cfg.resolved_head_dim ** -0.5
    o = ops.verify_attention(q, k_cache, v_cache, k, v, pos, window=window,
                             logit_cap=cfg.attn_logit_softcap, scale=scale,
                             policy=policy)
    o = _mask_padded_heads(o, cfg)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(adt))
    return out, (k, v)


def gqa_verify_paged(params, x, pos, cache_kv, block_tables, cfg: ModelConfig,
                     *, window: int = 0,
                     policy: ops.KernelPolicy = ops.DEFAULT_POLICY,
                     constrain=None):
    """Paged analogue of ``gqa_verify``: per-request ``pos`` (B,), shared
    page pools committed through ``pos[b] - 1``.  The pending rows are
    returned for a masked per-slot commit — pools stay untouched here.

    Besides speculative verify, this is the sweep behind **chunked paged
    prefill** (``transformer.prefill_suffix``): a prompt-suffix chunk at
    positions ``pos .. pos+Q-1`` attending to a prefix the cache already
    holds (possibly on pages shared read-only with other slots) is the
    same computation with every row "accepted" at commit time.

    ``cache_kv`` may be the 4-tuple int8 form (see ``gqa_decode_paged``);
    the scales are read-only here — pending rows stay unquantized and are
    quantized (if at all) by ``commit_spec_paged``.  The SWEEP, however,
    must see the in-flight rows at cache precision: a decode step commits
    its row before attending (so it reads the dequantized value), and a
    chunk boundary moves rows between "committed" and "in-flight" — if
    the in-flight side rode through raw, logits would depend on where the
    chunk boundary fell and requeue replay would not be bit-exact.  So the
    candidates are round-tripped through the row quantizer here, exactly
    the (q, scale) pair the commit will write."""
    adt = x.dtype
    if len(cache_kv) == 4:
        k_pages, v_pages, k_scales, v_scales = cache_kv
    else:
        k_pages, v_pages = cache_kv
        k_scales = v_scales = None
    Q = x.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(adt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(adt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(adt))
    posq = jnp.asarray(pos)[:, None] + jnp.arange(Q)[None, :]  # (B, Q)
    q = common.apply_rope_partial(q, posq, cfg.rope_theta, cfg.rope_fraction)
    k = common.apply_rope_partial(k, posq, cfg.rope_theta, cfg.rope_fraction)
    scale = cfg.query_scale or cfg.resolved_head_dim ** -0.5
    if k_scales is not None:
        k_sweep = quant.dequantize_int8_rows(*quant.quantize_int8_rows(k))
        v_sweep = quant.dequantize_int8_rows(*quant.quantize_int8_rows(v))
    else:
        k_sweep, v_sweep = k, v
    o = ops.paged_verify_attention(q, k_pages, v_pages, k_sweep, v_sweep,
                                   block_tables,
                                   pos, window=window,
                                   logit_cap=cfg.attn_logit_softcap,
                                   scale=scale, policy=policy,
                                   k_scale=k_scales, v_scale=v_scales)
    o = _mask_padded_heads(o, cfg)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(adt))
    return out, (k, v)


# ==========================================================================
# MLA (DeepSeek-V2)
# ==========================================================================
def init_mla(key, cfg: ModelConfig) -> dict[str, Any]:
    d = cfg.d_model
    H = cfg.n_heads
    r_kv, r_q = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    keys = common.split_keys(key, 8)
    dt = cfg.param_dtype
    p: dict[str, Any] = {
        # latent kv down-projection (+ shared rope key)
        "wdkv": pl_(keys[0], (d, r_kv + dr), ("embed", None), dtype=dt),
        "kv_norm": ParamLeaf(common.ones((r_kv,), dt), (None,)),
        # up-projections from the latent
        "wuk": pl_(keys[1], (r_kv, H, dn), (None, "q_heads", None), dtype=dt),
        "wuv": pl_(keys[2], (r_kv, H, dv), (None, "q_heads", None), dtype=dt),
        "wo": pl_(keys[3], (H, dv, d), ("q_heads", None, "embed"), dtype=dt),
    }
    if r_q:
        p["wdq"] = pl_(keys[4], (d, r_q), ("embed", None), dtype=dt)
        p["q_norm"] = ParamLeaf(common.ones((r_q,), dt), (None,))
        p["wuq"] = pl_(keys[5], (r_q, H, dn + dr), (None, "q_heads", None), dtype=dt)
    else:
        p["wuq"] = pl_(keys[5], (d, H, dn + dr), ("embed", "q_heads", None), dtype=dt)
    return p


def _mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale over the [nope | rope] query-key dims, times YaRN's
    ``mscale_all_dim`` correction where the config scales its rope."""
    base = cfg.query_scale or (cfg.nope_head_dim + cfg.rope_head_dim) ** -0.5
    return base * common.yarn_softmax_factor(cfg.rope_scaling)


def _mla_queries(params, x, positions, cfg: ModelConfig):
    adt = x.dtype
    dn, dr = cfg.nope_head_dim, cfg.rope_head_dim
    if cfg.q_lora_rank:
        cq = jnp.einsum("bsd,dr->bsr", x, params["wdq"].astype(adt))
        cq = common.rmsnorm(cq, params["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, params["wuq"].astype(adt))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wuq"].astype(adt))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = common.apply_rope(q_rope, positions, cfg.rope_theta,
                               cfg.rope_scaling)
    return q_nope, q_rope


def _mla_latent(params, x, positions, cfg: ModelConfig):
    """Down-project to the compressed latent: returns (c_kv, k_rope)."""
    adt = x.dtype
    r_kv = cfg.kv_lora_rank
    ckv_rope = jnp.einsum("bsd,dr->bsr", x, params["wdkv"].astype(adt))
    c_kv, k_rope = ckv_rope[..., :r_kv], ckv_rope[..., r_kv:]
    c_kv = common.rmsnorm(c_kv, params["kv_norm"], cfg.norm_eps)
    k_rope = common.apply_rope(k_rope[:, :, None, :], positions,
                               cfg.rope_theta, cfg.rope_scaling)[:, :, 0, :]
    return c_kv, k_rope


def mla_forward(params, x, positions, cfg: ModelConfig, *,
                policy=ops.DEFAULT_POLICY, constrain=None,
                return_latent: bool = False):
    """Training/prefill MLA: expand the latent to per-head k/v, run GQA-style
    flash attention with concatenated [nope|rope] q/k."""
    adt = x.dtype
    dr = cfg.rope_head_dim
    q_nope, q_rope = _mla_queries(params, x, positions, cfg)
    c_kv, k_rope = _mla_latent(params, x, positions, cfg)

    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, params["wuk"].astype(adt))
    v = jnp.einsum("bsr,rhk->bshk", c_kv, params["wuv"].astype(adt))
    H = cfg.n_heads
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                (*k_rope.shape[:2], H, dr))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    if constrain is not None:
        q = constrain(q, ("batch", None, "q_heads", None))
        k = constrain(k, ("batch", None, "q_heads", None))
        v = constrain(v, ("batch", None, "q_heads", None))
    scale = _mla_scale(cfg)
    o = ops.attention(q, k, v, causal=True, scale=scale,
                      logit_cap=cfg.attn_logit_softcap, policy=policy)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(adt))
    if constrain is not None:
        out = constrain(out, ("batch", None, "embed_act"))
    if return_latent:
        return out, (c_kv, k_rope)
    return out


def mla_prefill(params, x, positions, cfg: ModelConfig, *, cache_len: int,
                policy=ops.DEFAULT_POLICY, constrain=None):
    """Prefill that also emits the compressed (c_kv, k_rope) cache — the whole
    point of MLA: the cache is rank r_kv + d_rope per token, not H*(dk+dv)."""
    out, (c_kv, k_rope) = mla_forward(params, x, positions, cfg, policy=policy,
                                      constrain=constrain, return_latent=True)
    S = x.shape[1]
    lat = jnp.concatenate([c_kv, k_rope], axis=-1)       # (B, S, r_kv + dr)
    if cache_len >= S:
        lat = jnp.pad(lat, ((0, 0), (0, cache_len - S), (0, 0)))
    else:
        lat = jnp.roll(lat[:, -cache_len:], S % cache_len, axis=1)
    return out, lat


def _mla_expand(params, o_lat, cfg: ModelConfig, adt):
    """Re-expand latent attention outputs through W_uv then W_o.
    o_lat: (B, H, r_kv) -> (B, 1, d)."""
    o = jnp.einsum("bhr,rhk->bhk", o_lat, params["wuv"].astype(adt))
    return jnp.einsum("bhk,hkd->bd", o, params["wo"].astype(adt))[:, None]


def mla_decode(params, x, pos, cache_lat, cfg: ModelConfig, *,
               policy: ops.KernelPolicy = ops.DEFAULT_POLICY, constrain=None):
    """Absorbed-matmul decode: score via q_nope @ W_uk acting on the latent
    cache directly; attention output re-expanded with W_uv afterwards.  The
    attend body is ``ops.mla_absorbed_attend_jnp`` — the SAME body the
    paged jnp path runs, which is what keeps paged greedy streams on the
    ring reference's argmax."""
    adt = x.dtype
    r_kv = cfg.kv_lora_rank
    C = cache_lat.shape[1]
    posb = jnp.asarray(pos)[None]
    q_nope, q_rope = _mla_queries(params, x, posb, cfg)      # (B,1,H,*)
    c_kv, k_rope = _mla_latent(params, x, posb, cfg)         # (B,1,r_kv),(B,1,dr)

    lat_t = jnp.concatenate([c_kv, k_rope], axis=-1)
    slot = jnp.mod(pos, C)
    cache_lat = jax.lax.dynamic_update_slice(
        cache_lat, lat_t.astype(cache_lat.dtype), (0, slot, 0))

    # absorb W_uk into the query:  (B,1,H,dn) @ (r,H,dn) -> (B,H,r)
    q_abs = jnp.einsum("bshk,rhk->bhr", q_nope, params["wuk"].astype(adt))
    scale = _mla_scale(cfg)
    k_pos = pos - jnp.mod(pos - jnp.arange(C), C)
    valid = (k_pos >= 0) & (k_pos <= pos)
    o_lat = ops.mla_absorbed_attend_jnp(
        q_abs, q_rope[:, 0], cache_lat[..., :r_kv].astype(adt),
        cache_lat[..., r_kv:].astype(adt),
        jnp.broadcast_to(valid[None], (x.shape[0], C)),
        scale=scale, logit_cap=cfg.attn_logit_softcap)
    return _mla_expand(params, o_lat, cfg, adt), cache_lat


def mla_decode_paged(params, x, pos, cache_lat, block_tables,
                     cfg: ModelConfig, *,
                     policy: ops.KernelPolicy = ops.DEFAULT_POLICY,
                     constrain=None):
    """One-token absorbed-matmul MLA decode against a PAGED latent pool —
    the model zoo's compressed-KV headline.  x: (B, 1, d);
    cache_lat: (P, ps, R) latent page pool, R = kv_lora_rank +
    rope_head_dim (ONE row per token, every head shares it — ~5x fewer KV
    bytes than the dense-GQA layout); block_tables: (B, nb); pos: (B,)
    ragged per-request position.

    The new latent row is scattered at physical row
    ``block_tables[b, pos[b] // ps] * ps + pos[b] % ps`` (linear layout —
    same scheme as ``gqa_decode_paged``), then the whole query block
    [q_abs | q_rope] sweeps the pool through ``ops.mla_decode_paged``:
    the latent row serves scores AND values, so one page DMA feeds all
    heads."""
    adt = x.dtype
    r_kv = cfg.kv_lora_rank
    P, ps, R = cache_lat.shape
    posb = jnp.asarray(pos)[:, None]                       # (B, 1)
    q_nope, q_rope = _mla_queries(params, x, posb, cfg)    # (B,1,H,*)
    c_kv, k_rope = _mla_latent(params, x, posb, cfg)

    lat_row = jnp.concatenate([c_kv, k_rope], axis=-1)[:, 0]     # (B, R)
    page = jnp.take_along_axis(block_tables, pos[:, None] // ps, axis=1)[:, 0]
    row = page * ps + pos % ps
    lat_flat = cache_lat.reshape(P * ps, R)
    cache_lat = lat_flat.at[row].set(
        lat_row.astype(lat_flat.dtype)).reshape(P, ps, R)

    q_abs = jnp.einsum("bshk,rhk->bhr", q_nope, params["wuk"].astype(adt))
    q_lat = jnp.concatenate([q_abs[:, None], q_rope], axis=-1)   # (B,1,H,R)
    scale = _mla_scale(cfg)
    o_lat = ops.mla_decode_paged(q_lat, cache_lat, block_tables, pos,
                                 r_kv=r_kv, scale=scale,
                                 logit_cap=cfg.attn_logit_softcap,
                                 policy=policy)                  # (B,1,H,r_kv)
    return _mla_expand(params, o_lat[:, 0], cfg, adt), cache_lat


def mla_verify_paged(params, x, pos, cache_lat, block_tables,
                     cfg: ModelConfig, *,
                     policy: ops.KernelPolicy = ops.DEFAULT_POLICY,
                     constrain=None):
    """Multi-query MLA sweep over the paged latent pool (speculative verify
    AND chunked paged prefill — same two callers as ``gqa_verify_paged``).

    No dedicated kernel: in absorbed form the latent pool IS a GQA cache
    with a single shared kv head — k_pages = the pool with an inserted
    head axis (P, ps, 1, R), v_pages = its first r_kv lanes, queries =
    [q_abs | q_rope] (B, Q, H, R) grouped G = H onto that one head — so
    the generic ``ops.paged_verify_attention`` sweep (and its Pallas
    kernel) serves MLA unchanged.  Returns the pending latent rows
    (B, Q, R) for the caller's masked commit; the pool stays untouched."""
    adt = x.dtype
    r_kv = cfg.kv_lora_rank
    Q = x.shape[1]
    posq = jnp.asarray(pos)[:, None] + jnp.arange(Q)[None, :]    # (B, Q)
    q_nope, q_rope = _mla_queries(params, x, posq, cfg)          # (B,Q,H,*)
    c_kv, k_rope = _mla_latent(params, x, posq, cfg)

    lat_new = jnp.concatenate([c_kv, k_rope], axis=-1)           # (B, Q, R)
    q_abs = jnp.einsum("bqhk,rhk->bqhr", q_nope, params["wuk"].astype(adt))
    q_lat = jnp.concatenate([q_abs, q_rope], axis=-1)            # (B,Q,H,R)
    scale = _mla_scale(cfg)
    o_lat = ops.paged_verify_attention(
        q_lat, cache_lat[:, :, None, :], cache_lat[:, :, None, :r_kv],
        lat_new[:, :, None, :].astype(adt),
        lat_new[:, :, None, :r_kv].astype(adt),
        block_tables, pos, scale=scale,
        logit_cap=cfg.attn_logit_softcap, policy=policy)         # (B,Q,H,r_kv)
    o = jnp.einsum("bqhr,rhk->bqhk", o_lat, params["wuv"].astype(adt))
    out = jnp.einsum("bqhk,hkd->bqd", o, params["wo"].astype(adt))
    return out, lat_new
