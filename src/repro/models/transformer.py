"""The LM trunk: init / train-forward / prefill / decode for every assigned
architecture family.

Layers are stacked into *pattern units* and iterated with ``lax.scan`` so the
HLO stays O(1) in depth (46-layer gemma2 compiles as fast as 2 layers):

  * dense / moe / ssm:  unit = 1 layer, scan over n_layers.
  * gemma2 (local/global alternation): unit = 2 layers (sub0 local window,
    sub1 global) — both sublayers are distinct programs in the scan body, so
    compiled FLOPs are honest (no lax.cond double-counting).
  * deepseek (first layer dense): layer 0 unrolled, units = remaining layers.
  * zamba2: unit = ``hybrid_attn_every`` mamba layers + ONE application of a
    *shared* attention block (single param copy, closed over by the scan
    body; Zamba2's core trick).

Each apply function takes a ``RunCtx`` carrying the mesh context, kernel
policy, activation-sharding ``constrain`` hook, and remat policy.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro import quant
from repro.kernels import ops
from repro.models import attention as attn
from repro.models import common, ssm
from repro.models.attention import ParamLeaf, pl_, split_leaves
from repro.models.config import ModelConfig
from repro.models.layers import NO_MESH, ParallelCtx, init_mlp, init_moe, \
    mlp_forward, moe_forward

VOCAB_PAD = 256


def padded_vocab(cfg: ModelConfig) -> int:
    v = cfg.vocab_size
    return (v + VOCAB_PAD - 1) // VOCAB_PAD * VOCAB_PAD


@dataclasses.dataclass(frozen=True)
class RunCtx:
    parallel: ParallelCtx = NO_MESH
    kernel_policy: ops.KernelPolicy = ops.DEFAULT_POLICY
    constrain: Callable[[jax.Array, tuple], jax.Array] | None = None
    remat: str = "none"                 # none | full | dots
    decode_cache_len: int = 0           # 0 -> cfg.max_seq_len


def unit_size(cfg: ModelConfig) -> int:
    if cfg.local_global:
        return 2
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        return cfg.hybrid_attn_every
    return 1


def n_units(cfg: ModelConfig) -> int:
    u = unit_size(cfg)
    body_layers = cfg.n_layers - cfg.first_dense_layers
    if body_layers % u:
        raise ValueError(f"{cfg.name}: {body_layers} layers not divisible by "
                         f"pattern unit {u}")
    return body_layers // u


# ==========================================================================
# init
# ==========================================================================
def _init_block(key, cfg: ModelConfig, *, moe: bool, d_ff: int | None = None):
    """One transformer sublayer: norm -> attn -> norm -> ffn."""
    k1, k2 = common.split_keys(key, 2)
    dt = cfg.param_dtype
    blk: dict[str, Any] = {
        "norm1": ParamLeaf(_norm_init(cfg), (None,)),
        "attn": (attn.init_mla(k1, cfg) if cfg.use_mla else attn.init_gqa(k1, cfg)),
        "norm2": ParamLeaf(_norm_init(cfg), (None,)),
        "ffn": (init_moe(k2, cfg) if moe else init_mlp(k2, cfg, d_ff)),
    }
    if cfg.post_norms:
        blk["post_attn_norm"] = ParamLeaf(_norm_init(cfg), (None,))
        blk["post_ffn_norm"] = ParamLeaf(_norm_init(cfg), (None,))
    return blk


def _norm_init(cfg: ModelConfig):
    # gemma stores (1 + w): init w at 0; others init scale at 1
    if cfg.post_norms:
        return common.zeros((cfg.d_model,), cfg.param_dtype)
    return common.ones((cfg.d_model,), cfg.param_dtype)


def _init_unit(key, cfg: ModelConfig):
    """One pattern unit (see module docstring)."""
    u = unit_size(cfg)
    keys = common.split_keys(key, u)
    unit: dict[str, Any] = {}
    for i in range(u):
        if cfg.uses_ssm:
            unit[f"sub{i}"] = ssm.init_mamba(keys[i], cfg)
        else:
            unit[f"sub{i}"] = _init_block(keys[i], cfg, moe=cfg.uses_moe)
    return unit


def _init_shared_attn(key, cfg: ModelConfig):
    """Zamba2's shared block: input = concat(hidden, embeddings) -> proj to
    d -> attn + MLP -> residual add into the trunk."""
    k0, k1 = common.split_keys(key, 2)
    d = cfg.d_model
    return {
        "w_in": pl_(k0, (2 * d, d), ("embed", "embed_out"), dtype=cfg.param_dtype),
        "block": _init_block(k1, cfg, moe=False),
    }


def init_lm(key, cfg: ModelConfig):
    """Returns (params, logical_axes) raw trees (ParamLeaf already split)."""
    keys = common.split_keys(key, 8)
    Vp = padded_vocab(cfg)
    d = cfg.d_model
    dt = cfg.param_dtype
    tree: dict[str, Any] = {}

    if cfg.n_codebooks:
        tree["embed"] = pl_(keys[0], (cfg.n_codebooks, Vp, d),
                            (None, "vocab", "embed"), std=0.02, dtype=dt)
        tree["lm_head"] = pl_(keys[1], (cfg.n_codebooks, d, Vp),
                              (None, "embed", "vocab"), std=0.02, dtype=dt)
    else:
        tree["embed"] = pl_(keys[0], (Vp, d), ("vocab", "embed"),
                            std=0.02, dtype=dt)
        if not cfg.tie_embeddings:
            tree["lm_head"] = pl_(keys[1], (d, Vp), ("embed", "vocab"),
                                  std=0.02, dtype=dt)

    if cfg.first_dense_layers:
        dense_keys = common.split_keys(keys[2], cfg.first_dense_layers)
        tree["dense_layers"] = [
            _init_block(dk, cfg, moe=False, d_ff=cfg.dense_d_ff or cfg.d_ff)
            for dk in dense_keys]

    nu = n_units(cfg)
    unit_keys = jax.random.split(keys[3], nu)
    stacked = jax.vmap(functools.partial(_init_unit, cfg=cfg))(unit_keys)
    # prepend the stacked "layers" axis to every leaf's logical axes
    is_leaf = lambda x: isinstance(x, ParamLeaf)
    stacked = jax.tree.map(
        lambda l: ParamLeaf(l.array, ("layers",) + tuple(l.axes)),
        stacked, is_leaf=is_leaf)
    tree["layers"] = stacked

    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        tree["shared_attn"] = _init_shared_attn(keys[4], cfg)

    tree["final_norm"] = ParamLeaf(_norm_init(cfg), (None,))
    return split_leaves(tree)


# ==========================================================================
# sublayer application
# ==========================================================================
def _norm(x, scale, cfg: ModelConfig):
    return common.rmsnorm(x, scale, cfg.norm_eps, gemma_style=cfg.post_norms)


def _ffn(blk, h, cfg: ModelConfig, ctx: RunCtx):
    """The block's feed-forward: (out, MoE aux loss, rows the held experts
    computed); a dense MLP gives (out, 0.0, None)."""
    if "router" in blk["ffn"]:
        return moe_forward(blk["ffn"], h, cfg, ctx.parallel,
                           constrain=ctx.constrain)
    return mlp_forward(blk["ffn"], h, cfg, constrain=ctx.constrain), 0.0, None


def _add_rows(total, rows):
    """Sum of expert-row counts where either may be None (no experts)."""
    if rows is None:
        return total
    return rows if total is None else total + rows


def _no_rows(cfg: ModelConfig):
    """The zero an expert-row count starts from; None without experts, so
    a dense model computes nothing for it."""
    return jnp.zeros((), jnp.int32) if cfg.uses_moe else None


def _apply_block(blk, x, positions, cfg: ModelConfig, ctx: RunCtx, *,
                 window: int, aux: jax.Array):
    h = _norm(x, blk["norm1"], cfg)
    if cfg.use_mla:
        a = attn.mla_forward(blk["attn"], h, positions, cfg,
                             policy=ctx.kernel_policy, constrain=ctx.constrain)
    else:
        a = attn.gqa_forward(blk["attn"], h, positions, cfg, window=window,
                             policy=ctx.kernel_policy, constrain=ctx.constrain)
    if cfg.post_norms:
        a = _norm(a, blk["post_attn_norm"], cfg)
    x = x + a
    h = _norm(x, blk["norm2"], cfg)
    f, aux_l, _ = _ffn(blk, h, cfg, ctx)
    if cfg.post_norms:
        f = _norm(f, blk["post_ffn_norm"], cfg)
    return x + f, aux + aux_l


def _apply_shared_attn(shared, x, emb0, positions, cfg: ModelConfig,
                       ctx: RunCtx, aux):
    h = jnp.concatenate([x, emb0], axis=-1) @ shared["w_in"].astype(x.dtype)
    out, aux = _apply_block(shared["block"], h, positions, cfg, ctx,
                            window=0, aux=aux)
    return x + (out - h), aux    # residual delta of the shared block


def _apply_unit(unit, x, emb0, positions, cfg: ModelConfig, ctx: RunCtx,
                shared, aux):
    u = unit_size(cfg)
    for i in range(u):
        sub = unit[f"sub{i}"]
        if cfg.uses_ssm:
            h = common.rmsnorm(x, sub["pre_norm"], cfg.norm_eps)
            x = x + ssm.mamba_forward(sub, h, cfg, policy=ctx.kernel_policy,
                                      constrain=ctx.constrain)
        else:
            window = cfg.window_for_layer(i)
            x, aux = _apply_block(sub, x, positions, cfg, ctx,
                                  window=window, aux=aux)
    if shared is not None:
        x, aux = _apply_shared_attn(shared, x, emb0, positions, cfg, ctx, aux)
    return x, aux


# ==========================================================================
# embedding / head
# ==========================================================================
def embed_tokens(params, tokens, cfg: ModelConfig, ctx: RunCtx):
    adt = common.dt(cfg.dtype)
    if cfg.n_codebooks:
        # tokens: (B, S, n_cb) — sum of per-codebook embeddings
        embs = params["embed"].astype(adt)          # (n_cb, Vp, d)
        x = jnp.zeros(tokens.shape[:2] + (cfg.d_model,), adt)
        for c in range(cfg.n_codebooks):
            x = x + embs[c][tokens[..., c]]
    else:
        x = params["embed"].astype(adt)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, adt)
    if ctx.constrain is not None:
        x = ctx.constrain(x, ("batch", None, "embed_act"))
    return x


def lm_logits(params, x, cfg: ModelConfig, ctx: RunCtx):
    adt = x.dtype
    Vp = padded_vocab(cfg)
    if cfg.n_codebooks:
        logits = jnp.einsum("bsd,cdv->bscv", x, params["lm_head"].astype(adt))
    elif cfg.tie_embeddings:
        logits = x @ params["embed"].astype(adt).T
    else:
        logits = x @ params["lm_head"].astype(adt)
    if cfg.final_logit_softcap > 0.0:
        logits = common.softcap(logits, cfg.final_logit_softcap)
    # mask the padded vocab tail
    if Vp != cfg.vocab_size:
        mask = jnp.arange(Vp) < cfg.vocab_size
        logits = jnp.where(mask, logits, -1e30)
    if ctx.constrain is not None:
        spec = ("batch", None, None, "vocab") if cfg.n_codebooks \
            else ("batch", None, "vocab")
        logits = ctx.constrain(logits, spec)
    return logits


# ==========================================================================
# full forward (training)
# ==========================================================================
def forward(params, tokens, cfg: ModelConfig, ctx: RunCtx = RunCtx(), *,
            extra_embeds: jax.Array | None = None):
    """Token ids -> logits.  ``extra_embeds`` (B, n_img, d) is the LLaVA
    vision prefix (precomputed patch embeddings; frontend is a stub)."""
    x = embed_tokens(params, tokens, cfg, ctx)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    B, S = x.shape[:2]
    positions = jnp.arange(S)[None, :]
    emb0 = x
    aux0 = jnp.zeros((), jnp.float32)

    for dense_blk in params.get("dense_layers", []):
        x, aux0 = _apply_block(dense_blk, x, positions, cfg, ctx,
                               window=cfg.sliding_window, aux=aux0)

    shared = params.get("shared_attn")

    def body(carry, unit):
        x, aux = carry
        x, aux = _apply_unit(unit, x, emb0, positions, cfg, ctx, shared, aux)
        return (x, aux), None

    if ctx.remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    elif ctx.remat == "dots":
        body = jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    (x, aux), _ = jax.lax.scan(body, (x, aux0), params["layers"])
    x = _norm(x, params["final_norm"], cfg)
    logits = lm_logits(params, x, cfg, ctx)
    return logits, aux


def lm_loss(params, tokens, cfg: ModelConfig, ctx: RunCtx = RunCtx(), *,
            extra_embeds: jax.Array | None = None):
    """Next-token CE (+ MoE aux).  For multi-codebook audio, the loss is the
    mean CE over codebooks; for VLM, image-prefix positions carry no loss."""
    if cfg.n_codebooks:
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        logits, aux = forward(params, inputs, cfg, ctx)
        losses = [common.cross_entropy(logits[:, :, c], labels[..., c])
                  for c in range(cfg.n_codebooks)]
        return sum(losses) / cfg.n_codebooks + aux
    inputs, labels, mask = common.shift_labels(tokens)
    logits, aux = forward(params, inputs, cfg, ctx, extra_embeds=extra_embeds)
    if extra_embeds is not None:
        logits = logits[:, extra_embeds.shape[1]:]
    return common.cross_entropy(logits, labels, mask) + aux


def lm_loss_pre_shifted(params, inputs, targets, cfg: ModelConfig,
                        ctx: RunCtx = RunCtx(), *,
                        extra_embeds: jax.Array | None = None):
    """CE with a pre-shifted (inputs, targets) pair — the production data
    pipeline emits these so the step sees clean power-of-two seq lengths."""
    logits, aux = forward(params, inputs, cfg, ctx, extra_embeds=extra_embeds)
    if extra_embeds is not None:
        logits = logits[:, extra_embeds.shape[1]:]
    if cfg.n_codebooks:
        losses = [common.cross_entropy(logits[:, :, c], targets[..., c])
                  for c in range(cfg.n_codebooks)]
        return sum(losses) / cfg.n_codebooks + aux
    return common.cross_entropy(logits, targets) + aux


# ==========================================================================
# prefill / decode
# ==========================================================================
def _cache_len(cfg: ModelConfig, ctx: RunCtx, seq_len: int, window: int) -> int:
    cap = ctx.decode_cache_len or max(cfg.max_seq_len, seq_len)
    if window > 0:
        cap = min(cap, window)
    return cap


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype: str = "bfloat16"):
    """Zero-filled decode cache pytree (+ its logical sharding axes)."""
    adt = common.dt(dtype)
    hd = cfg.resolved_head_dim
    nu, u = n_units(cfg), unit_size(cfg)

    def attn_cache(cap):
        if cfg.use_mla:
            return {"lat": jnp.zeros(
                (nu, batch, cap, cfg.kv_lora_rank + cfg.rope_head_dim), adt)}
        hkv = cfg.padded_kv_heads
        return {"k": jnp.zeros((nu, batch, cap, hkv, hd), adt),
                "v": jnp.zeros((nu, batch, cap, hkv, hd), adt)}

    def mamba_cache():
        cd = ssm.conv_dim(cfg)
        H, P_, N = cfg.resolved_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        return {"conv": jnp.zeros((nu, batch, cfg.conv_width - 1, cd), adt),
                "ssm": jnp.zeros((nu, batch, H, P_, N), jnp.float32)}

    cache: dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    units: dict[str, Any] = {}
    for i in range(u):
        if cfg.uses_ssm:
            units[f"sub{i}"] = mamba_cache()
        else:
            w = cfg.window_for_layer(i)
            cap = min(max_len, w) if w > 0 else max_len
            # MLA caches have no per-head dim; GQA caches are per-kv-head
            c = attn_cache(cap)
            units[f"sub{i}"] = c
    cache["units"] = units
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        cap = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
        cache["shared"] = {
            "k": jnp.zeros((nu, batch, cap, cfg.padded_kv_heads, hd), adt),
            "v": jnp.zeros((nu, batch, cap, cfg.padded_kv_heads, hd), adt)}
    if cfg.first_dense_layers:
        cap = max_len
        dc = []
        for _ in range(cfg.first_dense_layers):
            if cfg.use_mla:
                dc.append({"lat": jnp.zeros(
                    (batch, cap, cfg.kv_lora_rank + cfg.rope_head_dim), adt)})
            else:
                hkv = cfg.padded_kv_heads
                dc.append({"k": jnp.zeros((batch, cap, hkv, hd), adt),
                           "v": jnp.zeros((batch, cap, hkv, hd), adt)})
        cache["dense"] = dc
    return cache


def paged_cache_blockers(cfg: ModelConfig) -> tuple[str, ...]:
    """Named config features that keep a model family OFF the paged engine.

    Empty for every family in the zoo: dense/MoE/codebook GQA ride the
    shared page pools; MLA layers pool ONE compressed latent row per token;
    sliding-window layers hold O(window) private ring pages behind a static
    identity table; SSM layers park O(1) recurrent state in per-slot state
    slots of the same cache pytree; deepseek's first dense layers get their
    own stacked pool on the same page-id space.  The tuple form is the
    contract: capability gates report the SPECIFIC blocking feature by
    name, never a blanket boolean — an empty tuple means "serve it"."""
    del cfg
    return ()


def supports_paged_cache(cfg: ModelConfig) -> bool:
    return not paged_cache_blockers(cfg)


def int8_paged_blockers(cfg: ModelConfig) -> tuple[str, ...]:
    """Features blocking the int8 paged storage mode: the per-row scale
    leaves pair with full-length k/v page pools, which SSM state slots,
    latent (MLA) pools, private windowed rings, and the hybrid shared
    buffer do not carry."""
    checks = (("uses_ssm", cfg.uses_ssm), ("use_mla", cfg.use_mla),
              ("sliding_window", bool(cfg.sliding_window)),
              ("local_global", cfg.local_global),
              ("first_dense_layers", bool(cfg.first_dense_layers)),
              ("hybrid_attn_every",
               cfg.family == "hybrid" and bool(cfg.hybrid_attn_every)))
    return tuple(name for name, on in checks if on)


def init_paged_cache(cfg: ModelConfig, n_slots: int, n_pages: int,
                     page_size: int, max_blocks: int,
                     dtype: str = "bfloat16"):
    """Zero-filled paged decode cache: per-unit page *pools* shared by every
    slot, one block table and one position counter per slot.

    Per-family layout — every group keeps page axis 1 so the engine's page
    accounting / snapshot / host-tier seams iterate them uniformly:

      * full-attention GQA unit: k/v pools (n_units, n_pages, page_size,
        Hkv, hd) addressed through ``block_tables`` (positions
        [j*ps, (j+1)*ps) live on physical page ``block_tables[s, j]``; the
        engine parks free slots on a reserved per-slot scratch page so
        decode needs no validity branch).
      * MLA unit: ONE latent pool (n_units, n_pages, page_size, R) with
        R = kv_lora_rank + rope_head_dim — a single row per token shared
        by every head (~5x fewer KV bytes than per-head k/v), on the same
        block tables.
      * sliding-window unit: a PRIVATE ring of ``nbw = ceil(min(max_len,
        window)/ps)`` pages per slot, pool (n_units, n_slots*nbw, ps, Hkv,
        hd).  The "page table" is the static identity ``slot*nbw + j`` and
        logical blocks wrap at ``window/page_size`` — O(window) bytes per
        slot no matter how deep the stream runs, no host page management.
      * SSM unit: per-slot O(1) state slots {"conv": (n_units, n_slots,
        conv_width-1, cd), "ssm": (n_units, n_slots, H, P, N) fp32} — state
        rides the cache pytree, so snapshot/restore, preemption-fold and
        chaos drills cover recurrent layers unchanged.
      * hybrid shared block: per-slot linear buffer ``cache["shared"]``
        (n_units, n_slots, max_len, Hkv, hd), decoded through the paged
        sweep behind a static identity table.
      * first dense layers: ``cache["dense"]`` — a stacked group
        (n_dense, n_pages, page_size, ...) sharing the main page-id space.

    ``pos`` is per-slot — the batch is ragged by construction.

    ``dtype="int8"`` selects the quantized storage mode: int8 pools plus
    per-ROW-per-kv-head fp32 scale leaves ``k_scale``/``v_scale`` of shape
    (n_units, n_pages, page_size, Hkv, 1).  Rows are quantized at write
    time (decode scatter / speculative commit) and dequantized inside the
    attention sweep; a row, once written, never rescales, so page-level
    sharing and snapshots stay bit-stable.  The cache *structure* carries
    the mode — downstream seams discriminate on ``"k_scale" in unit``,
    which is static under jit."""
    blockers = paged_cache_blockers(cfg)
    if blockers:
        raise ValueError(f"{cfg.name}: paged KV cache blocked by "
                         f"{blockers[0]}")
    quantized = dtype == "int8"
    if quantized:
        i8_block = int8_paged_blockers(cfg)
        if i8_block:
            raise ValueError(f"{cfg.name}: int8 paged cache blocked by "
                             f"{i8_block[0]}")
    adt = jnp.int8 if quantized else common.dt(dtype)
    hd = cfg.resolved_head_dim
    nu, u = n_units(cfg), unit_size(cfg)
    hkv = cfg.padded_kv_heads
    R = cfg.kv_lora_rank + cfg.rope_head_dim
    max_len = max_blocks * page_size

    units: dict[str, Any] = {}
    for i in range(u):
        if cfg.uses_ssm:
            cd = ssm.conv_dim(cfg)
            H, P_, N = cfg.resolved_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
            units[f"sub{i}"] = {
                "conv": jnp.zeros((nu, n_slots, cfg.conv_width - 1, cd), adt),
                "ssm": jnp.zeros((nu, n_slots, H, P_, N), jnp.float32)}
        elif cfg.use_mla:
            units[f"sub{i}"] = {
                "lat": jnp.zeros((nu, n_pages, page_size, R), adt)}
        else:
            w = cfg.window_for_layer(i)
            if w > 0:
                nbw = -(-min(max_len, w) // page_size)
                units[f"sub{i}"] = {
                    "k": jnp.zeros((nu, n_slots * nbw, page_size, hkv, hd),
                                   adt),
                    "v": jnp.zeros((nu, n_slots * nbw, page_size, hkv, hd),
                                   adt)}
            else:
                sub = {"k": jnp.zeros((nu, n_pages, page_size, hkv, hd), adt),
                       "v": jnp.zeros((nu, n_pages, page_size, hkv, hd), adt)}
                if quantized:
                    sub["k_scale"] = jnp.zeros(
                        (nu, n_pages, page_size, hkv, 1), jnp.float32)
                    sub["v_scale"] = jnp.zeros(
                        (nu, n_pages, page_size, hkv, 1), jnp.float32)
                units[f"sub{i}"] = sub
    cache = {"pos": jnp.zeros((n_slots,), jnp.int32),
             "block_tables": jnp.zeros((n_slots, max_blocks), jnp.int32),
             "units": units}
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        cache["shared"] = {
            "k": jnp.zeros((nu, n_slots, max_len, hkv, hd), adt),
            "v": jnp.zeros((nu, n_slots, max_len, hkv, hd), adt)}
    if cfg.first_dense_layers:
        nd = cfg.first_dense_layers
        if cfg.use_mla:
            cache["dense"] = {
                "lat": jnp.zeros((nd, n_pages, page_size, R), adt)}
        else:
            cache["dense"] = {
                "k": jnp.zeros((nd, n_pages, page_size, hkv, hd), adt),
                "v": jnp.zeros((nd, n_pages, page_size, hkv, hd), adt)}
    return cache


def _block_prefill(blk, x, positions, cfg: ModelConfig, ctx: RunCtx, *,
                   window: int, cache_len: int):
    """_apply_block that also emits this layer's decode cache: returns
    (x, cache, expert rows | None)."""
    h = _norm(x, blk["norm1"], cfg)
    if cfg.use_mla:
        a, lat = attn.mla_prefill(blk["attn"], h, positions, cfg,
                                  cache_len=cache_len,
                                  policy=ctx.kernel_policy,
                                  constrain=ctx.constrain)
        c = {"lat": lat}
    else:
        a, (k, v) = attn.gqa_prefill(blk["attn"], h, positions, cfg,
                                     window=window, cache_len=cache_len,
                                     policy=ctx.kernel_policy,
                                     constrain=ctx.constrain)
        c = {"k": k, "v": v}
    if cfg.post_norms:
        a = _norm(a, blk["post_attn_norm"], cfg)
    x = x + a
    h = _norm(x, blk["norm2"], cfg)
    f, _, rows = _ffn(blk, h, cfg, ctx)
    if cfg.post_norms:
        f = _norm(f, blk["post_ffn_norm"], cfg)
    return x + f, c, rows


def prefill(params, tokens, cfg: ModelConfig, ctx: RunCtx = RunCtx(), *,
            max_len: int = 0, extra_embeds: jax.Array | None = None,
            full_cache: bool = False):
    """Process the full prompt and build the decode cache.

    Returns (logits, cache) — logits for every prompt position (the serving
    layer samples from the last one); cache['pos'] = prompt length.
    ``prefill_counted`` also returns the expert rows it computed.

    ``full_cache=True`` keeps sliding-window layers' caches LINEAR at
    capacity ``max_len`` instead of wrapping them into an O(window) ring:
    position ``p``'s row sits at index ``p``.  The serving engine needs
    this for page inject — prompts pad up to a power-of-2 bucket, and in
    the ring layout the pad rows written past the prompt would overwrite
    the real window tail before the engine can scatter it into the slot's
    private ring pages.  Attention masking is unchanged (the window still
    clips scores); only the emitted cache layout differs.
    """
    return prefill_counted(params, tokens, cfg, ctx, max_len=max_len,
                           extra_embeds=extra_embeds,
                           full_cache=full_cache)[:2]


def prefill_counted(params, tokens, cfg: ModelConfig, ctx: RunCtx = RunCtx(),
                    *, max_len: int = 0,
                    extra_embeds: jax.Array | None = None,
                    full_cache: bool = False):
    """``prefill`` that also returns the (token, expert) rows its held
    experts computed, pad positions included: (logits, cache, rows), rows
    None for a model without experts."""
    x = embed_tokens(params, tokens, cfg, ctx)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    B, S = x.shape[:2]
    max_len = max_len or max(cfg.max_seq_len, S)
    positions = jnp.arange(S)[None, :]
    emb0 = x
    rows = _no_rows(cfg)
    shared = params.get("shared_attn")

    dense_cache = []
    for blk in params.get("dense_layers", []):
        cap = min(max_len, cfg.sliding_window) \
            if (cfg.sliding_window and not full_cache) else max_len
        x, c, r = _block_prefill(blk, x, positions, cfg, ctx,
                                 window=cfg.sliding_window, cache_len=cap)
        rows = _add_rows(rows, r)
        dense_cache.append(c)

    def body(carry, unit):
        x, rows = carry
        u = unit_size(cfg)
        unit_cache = {}
        for i in range(u):
            sub = unit[f"sub{i}"]
            if cfg.uses_ssm:
                h = common.rmsnorm(x, sub["pre_norm"], cfg.norm_eps)
                out, (conv, ssm_state) = ssm.mamba_forward(
                    sub, h, cfg, policy=ctx.kernel_policy,
                    constrain=ctx.constrain, return_state=True)
                x = x + out
                unit_cache[f"sub{i}"] = {"conv": conv, "ssm": ssm_state}
            else:
                w = cfg.window_for_layer(i)
                cap = min(max_len, w) if (w > 0 and not full_cache) \
                    else max_len
                x, c, r = _block_prefill(sub, x, positions, cfg, ctx,
                                         window=w, cache_len=cap)
                rows = _add_rows(rows, r)
                unit_cache[f"sub{i}"] = c
        if shared is not None:
            h = jnp.concatenate([x, emb0], axis=-1) @ shared["w_in"].astype(x.dtype)
            out, c, r = _block_prefill(shared["block"], h, positions, cfg,
                                       ctx, window=0, cache_len=max_len)
            rows = _add_rows(rows, r)
            x = x + (out - h)
            unit_cache["__shared__"] = c
        return (x, rows), unit_cache

    (x, rows), unit_caches = jax.lax.scan(body, (x, rows), params["layers"])
    x = _norm(x, params["final_norm"], cfg)
    logits = lm_logits(params, x, cfg, ctx)

    cache = {"pos": jnp.asarray(S, jnp.int32),
             "units": {k: v for k, v in unit_caches.items()
                       if k != "__shared__"}}
    if shared is not None:
        cache["shared"] = unit_caches["__shared__"]
    if dense_cache:
        cache["dense"] = dense_cache
    return logits, cache, rows


def _block_decode(blk, x, pos, c, cfg: ModelConfig, ctx: RunCtx, *,
                  window: int, block_tables: jax.Array | None = None):
    h = _norm(x, blk["norm1"], cfg)
    if cfg.use_mla and block_tables is not None:
        a, lat = attn.mla_decode_paged(blk["attn"], h, pos, c["lat"],
                                       block_tables, cfg,
                                       policy=ctx.kernel_policy,
                                       constrain=ctx.constrain)
        c = {"lat": lat}
    elif cfg.use_mla:
        a, lat = attn.mla_decode(blk["attn"], h, pos, c["lat"], cfg,
                                 policy=ctx.kernel_policy,
                                 constrain=ctx.constrain)
        c = {"lat": lat}
    elif block_tables is not None and window > 0:
        # sliding-window layer on the paged engine: the pool is a batch of
        # PRIVATE per-slot rings ((n_slots*nbw, ps, Hkv, *) -> (B, Cw, ...))
        # behind a static identity table — ragged pos masks per row
        B = pos.shape[0]
        kp, vp = c["k"], c["v"]
        nbw, ps = kp.shape[0] // B, kp.shape[1]
        ring = lambda p: p.reshape(B, nbw * ps, *p.shape[2:])
        a, (k, v) = attn.gqa_decode_ragged(blk["attn"], h, pos,
                                           (ring(kp), ring(vp)), cfg,
                                           window=window,
                                           policy=ctx.kernel_policy,
                                           constrain=ctx.constrain)
        c = {"k": k.reshape(kp.shape), "v": v.reshape(vp.shape)}
    elif block_tables is not None:
        if "k_scale" in c:       # int8 pools: thread the scale leaves
            kv_in = (c["k"], c["v"], c["k_scale"], c["v_scale"])
            a, kv_out = attn.gqa_decode_paged(blk["attn"], h, pos, kv_in,
                                              block_tables, cfg,
                                              window=window,
                                              policy=ctx.kernel_policy,
                                              constrain=ctx.constrain)
            c = dict(zip(("k", "v", "k_scale", "v_scale"), kv_out))
        else:
            a, (k, v) = attn.gqa_decode_paged(blk["attn"], h, pos,
                                              (c["k"], c["v"]), block_tables,
                                              cfg, window=window,
                                              policy=ctx.kernel_policy,
                                              constrain=ctx.constrain)
            c = {"k": k, "v": v}
    else:
        a, (k, v) = attn.gqa_decode(blk["attn"], h, pos, (c["k"], c["v"]),
                                    cfg, window=window,
                                    policy=ctx.kernel_policy,
                                    constrain=ctx.constrain)
        c = {"k": k, "v": v}
    if cfg.post_norms:
        a = _norm(a, blk["post_attn_norm"], cfg)
    x = x + a
    h = _norm(x, blk["norm2"], cfg)
    f, _, rows = _ffn(blk, h, cfg, ctx)
    if cfg.post_norms:
        f = _norm(f, blk["post_ffn_norm"], cfg)
    return x + f, c, rows


def paged_decode_step(params, cache, tokens, cfg: ModelConfig, ctx: RunCtx,
                      active: jax.Array | None):
    """decode_step over the paged cache layout, returning (logits, cache,
    rows): ``rows`` counts the (token, expert) rows the held experts
    computed, every slot included (None without experts).

    Per-slot positions, block
    tables, shared page pools.  ``active`` (B,) gates the position advance —
    parked slots keep rewriting row ``pos[b]`` of their scratch page (or
    their private ring / state slot) and their sampled tokens are discarded
    by the engine, so one executable serves every occupancy pattern.

    Routing mirrors the ring ``decode_step`` sub for sub: MLA units sweep
    the latent pool, sliding-window units their private rings, SSM units
    advance per-slot recurrent state, first dense layers and the hybrid
    shared block run before/inside the scan — the full model zoo behind
    ONE seam."""
    pos = cache["pos"]                                     # (B,)
    bt = cache["block_tables"]
    x = embed_tokens(params, tokens, cfg, ctx)
    emb0 = x
    shared = params.get("shared_attn")

    rows = _no_rows(cfg)
    new_dense = None
    if cfg.first_dense_layers:
        new_layers = []
        for j, blk in enumerate(params["dense_layers"]):
            c = jax.tree.map(lambda p: p[j], cache["dense"])
            x, c2, r = _block_decode(blk, x, pos, c, cfg, ctx,
                                     window=cfg.sliding_window,
                                     block_tables=bt)
            rows = _add_rows(rows, r)
            new_layers.append(c2)
        new_dense = jax.tree.map(lambda *xs: jnp.stack(xs), *new_layers)

    def body(carry, xs):
        x, rows = carry
        unit, c_unit = xs
        new_c = {}
        for i in range(unit_size(cfg)):
            sub, c = unit[f"sub{i}"], c_unit[f"sub{i}"]
            if cfg.uses_ssm:
                h = common.rmsnorm(x, sub["pre_norm"], cfg.norm_eps)
                out, (conv, ssm_state) = ssm.mamba_decode(
                    sub, h, (c["conv"], c["ssm"]), cfg,
                    constrain=ctx.constrain)
                x = x + out
                # state pools keep their storage dtype (mamba_decode
                # computes the conv tail in activation dtype): the fused
                # serving loop carries the cache through lax.scan, which
                # needs a dtype-stable carry
                new_c[f"sub{i}"] = {"conv": conv.astype(c["conv"].dtype),
                                    "ssm": ssm_state.astype(c["ssm"].dtype)}
            else:
                w = cfg.window_for_layer(i)
                x, c2, r = _block_decode(sub, x, pos, c, cfg, ctx, window=w,
                                         block_tables=bt)
                rows = _add_rows(rows, r)
                new_c[f"sub{i}"] = c2
        if shared is not None:
            # per-slot linear buffer behind a static identity table: slot
            # b's block j IS physical page b*nbs + j of the reshaped pool
            h = jnp.concatenate([x, emb0], axis=-1) \
                @ shared["w_in"].astype(x.dtype)
            sc = c_unit["__shared__"]
            B, Cs = sc["k"].shape[0], sc["k"].shape[1]
            nbs = bt.shape[1]
            ps = Cs // nbs
            bt_id = jnp.arange(B * nbs, dtype=jnp.int32).reshape(B, nbs)
            pool = lambda p: p.reshape(B * nbs, ps, *p.shape[2:])
            out, sc2, _ = _block_decode(
                shared["block"], h, pos,
                {"k": pool(sc["k"]), "v": pool(sc["v"])},
                cfg, ctx, window=0, block_tables=bt_id)
            x = x + (out - h)
            new_c["__shared__"] = {"k": sc2["k"].reshape(sc["k"].shape),
                                   "v": sc2["v"].reshape(sc["v"].shape)}
        return (x, rows), new_c

    units_cache = cache["units"]
    if shared is not None:
        units_cache = dict(units_cache)
        units_cache["__shared__"] = cache["shared"]
    (x, rows), new_units = jax.lax.scan(body, (x, rows),
                                        (params["layers"], units_cache))
    x = _norm(x, params["final_norm"], cfg)
    logits = lm_logits(params, x, cfg, ctx)
    adv = jnp.ones_like(pos) if active is None \
        else jnp.asarray(active, jnp.int32)
    new_cache = {"pos": pos + adv, "block_tables": bt,
                 "units": {k: v for k, v in new_units.items()
                           if k != "__shared__"}}
    if shared is not None:
        new_cache["shared"] = new_units["__shared__"]
    if new_dense is not None:
        new_cache["dense"] = new_dense
    return logits, new_cache, rows


def speculative_blockers(cfg: ModelConfig) -> tuple[str, ...]:
    """Named features blocking speculative verify/commit.  SSM recurrence
    would need per-step state snapshots to roll back, MLA decode runs the
    absorbed custom path (drafting against it is a follow-on), multi-
    codebook drafts would have to match on every codebook, and the hybrid
    shared block carries its own cache."""
    checks = (("uses_ssm", cfg.uses_ssm), ("use_mla", cfg.use_mla),
              ("n_codebooks", bool(cfg.n_codebooks)),
              ("first_dense_layers", bool(cfg.first_dense_layers)),
              ("hybrid_attn_every",
               cfg.family == "hybrid" and bool(cfg.hybrid_attn_every)))
    return tuple(name for name, on in checks if on)


def supports_speculative(cfg: ModelConfig) -> bool:
    return not speculative_blockers(cfg)


def chunked_prefill_blockers(cfg: ModelConfig) -> tuple[str, ...]:
    """Named features blocking the paged multi-query sweep behind chunked
    prefill / prefix-cache joins (``prefill_suffix``): SSM and the hybrid
    shared block are recurrent or privately cached (no shared full-length
    pool to sweep a suffix chunk against), windowed layers keep O(window)
    ring pages, codebook models feed (B, Q, n_cb) tokens.  MLA and first
    dense layers ARE covered — the latent pool in absorbed form is a
    single-kv-head GQA pool, which is what lets deepseek ride the prefix
    cache."""
    checks = (("uses_ssm", cfg.uses_ssm),
              ("n_codebooks", bool(cfg.n_codebooks)),
              ("hybrid_attn_every",
               cfg.family == "hybrid" and bool(cfg.hybrid_attn_every)),
              ("sliding_window", bool(cfg.sliding_window)),
              ("local_global", cfg.local_global))
    return tuple(name for name, on in checks if on)


def _block_verify(blk, x, pos, c, cfg: ModelConfig, ctx: RunCtx, *,
                  window: int, block_tables: jax.Array | None = None):
    """_block_decode's speculative sibling: scores the whole fed block in
    one cache sweep and returns this layer's *pending* k/v rows instead of
    writing the cache."""
    h = _norm(x, blk["norm1"], cfg)
    if cfg.use_mla:
        # paged-only (the ring gate names use_mla): the latent pool in
        # absorbed form is a single-kv-head GQA pool — generic sweep
        a, lat_new = attn.mla_verify_paged(blk["attn"], h, pos, c["lat"],
                                           block_tables, cfg,
                                           policy=ctx.kernel_policy,
                                           constrain=ctx.constrain)
        kv_new = None
    elif block_tables is not None:
        kv_in = ((c["k"], c["v"], c["k_scale"], c["v_scale"])
                 if "k_scale" in c else (c["k"], c["v"]))
        a, kv_new = attn.gqa_verify_paged(blk["attn"], h, pos, kv_in,
                                          block_tables,
                                          cfg, window=window,
                                          policy=ctx.kernel_policy,
                                          constrain=ctx.constrain)
    else:
        a, kv_new = attn.gqa_verify(blk["attn"], h, pos, (c["k"], c["v"]),
                                    cfg, window=window,
                                    policy=ctx.kernel_policy,
                                    constrain=ctx.constrain)
    if cfg.post_norms:
        a = _norm(a, blk["post_attn_norm"], cfg)
    x = x + a
    h = _norm(x, blk["norm2"], cfg)
    f, _, rows = _ffn(blk, h, cfg, ctx)
    if cfg.post_norms:
        f = _norm(f, blk["post_ffn_norm"], cfg)
    pend = {"lat": lat_new} if kv_new is None \
        else {"k": kv_new[0], "v": kv_new[1]}
    return x + f, pend, rows


def verify_step(params, cache, tokens, cfg: ModelConfig,
                ctx: RunCtx = RunCtx()):
    """(logits, pending) of ``verify_counted``, which see."""
    return verify_counted(params, cache, tokens, cfg, ctx)[:2]


def verify_counted(params, cache, tokens, cfg: ModelConfig,
                   ctx: RunCtx = RunCtx()):
    """Score ``Q = K+1`` speculative tokens in ONE cache sweep; returns
    (logits, pending, rows the held experts computed | None).

    tokens: (B, Q) — the fed block [t_last, d_1..d_K] at positions
    ``pos .. pos+Q-1``.  Returns (logits (B, Q, V), pending) where
    ``pending`` mirrors ``cache['units']`` with per-layer candidate k/v
    rows of shape (n_units, B, Q, Hkv, hd) — NOTHING is committed past the
    accepted prefix until :func:`commit_spec` / :func:`commit_spec_paged`
    scatters rows ``0..n_accept`` and advances ``pos``.  Both cache
    layouts share this seam, discriminated by pytree structure exactly
    like ``decode_step``.

    MLA units pend one latent row per token ({"lat": (n_units, B, Q, R)},
    paged only); first dense layers pend under ``pending["__dense__"]``
    (stacked over layers) — absent for configs without them, so the
    established pending pytree is unchanged for the GQA families.  The
    gate is per-feature: ring sweeps require ``speculative_blockers``
    empty, paged sweeps ``chunked_prefill_blockers`` empty (the looser
    contract both the spec engine and prefix-cache joins build on)."""
    paged = "block_tables" in cache
    blockers = chunked_prefill_blockers(cfg) if paged \
        else speculative_blockers(cfg)
    if blockers:
        kind = "paged verify sweep" if paged else "speculative decode"
        raise ValueError(f"{cfg.name}: {kind} blocked by {blockers[0]}")
    pos = cache["pos"]                  # () ring | (B,) paged
    bt = cache.get("block_tables")
    x = embed_tokens(params, tokens, cfg, ctx)

    rows = _no_rows(cfg)
    pend_dense = None
    if cfg.first_dense_layers:          # paged-only: the ring gate names it
        layer_pend = []
        for j, blk in enumerate(params["dense_layers"]):
            c = jax.tree.map(lambda p: p[j], cache["dense"])
            x, p, r = _block_verify(blk, x, pos, c, cfg, ctx,
                                    window=cfg.sliding_window,
                                    block_tables=bt)
            rows = _add_rows(rows, r)
            layer_pend.append(p)
        pend_dense = jax.tree.map(lambda *xs: jnp.stack(xs), *layer_pend)

    def body(carry, xs):
        x, rows = carry
        unit, c_unit = xs
        pend = {}
        for i in range(unit_size(cfg)):
            sub, c = unit[f"sub{i}"], c_unit[f"sub{i}"]
            window = 0 if paged else cfg.window_for_layer(i)
            x, p, r = _block_verify(sub, x, pos, c, cfg, ctx, window=window,
                                    block_tables=bt)
            rows = _add_rows(rows, r)
            pend[f"sub{i}"] = p
        return (x, rows), pend

    (x, rows), pending = jax.lax.scan(body, (x, rows),
                                      (params["layers"], cache["units"]))
    x = _norm(x, params["final_norm"], cfg)
    logits = lm_logits(params, x, cfg, ctx)
    if pend_dense is not None:
        pending = dict(pending)
        pending["__dense__"] = pend_dense
    return logits, pending, rows


def commit_spec(cache, pending, n_accept, cfg: ModelConfig):
    """Commit the accepted prefix of a verify step into the ring cache.

    ``pending`` holds rows for the fed block [t_last, d_1..d_K]; rows
    ``0..n_accept`` (t_last plus the accepted drafts) scatter into slots
    ``(pos + i) % C`` and ``pos`` advances by ``n_accept + 1``.  Rejected
    rows route to an out-of-bounds slot and are dropped — the ring's
    history is never touched past the accepted prefix, so there is nothing
    to roll back.  ``n_accept`` is a traced scalar: ONE executable serves
    every acceptance pattern inside the fused scan."""
    pos = cache["pos"]
    new_units = {}
    for name, c in cache["units"].items():
        pend = pending[name]
        Q = pend["k"].shape[2]
        C = c["k"].shape[2]
        i = jnp.arange(Q)
        slots = jnp.where(i <= n_accept, (pos + i) % C, C)   # C is OOB
        new_units[name] = {
            key: c[key].at[:, :, slots].set(
                pend[key].astype(c[key].dtype), mode="drop")
            for key in ("k", "v")}
    return {"pos": pos + n_accept + 1, "units": new_units}


def prefill_suffix(params, cache, tokens, n_commit, cfg: ModelConfig,
                   ctx: RunCtx = RunCtx()):
    """Chunked paged prefill: score a block of *prompt suffix* tokens
    against a slot's already-cached prefix and commit their k/v.

    This is the prefix-sharing engine's join path: when
    ``PagedKVCache.admit_with_prefix`` maps a cached prefix of length
    ``m``, only ``tokens[m:]`` need compute — and scoring a suffix chunk
    at positions ``pos .. pos+Q-1`` against pages committed through
    ``pos-1`` is *exactly* the speculative verify sweep with
    ``q_len = chunk`` (``ops.paged_verify_attention`` — no new kernel).
    The commit is the speculative commit with every real row accepted:
    ``n_commit`` (B,) counts each slot's real (non-pad) rows this chunk;
    rows ``0..n_commit-1`` scatter through the block table, ``pos``
    advances by ``n_commit``, and slots with ``n_commit == 0`` neither
    write nor advance — so one fixed-shape executable serves every join
    against the live engine cache without touching the other slots.

    Returns ``(logits, cache, rows)``: row ``n_commit[b] - 1`` of slot b's
    logits scores the token after its last real suffix token (the
    engine's first-token sample on a full-suffix join); ``rows`` counts
    the (token, expert) rows the held experts computed, pad rows and
    other slots' rows included (None without experts)."""
    logits, pending, rows = verify_counted(params, cache, tokens, cfg, ctx)
    active = (n_commit > 0).astype(jnp.int32)
    new_cache = commit_spec_paged(cache, pending, n_commit - 1, active, cfg)
    return logits, new_cache, rows


def commit_spec_paged(cache, pending, n_accept, active, cfg: ModelConfig):
    """Paged commit: per-slot accepted counts (B,) — every engine slot
    keeps its own prefix.  Accepted rows scatter through the block table
    into the shared pools; rejected or inactive rows route out of bounds
    and drop.  Parked slots neither write nor advance.

    Quantized caches (``"k_scale" in unit``) quantize the pending rows
    per-row at commit time and scatter the int8 rows plus their fp32
    scales through the same index — dropped rows drop both halves, so a
    row's (q, scale) pair is always written atomically.

    MLA units commit their single pending latent row per token through the
    identical scatter (key "lat", pool (n_units, P, ps, R)); a pending
    ``"__dense__"`` group commits into ``cache["dense"]`` the same way —
    dense layers share the main page-id space, so the SAME block-table
    rows address them."""
    pos = cache["pos"]                                       # (B,)
    bt = cache["block_tables"]

    def commit_group(c, pend):
        quantized = "k_scale" in c
        keys = [k for k in ("k", "v", "lat") if k in c]
        ng, P, ps = c[keys[0]].shape[0], c[keys[0]].shape[1], \
            c[keys[0]].shape[2]
        B, Q = pend[keys[0]].shape[1], pend[keys[0]].shape[2]
        i = jnp.arange(Q)[None, :]                           # (1, Q)
        posq = pos[:, None] + i                              # (B, Q)
        page = jnp.take_along_axis(bt, jnp.minimum(posq // ps,
                                                   bt.shape[1] - 1), axis=1)
        row = page * ps + posq % ps
        ok = (i <= n_accept[:, None]) & (active[:, None] > 0)
        rows = jnp.where(ok, row, P * ps).reshape(-1)        # OOB dropped

        def scatter(pool, vals):
            flat = pool.reshape(ng, P * ps, *pool.shape[3:])
            flat = flat.at[:, rows].set(
                vals.astype(flat.dtype).reshape(ng, B * Q, *vals.shape[3:]),
                mode="drop")
            return flat.reshape(pool.shape)

        new = {}
        for key in keys:
            if quantized and key in ("k", "v"):
                qrows, srows = quant.quantize_int8_rows(pend[key])
                new[key] = scatter(c[key], qrows)
                new[key + "_scale"] = scatter(c[key + "_scale"], srows)
            else:
                new[key] = scatter(c[key], pend[key])
        return new

    new_units = {name: commit_group(c, pending[name])
                 for name, c in cache["units"].items()}
    adv = jnp.where(active > 0, n_accept + 1, 0)
    out = {"pos": pos + adv, "block_tables": bt, "units": new_units}
    if "__dense__" in pending:
        out["dense"] = commit_group(cache["dense"], pending["__dense__"])
    return out


def decode_step(params, cache, tokens, cfg: ModelConfig, ctx: RunCtx = RunCtx(),
                *, active: jax.Array | None = None):
    """One decode step: tokens (B, 1) [or (B, 1, n_cb)] + cache -> logits,
    updated cache.

    Two cache layouts share this seam, discriminated by pytree structure
    (keys are static under jit): the classic ring buffer (scalar ``pos``,
    per-slot ring per layer) and the paged layout from ``init_paged_cache``
    (per-slot ``pos``/``block_tables``, shared page pools).  ``active``
    applies to the paged layout only: it gates which slots advance."""
    if "block_tables" in cache:
        return paged_decode_step(params, cache, tokens, cfg, ctx, active)[:2]
    pos = cache["pos"]
    x = embed_tokens(params, tokens, cfg, ctx)
    emb0 = x
    shared = params.get("shared_attn")

    new_dense = []
    for blk, c in zip(params.get("dense_layers", []), cache.get("dense", [])):
        x, c, _ = _block_decode(blk, x, pos, c, cfg, ctx,
                                window=cfg.sliding_window)
        new_dense.append(c)

    def body(x, xs):
        unit, c_unit = xs
        u = unit_size(cfg)
        new_c = {}
        for i in range(u):
            sub, c = unit[f"sub{i}"], c_unit[f"sub{i}"]
            if cfg.uses_ssm:
                h = common.rmsnorm(x, sub["pre_norm"], cfg.norm_eps)
                out, (conv, ssm_state) = ssm.mamba_decode(
                    sub, h, (c["conv"], c["ssm"]), cfg, constrain=ctx.constrain)
                x = x + out
                new_c[f"sub{i}"] = {"conv": conv.astype(c["conv"].dtype),
                                    "ssm": ssm_state.astype(c["ssm"].dtype)}
            else:
                window = cfg.window_for_layer(i)
                x, c2, _ = _block_decode(sub, x, pos, c, cfg, ctx,
                                         window=window)
                new_c[f"sub{i}"] = c2
        if shared is not None:
            h = jnp.concatenate([x, emb0], axis=-1) @ shared["w_in"].astype(x.dtype)
            sc = c_unit["__shared__"]
            out, sc2, _ = _block_decode(shared["block"], h, pos, sc, cfg, ctx,
                                     window=0)
            x = x + (out - h)
            new_c["__shared__"] = sc2
        return x, new_c

    units_cache = cache["units"]
    if shared is not None:
        units_cache = dict(units_cache)
        units_cache["__shared__"] = cache["shared"]
    x, new_units = jax.lax.scan(body, x, (params["layers"], units_cache))

    x = _norm(x, params["final_norm"], cfg)
    logits = lm_logits(params, x, cfg, ctx)

    new_cache = {"pos": pos + 1, "units": {k: v for k, v in new_units.items()
                                           if k != "__shared__"}}
    if shared is not None:
        new_cache["shared"] = new_units["__shared__"]
    if new_dense:
        new_cache["dense"] = new_dense
    return logits, new_cache
