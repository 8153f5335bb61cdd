"""Plain reference for DeepSeek-V2 decoders: latent attention (MLA) with
YaRN rope, and routed plus shared experts after leading dense layers.

Written from the published description (DeepSeek-V2, arXiv:2405.04434, and
the model's ``config.json``) in straightforward ``jax.numpy`` and float32,
with no kernel, cache or batching, and importing nothing of the program
under test.  It also owns the seeded weights: the benchmark makes them
here, hands the same tree to the program (whose parameter layout it
matches), and remakes them from the seed for the comparison.

Per layer (pre-norm residual):
    h = RMSNorm(x) ; x += MLA(h) ; h = RMSNorm(x) ; x += FFN(h)
MLA, in the published non-absorbed form, without query compression
(``q_lora_rank`` null):
    q = h W_q -> per head [q_nope (qk_nope_head_dim) | q_rope (qk_rope_head_dim)]
    [c | k_rope] = h W_dkv ; c = RMSNorm(c) (kv_lora_rank)
    k_nope = c W_uk, v = c W_uv, per head ; k_rope is one rope key every head shares
    scores = [q_nope | rope(q_rope)] . [k_nope | rope(k_rope)] * softmax scale, causal
Rope is the rotate-half form (first half against second half) with YaRN
frequencies: extrapolated dims keep theta^(-2i/d), interpolated ones divide
it by ``factor``, a linear ramp between the correction dims of
``beta_fast`` and ``beta_slow`` rotations over the original context;
cos and sin scale by mscale(factor, mscale) / mscale(factor,
mscale_all_dim), and the softmax scale is qk_head_dim^-0.5 times
mscale(factor, mscale_all_dim)^2, where mscale(s, m) = 0.1 m ln s + 1.
FFN: layers below ``first_k_dense_replace`` are a dense SwiGLU MLP of
``intermediate_size``.  The others route: probabilities = softmax(h W_gate)
over all ``published n_routed_experts``, the top ``num_experts_per_tok``
kept (greedy), renormalised only where ``norm_topk_prob``; the layer sums
gate * expert(h) over the experts held here (experts 0 .. held - 1, the
chip's share; what the others add is left out, as on the chip), then adds
the shared experts, one SwiGLU MLP of n_shared_experts *
moe_intermediate_size.  Logits: RMSNorm(x) @ head (untied).

``precision``: "highest" is float32 at highest matmul precision; "fp8"
rounds every matmul operand (weights and activations, scores and
probabilities) to float8_e4m3fn first: the control that a sound
comparison must reject.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def sizes(config: dict) -> dict:
    """The reference's sizes from a configuration file's published keys.
    The router spans the published expert count; ``n_routed_experts`` is
    how many of them are held here."""
    if config.get("q_lora_rank"):
        raise ValueError("this reference has no query compression "
                         "(q_lora_rank must be null)")
    fixed = {"scoring_func": "softmax", "topk_method": "greedy",
             "moe_layer_freq": 1, "routed_scaling_factor": 1}
    off = {k: config.get(k) for k, v in fixed.items() if config.get(k) != v}
    if off:
        raise ValueError(f"unsupported routing settings: {off}")
    y = config.get("rope_scaling") or {}
    if y and y.get("type") != "yarn":
        raise ValueError(f"unsupported rope_scaling {y!r}")
    published = config["bench"]["published"]
    return {"d": int(config["hidden_size"]),
            "hq": int(config["num_attention_heads"]),
            "r_kv": int(config["kv_lora_rank"]),
            "dn": int(config["qk_nope_head_dim"]),
            "dr": int(config["qk_rope_head_dim"]),
            "dv": int(config["v_head_dim"]),
            "ff": int(config["intermediate_size"]),
            "moe_ff": int(config["moe_intermediate_size"]),
            "experts": int(published.get("n_routed_experts",
                                         config["n_routed_experts"])),
            "held": int(config["n_routed_experts"]),
            "top_k": int(config["num_experts_per_tok"]),
            "norm_topk": bool(config["norm_topk_prob"]),
            "shared": int(config["n_shared_experts"]),
            "dense_layers": int(config["first_k_dense_replace"]),
            "layers": int(config["num_hidden_layers"]),
            "vocab": int(config["vocab_size"]),
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "tied": bool(config["tie_word_embeddings"]),
            "yarn_factor": float(y.get("factor", 0.0)),
            "yarn_original": int(y.get("original_max_position_embeddings",
                                       0)),
            "yarn_beta_fast": float(y.get("beta_fast", 32.0)),
            "yarn_beta_slow": float(y.get("beta_slow", 1.0)),
            "yarn_mscale": float(y.get("mscale", 1.0)),
            "yarn_mscale_all_dim": float(y.get("mscale_all_dim", 0.0))}


def check_program(s: dict, cfg) -> None:
    """Refuse to run a program configuration that differs from the file's:
    the harness would then compare two different models."""
    y = cfg.rope_scaling
    got = {"d": cfg.d_model, "hq": cfg.n_heads, "r_kv": cfg.kv_lora_rank,
           "dn": cfg.nope_head_dim, "dr": cfg.rope_head_dim,
           "dv": cfg.v_head_dim, "ff": cfg.dense_d_ff or cfg.d_ff,
           "moe_ff": cfg.resolved_moe_d_ff, "experts": cfg.n_experts,
           "held": cfg.resolved_held_experts,
           "top_k": cfg.experts_per_token,
           "norm_topk": bool(cfg.norm_topk_prob),
           "shared": cfg.n_shared_experts,
           "dense_layers": cfg.first_dense_layers, "layers": cfg.n_layers,
           "vocab": cfg.vocab_size, "eps": float(cfg.norm_eps),
           "theta": float(cfg.rope_theta), "tied": bool(cfg.tie_embeddings),
           "yarn_factor": float(y.factor) if y else 0.0,
           "yarn_original": int(y.original_max_position) if y else 0,
           "yarn_beta_fast": float(y.beta_fast) if y else 32.0,
           "yarn_beta_slow": float(y.beta_slow) if y else 1.0,
           "yarn_mscale": float(y.mscale) if y else 1.0,
           "yarn_mscale_all_dim": float(y.mscale_all_dim) if y else 0.0}
    diff = {k: (v, s[k]) for k, v in got.items() if v != s[k]}
    if cfg.resolved_shared_d_ff != s["moe_ff"]:
        diff["shared_ff"] = (cfg.resolved_shared_d_ff, s["moe_ff"])
    if not (cfg.family == "moe" and cfg.use_mla and cfg.gated_mlp
            and cfg.act == "silu" and cfg.q_lora_rank == 0):
        diff["family"] = ((cfg.family, cfg.use_mla, cfg.q_lora_rank),
                          "moe, MLA without query compression, gated silu")
    extras = ("post_norms", "embed_scale", "attn_logit_softcap",
              "final_logit_softcap", "query_scale", "sliding_window",
              "local_global", "heads_padded", "route_group_limit")
    on = [k for k in extras if getattr(cfg, k)]
    if on:
        diff["features"] = (on, "none")
    if diff:
        raise ValueError(f"program configuration differs from the file "
                         f"(program, file): {diff}")


def init_weights(key, s: dict) -> dict:
    """Seeded float32 weights, in the program's parameter layout.

    Scales (listed under ``assumed`` in the configuration file): inputs of
    unit RMS give q entries of variance 1 and k entries of variance 2
    (YaRN's softmax factor of about 1.59 then gives scores of std about 2),
    v entries of variance 1; every other projection preserves variance;
    the router gives logits of std 3, so a token's top gates are large;
    norm gains are 1 + N(0, 0.1^2); the head gives logits of about std 3."""
    d, hq, rk, dn, dr, dv = (s["d"], s["hq"], s["r_kv"], s["dn"], s["dr"],
                             s["dv"])
    ff, mff, eh = s["ff"], s["moe_ff"], s["held"]
    sff = s["shared"] * mff
    nd, nm = s["dense_layers"], s["layers"] - s["dense_layers"]
    ks = iter(jax.random.split(key, 64))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def gain(shape):
        return 1.0 + normal(shape, 0.1)

    def attn(lead):
        wdkv = jnp.concatenate(
            [normal(lead + (d, rk), 1.0 / np.sqrt(d)),
             normal(lead + (d, dr), np.sqrt(2.0 / d))], axis=-1)
        return {"wuq": normal(lead + (d, hq, dn + dr), 1.0 / np.sqrt(d)),
                "wdkv": wdkv, "kv_norm": gain(lead + (rk,)),
                "wuk": normal(lead + (rk, hq, dn), np.sqrt(2.0 / rk)),
                "wuv": normal(lead + (rk, hq, dv), 1.0 / np.sqrt(rk)),
                "wo": normal(lead + (hq, dv, d), 1.0 / np.sqrt(hq * dv))}

    def mlp(lead, width):
        return {"wi_gate": normal(lead + (d, width), 1.0 / np.sqrt(d)),
                "wi_up": normal(lead + (d, width), 1.0 / np.sqrt(d)),
                "wo": normal(lead + (width, d), 1.0 / np.sqrt(width))}

    dense = [{"norm1": gain((d,)), "attn": attn(()), "norm2": gain((d,)),
              "ffn": mlp((), ff)} for _ in range(nd)]
    ffn = {"router": normal((nm, d, s["experts"]), 3.0 / np.sqrt(d)),
           **mlp((nm, eh), mff), "shared": mlp((nm,), sff)}
    tree = {"embed": normal((s["vocab"], d), 1.0),
            "dense_layers": dense,
            "layers": {"sub0": {"norm1": gain((nm, d)), "attn": attn((nm,)),
                                "norm2": gain((nm, d)), "ffn": ffn}},
            "final_norm": gain((d,))}
    if s["tied"]:
        raise ValueError("DeepSeek-V2 publishes an untied head")
    tree["lm_head"] = normal((d, s["vocab"]), 3.0 / np.sqrt(d))
    return tree


def _round(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(s: dict) -> np.ndarray:
    """Inverse frequencies of the rope dims, YaRN-scaled where the file
    sets ``rope_scaling``."""
    dim, base = s["dr"], s["theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    f = s["yarn_factor"]
    if not f:
        return extra

    def corr(rot):
        return dim * math.log(s["yarn_original"] / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    lo = max(math.floor(corr(s["yarn_beta_fast"])), 0)
    hi = min(math.ceil(corr(s["yarn_beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    mask = 1.0 - ramp               # 1 where a dim keeps its frequency
    return extra / f * (1.0 - mask) + extra * mask


def softmax_scale(s: dict) -> float:
    scale = (s["dn"] + s["dr"]) ** -0.5
    if s["yarn_factor"] and s["yarn_mscale_all_dim"]:
        scale *= _mscale(s["yarn_factor"], s["yarn_mscale_all_dim"]) ** 2
    return scale


def _rope(x, s):
    """Rotate-half rope on the last dim; x: (S, H, dr)."""
    pos = jnp.arange(x.shape[0], dtype=jnp.float32)
    ang = pos[:, None] * jnp.asarray(yarn_inv_freq(s), jnp.float32)[None, :]
    m = 1.0
    if s["yarn_factor"]:
        m = _mscale(s["yarn_factor"], s["yarn_mscale"]) \
            / _mscale(s["yarn_factor"], s["yarn_mscale_all_dim"])
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(h, a, s, r):
    S, hq, rk, dn = h.shape[0], s["hq"], s["r_kv"], s["dn"]
    q = jnp.einsum("sd,dhk->shk", h, r(a["wuq"]))
    ckv = h @ r(a["wdkv"])
    c = _rmsnorm(ckv[:, :rk], a["kv_norm"], s["eps"])
    k_nope = jnp.einsum("sr,rhk->shk", r(c), r(a["wuk"]))
    v = jnp.einsum("sr,rhk->shk", r(c), r(a["wuv"]))
    k_rope = jnp.broadcast_to(_rope(ckv[:, None, rk:], s),
                              (S, hq, s["dr"]))
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], s)], -1)
    k = jnp.concatenate([k_nope, k_rope], -1)
    sc = jnp.einsum("qhk,shk->hqs", r(q), r(k)) * softmax_scale(s)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqs,shk->qhk", r(p), r(v))
    return jnp.einsum("qhk,hkd->qd", r(o), r(a["wo"]))


def _mlp(h, f, r):
    g = jax.nn.silu(h @ r(f["wi_gate"])) * (h @ r(f["wi_up"]))
    return r(g) @ r(f["wo"])


def route(h, router, s):
    """(S, held) gates of the held experts: zero where a token did not
    route to the expert."""
    probs = jax.nn.softmax(h @ router, axis=-1)
    top, idx = jax.lax.top_k(probs, s["top_k"])
    if s["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    full = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)
    return full[:, :s["held"]]


def _experts(h, f, s, r):
    gates = route(h, r(f["router"]), s)
    g = jax.nn.silu(jnp.einsum("sd,edf->esf", h, r(f["wi_gate"]))) \
        * jnp.einsum("sd,edf->esf", h, r(f["wi_up"]))
    y = jnp.einsum("esf,efd->esd", r(g), r(f["wo"]))
    return jnp.einsum("se,esd->sd", gates, y) + _mlp(h, f["shared"], r)


def _layer(x, w, s, precision, *, moe):
    r = functools.partial(_round, precision=precision)
    h = r(_rmsnorm(x, w["norm1"], s["eps"]))
    x = x + _mla(h, w["attn"], s, r)
    h = r(_rmsnorm(x, w["norm2"], s["eps"]))
    return x + (_experts(h, w["ffn"], s, r) if moe else _mlp(h, w["ffn"], r))


@functools.partial(jax.jit, static_argnames=("s", "n_rows", "precision"))
def _logits(weights, tokens, first, *, s, n_rows, precision):
    s = dict(s)
    x = weights["embed"][tokens]
    for w in weights["dense_layers"]:
        x = _layer(x, w, s, precision, moe=False)

    def body(x, w):
        return _layer(x, w, s, precision, moe=True), None

    x, _ = jax.lax.scan(body, x, weights["layers"]["sub0"])
    x = jax.lax.dynamic_slice_in_dim(x, first, n_rows, axis=0)
    x = _round(_rmsnorm(x, weights["final_norm"], s["eps"]), precision)
    return x @ _round(weights["lm_head"], precision)


def _bucket(n: int, step: int) -> int:
    return -(-n // step) * step


def logits(weights, tokens: np.ndarray, first: int, n: int, s: dict, *,
           precision: str = "highest") -> np.ndarray:
    """Reference logits (n, vocab) for positions ``first .. first + n - 1``
    of the causal forward pass over ``tokens``.  Lengths are padded to
    buckets (pads sit after every scored position, so the causal mask keeps
    them out) to bound the number of compiled shapes."""
    n_rows = _bucket(n, 256)
    S = _bucket(max(len(tokens), first + n_rows), 512)
    padded = np.zeros(S, np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        out = _logits(weights, jnp.asarray(padded), jnp.int32(first),
                      s=tuple(sorted(s.items())), n_rows=n_rows,
                      precision=precision)
    return np.asarray(out, np.float32)[:n]
