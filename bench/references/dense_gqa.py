"""Plain reference for dense decoder-only LMs with grouped-query attention.

Covers the Llama layout (SmolLM) and StableLM-2's partial rotary.  Written
from the published descriptions in straightforward ``jax.numpy`` and
float32, with no kernel, cache or batching, and importing nothing of the
program under test.  It also owns the seeded weights: the benchmark makes
them here, hands the same tree to the program (whose parameter layout it
matches), and remakes them from the seed for the comparison.

Per layer (pre-norm residual):
    h = RMSNorm(x) ; x += Attn(h) ; h = RMSNorm(x) ; x += W_down(silu(W_gate h) * W_up h)
Attention: q, k, v projections without bias; rotary embedding on the first
``partial_rotary_factor`` of each head's dims, rotate-half convention
(first half against second half); causal softmax at 1/sqrt(head_dim).
Logits: RMSNorm(x) @ head, with the head tied to the embedding where
``tie_word_embeddings``.  StableLM-2 publishes LayerNorm with bias and q/k/v
biases; the configuration file states the RMSNorm and bias-free form that
is run, and lists the departure under ``assumed``.

``precision``: "highest" is float32 at highest matmul precision;
"fp8" rounds every matmul operand (weights and activations, scores and
probabilities) to float8_e4m3fn first: the control that a sound
comparison must reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sizes(config: dict) -> dict:
    """The reference's sizes from a configuration file's published keys."""
    d = int(config["hidden_size"])
    hq = int(config["num_attention_heads"])
    hd = int(config.get("head_dim") or d // hq)
    rot = int(hd * float(config.get("partial_rotary_factor", 1.0)))
    rot -= rot % 2
    eps = config.get("rms_norm_eps", config.get("layer_norm_eps"))
    return {"d": d, "hq": hq, "hkv": int(config["num_key_value_heads"]),
            "hd": hd, "ff": int(config["intermediate_size"]),
            "vocab": int(config["vocab_size"]),
            "layers": int(config["num_hidden_layers"]),
            "rot": rot, "theta": float(config["rope_theta"]),
            "eps": float(eps), "tied": bool(config["tie_word_embeddings"])}


def check_program(s: dict, cfg) -> None:
    """Refuse to run a program configuration that differs from the file's:
    the harness would then compare two different models."""
    got = {"d": cfg.d_model, "hq": cfg.n_heads, "hkv": cfg.n_kv_heads,
           "hd": cfg.resolved_head_dim, "ff": cfg.d_ff,
           "vocab": cfg.vocab_size, "layers": cfg.n_layers,
           "theta": float(cfg.rope_theta), "eps": float(cfg.norm_eps),
           "tied": bool(cfg.tie_embeddings)}
    rot = int(cfg.resolved_head_dim * cfg.rope_fraction)
    got["rot"] = rot - rot % 2
    diff = {k: (v, s[k]) for k, v in got.items() if v != s[k]}
    if cfg.family != "dense" or cfg.gated_mlp is not True or cfg.act != "silu":
        diff["family"] = (cfg.family, "dense gated silu")
    extras = ("post_norms", "embed_scale", "attn_logit_softcap",
              "final_logit_softcap", "query_scale", "sliding_window",
              "local_global", "heads_padded", "use_mla", "n_experts")
    on = [k for k in extras if getattr(cfg, k)]
    if on:
        diff["features"] = (on, "none")
    if diff:
        raise ValueError(f"program configuration differs from the file "
                         f"(program, file): {diff}")


def init_weights(key, s: dict) -> dict:
    """Seeded float32 weights, in the program's parameter layout.

    Scales (listed under ``assumed`` in each configuration file): inputs of
    unit RMS give q and k entries of variance 2 (attention scores of about
    std 2), every other projection preserves variance, norm gains are
    1 + N(0, 0.1^2), and the head gives logits of about std 3."""
    d, hq, hkv, hd, ff, nl = (s["d"], s["hq"], s["hkv"], s["hd"], s["ff"],
                              s["layers"])
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def gain(shape):
        return 1.0 + normal(shape, 0.1)

    tree = {
        "embed": normal((s["vocab"], d), 3.0 / np.sqrt(d) if s["tied"]
                        else 1.0),
        "layers": {"sub0": {
            "norm1": gain((nl, d)),
            "attn": {"wq": normal((nl, d, hq, hd), np.sqrt(2.0 / d)),
                     "wk": normal((nl, d, hkv, hd), np.sqrt(2.0 / d)),
                     "wv": normal((nl, d, hkv, hd), 1.0 / np.sqrt(d)),
                     "wo": normal((nl, hq, hd, d), 1.0 / np.sqrt(hq * hd))},
            "norm2": gain((nl, d)),
            "ffn": {"wi_gate": normal((nl, d, ff), 1.0 / np.sqrt(d)),
                    "wi_up": normal((nl, d, ff), 1.0 / np.sqrt(d)),
                    "wo": normal((nl, ff, d), 1.0 / np.sqrt(ff))},
        }},
        "final_norm": gain((d,)),
    }
    if not s["tied"]:
        tree["lm_head"] = normal((d, s["vocab"]), 3.0 / np.sqrt(d))
    return tree


def _round(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, s):
    """Rotate the first ``rot`` dims of each head; x: (S, H, hd)."""
    rot = s["rot"]
    if rot == 0:
        return x
    pos = jnp.arange(x.shape[0], dtype=jnp.float32)
    inv = 1.0 / (s["theta"] ** (np.arange(0, rot, 2, dtype=np.float64)
                                / rot))
    ang = pos[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def _layer(x, w, s, precision):
    r = functools.partial(_round, precision=precision)
    S = x.shape[0]
    hq, hkv, hd = s["hq"], s["hkv"], s["hd"]
    h = r(_rmsnorm(x, w["norm1"], s["eps"]))
    a = w["attn"]
    q = _rope(jnp.einsum("sd,dhk->shk", h, r(a["wq"])), s)
    k = _rope(jnp.einsum("sd,dhk->shk", h, r(a["wk"])), s)
    v = jnp.einsum("sd,dhk->shk", h, r(a["wv"]))
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    sc = jnp.einsum("qhk,shk->hqs", r(q), r(k)) / np.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqs,shk->qhk", r(p), r(v))
    x = x + jnp.einsum("qhk,hkd->qd", r(o), r(a["wo"]))
    h = r(_rmsnorm(x, w["norm2"], s["eps"]))
    f = w["ffn"]
    g = jax.nn.silu(h @ r(f["wi_gate"])) * (h @ r(f["wi_up"]))
    return x + r(g) @ r(f["wo"])


@functools.partial(jax.jit, static_argnames=("s", "n_rows", "precision"))
def _logits(weights, tokens, first, *, s, n_rows, precision):
    s = dict(s)
    x = weights["embed"][tokens]

    def body(x, w):
        return _layer(x, w, s, precision), None

    x, _ = jax.lax.scan(body, x, weights["layers"]["sub0"])
    x = jax.lax.dynamic_slice_in_dim(x, first, n_rows, axis=0)
    x = _round(_rmsnorm(x, weights["final_norm"], s["eps"]), precision)
    head = weights["embed"].T if s["tied"] else weights["lm_head"]
    return x @ _round(head, precision)


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
