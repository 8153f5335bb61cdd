"""Shared model building blocks: norms, RoPE, activations, initialisers.

Pure-functional JAX: params are pytrees of jnp arrays, every module is a pair
of (init_fn, apply_fn)-style free functions.  Keeping this dependency-free
(no flax/haiku) makes the sharding rules in repro.runtime.sharding a simple
path-pattern match over the param tree.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = dict[str, Any]


# --------------------------------------------------------------------------
# dtype helpers
# --------------------------------------------------------------------------
def dt(name: str) -> jnp.dtype:
    return jnp.dtype(name)


# --------------------------------------------------------------------------
# initialisers (numpy RNG for cheap, reproducible host-side init)
# --------------------------------------------------------------------------
def normal_init(key: jax.Array, shape: tuple[int, ...], std: float,
                dtype: str = "float32") -> jax.Array:
    return (jax.random.normal(key, shape, dtype=jnp.float32) * std).astype(dtype)


def fan_in_init(key: jax.Array, shape: tuple[int, ...],
                dtype: str = "float32") -> jax.Array:
    """Truncated-normal-ish scaled by 1/sqrt(fan_in) (first dim = fan_in)."""
    fan_in = shape[0] if len(shape) >= 1 else 1
    return normal_init(key, shape, std=1.0 / np.sqrt(max(fan_in, 1)), dtype=dtype)


def zeros(shape: tuple[int, ...], dtype: str = "float32") -> jax.Array:
    return jnp.zeros(shape, dtype=dtype)


def ones(shape: tuple[int, ...], dtype: str = "float32") -> jax.Array:
    return jnp.ones(shape, dtype=dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
            gemma_style: bool = False) -> jax.Array:
    """RMSNorm in fp32, cast back to x.dtype.

    ``gemma_style=True`` uses the (1 + scale) parameterisation gemma2 ships.
    """
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    w = scale.astype(jnp.float32)
    if gemma_style:
        w = 1.0 + w
    return (y * w).astype(x.dtype)


def gated_rmsnorm(x: jax.Array, gate: jax.Array, scale: jax.Array,
                  eps: float = 1e-6) -> jax.Array:
    """Mamba2's norm: RMSNorm(x * silu(gate)) — fused gate-then-norm."""
    x32 = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# activations
# --------------------------------------------------------------------------
def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "relu":
        return jax.nn.relu
    raise ValueError(f"unknown activation {name!r}")


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """gemma2 logit soft-capping: cap * tanh(x / cap)."""
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rope scaling as DeepSeek-V2 publishes it (``rope_scaling`` of
    type "yarn"): frequencies below ``beta_slow`` rotations over the
    original context are interpolated by ``factor``, those above
    ``beta_fast`` kept, with a linear ramp between; cos/sin are scaled by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` and the
    attention softmax by ``mscale(factor, mscale_all_dim) ** 2``."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_softmax_factor(scaling: YaRN | None) -> float:
    """What YaRN multiplies the attention softmax scale by (1 without)."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def rope_frequencies(head_dim: int, theta: float,
                     scaling: YaRN | None = None) -> np.ndarray:
    extra = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    if scaling is None:
        return extra

    def correction_dim(rotations):
        return head_dim * math.log(scaling.original_max_position
                                   / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0.0, 1.0)
    return extra / scaling.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0,
               scaling: YaRN | None = None) -> jax.Array:
    """Rotate pairs (x[..., :d/2], x[..., d/2:]) — llama convention.

    x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).
    """
    head_dim = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(head_dim, theta, scaling),
                        dtype=jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs          # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                                # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None:
        m = yarn_mscale(scaling.factor, scaling.mscale) \
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim)
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope_partial(x: jax.Array, positions: jax.Array, theta: float,
                       fraction: float = 1.0) -> jax.Array:
    """stablelm-style partial rotary: rotate only the first ``fraction`` of
    head dims, pass the rest through."""
    if fraction >= 1.0:
        return apply_rope(x, positions, theta)
    rd = int(x.shape[-1] * fraction)
    rd -= rd % 2
    rot = apply_rope(x[..., :rd], positions, theta)
    return jnp.concatenate([rot, x[..., rd:]], axis=-1)


# --------------------------------------------------------------------------
# losses
# --------------------------------------------------------------------------
def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    """Mean token-level CE in fp32.  labels: int ids; mask: 1 = count."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def shift_labels(tokens: jax.Array, pad_id: int = 0):
    """Next-token prediction: inputs tokens[:, :-1] predict tokens[:, 1:]."""
    inputs = tokens[:, :-1]
    labels = tokens[:, 1:]
    mask = (labels != pad_id).astype(jnp.float32)
    return inputs, labels, mask


# --------------------------------------------------------------------------
# tree utilities
# --------------------------------------------------------------------------
def tree_size_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_count(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def split_keys(key: jax.Array, n: int) -> list[jax.Array]:
    return list(jax.random.split(key, n))
