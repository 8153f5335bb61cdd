"""Shared model building blocks: norms, RoPE, activations, initialisers.

Pure-functional JAX: params are pytrees of jnp arrays, every module is a pair
of (init_fn, apply_fn)-style free functions.  Keeping this dependency-free
(no flax/haiku) makes the sharding rules in repro.runtime.sharding a simple
path-pattern match over the param tree.
"""
from __future__ import annotations

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
def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0) -> jax.Array:
    """Rotate pairs (x[..., :d/2], x[..., d/2:]) — llama convention.

    x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).
    """
    head_dim = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(head_dim, theta), dtype=jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs          # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                                # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
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
