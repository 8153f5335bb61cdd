"""Gradient compression for the scarce cross-pod links.

int8 uniform quantization with per-tensor scale and error feedback
(1-bit-Adam-family trick): the quantization residual is carried in the
training state and added back before the next step's quantization, so the
compression bias telescopes away and convergence is preserved.

Used by the explicit-DP training path (shard_map over the "pod" axis) and
unit-tested for the telescoping property.  Under plain pjit the gradient
all-reduce is inserted by XLA and cannot be intercepted — that trade
(implicit fp32 reduce vs explicit int8 reduce) is a launcher flag.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# the int8 codec lives in repro.quant (shared with the quantized-KV-cache
# path); re-exported here so existing callers keep importing from compress
from repro.quant import dequantize_int8, quantize_int8

__all__ = ["quantize_int8", "dequantize_int8", "compress_residual",
           "compressed_psum", "init_error_state",
           "make_compressed_dp_allreduce"]


def compress_residual(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(q, scale, error) — error = x - dequant(q) for error feedback."""
    q, scale = quantize_int8(x)
    err = x.astype(jnp.float32) - dequantize_int8(q, scale)
    return q, scale, err


def compressed_psum(tree: Any, axis: str, error_state: Any):
    """int8 all-reduce over ``axis`` with error feedback.

    Must run inside shard_map.  ``error_state`` mirrors ``tree`` (fp32).
    Returns (reduced_tree_fp32_mean, new_error_state).

    Wire cost: 1 byte/element + one fp32 scale per tensor, vs 4 bytes for a
    plain fp32 psum — a 4x cut on the pod-to-pod DCI bottleneck.
    """
    n = jax.lax.psum(1, axis)

    def one(g, e):
        g_fb = g.astype(jnp.float32) + e
        q, scale, err = compress_residual(g_fb)
        # int8 summands can overflow int8 — widen to int32 for the wire sum;
        # real deployments use the s8->s32 accumulating all-reduce
        q_sum = jax.lax.psum(q.astype(jnp.int32), axis)
        scale_max = jax.lax.pmax(scale, axis)
        return (q_sum.astype(jnp.float32) * scale_max) / n, err

    flat, treedef = jax.tree.flatten(tree)
    eflat = jax.tree.leaves(error_state)
    out, errs = [], []
    for g, e in zip(flat, eflat):
        r, err = one(g, e)
        out.append(r)
        errs.append(err)
    return jax.tree.unflatten(treedef, out), jax.tree.unflatten(treedef, errs)


def init_error_state(params: Any) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def make_compressed_dp_allreduce(mesh, axis: str = "pod"):
    """shard_map wrapper: gradients sharded over nothing but the DP axis
    (each pod holds its own grads) -> int8 mean across pods."""
    def fn(grads, error_state):
        def inner(g, e):
            return compressed_psum(g, axis, e)
        spec = jax.tree.map(lambda _: P(), grads)
        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(spec, spec), out_specs=(spec, spec),
                             check_vma=False)(grads, error_state)
    return fn
