"""Fused RMSNorm as a Pallas TPU kernel.

One pass over HBM: the (rows x d) input streams through VMEM in row-block
tiles, the fp32 mean-square reduction and the scale multiply fuse in
registers — XLA usually emits this as two kernels (reduce + scale) when the
scale is a separate parameter.  Supports the gemma (1 + w) parameterisation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float, gemma_style: bool):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    w = w_ref[...].astype(jnp.float32)
    if gemma_style:
        w = 1.0 + w
    o_ref[...] = (y * w).astype(o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
                   gemma_style: bool = False, block_rows: int = 256,
                   interpret: bool = False) -> jax.Array:
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    if rows % block_rows:
        block_rows = 1                       # ragged smoke shapes
    kernel = functools.partial(_rms_kernel, eps=eps, gemma_style=gemma_style)
    out = pl.pallas_call(
        kernel,
        name="rmsnorm",
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
                  pl.BlockSpec((d,), lambda r: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x2, scale)
    return out.reshape(orig_shape)
