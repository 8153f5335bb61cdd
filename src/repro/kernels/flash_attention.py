"""Flash attention as a Pallas TPU kernel.

TPU-native tiling (the hardware-adaptation of the GPU flash algorithm):

  * grid = (batch, q_heads, q_blocks, k_blocks) — the k dimension is
    minor-most, so for a fixed (b, h, iq) the kernel revisits the same
    output tile while streaming k/v blocks HBM -> VMEM; the online-softmax
    running state (m, l, acc) lives in fp32 VMEM scratch across those
    revolutions (this replaces the GPU's shared-memory accumulator).
  * BlockSpec q tile (block_q, D) and k/v tiles (block_k, D) are chosen so
    q + k + v + acc fit VMEM (~2.6 MB at the 512/512 default with D=128)
    and all MXU operands are (8,128)-aligned.
  * GQA is folded into the k/v index_map (q head h reads kv head
    h // (Hq // Hkv)) — no kv replication in HBM.
  * causal / sliding-window masking is computed from block-relative iota;
    fully-masked k blocks are predicated off with pl.when (on real
    hardware a splash-style grid prune would skip their DMA too; the
    roofline accounting uses the jnp chunked path, which skips them
    structurally).

Validated in interpret mode against kernels/ref.py over shape/dtype sweeps
(tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: int, logit_cap: float,
               block_q: int, block_k: int, n_k: int, q_offset: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    iq = pl.program_id(2)
    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # does this (iq, ik) block contain any visible (q, k) pair?
    visible = jnp.bool_(True)
    if causal:
        visible = jnp.logical_and(
            visible, ik * block_k <= q_offset + (iq + 1) * block_q - 1)
    if window > 0:
        visible = jnp.logical_and(
            visible, (ik + 1) * block_k - 1 > q_offset + iq * block_q - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if logit_cap > 0.0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,                  # (B, Sq, Hq, D)
    k: jax.Array,                  # (B, Sk, Hkv, D)
    v: jax.Array,                  # (B, Sk, Hkv, Dv)
    *,
    causal: bool = True, window: int = 0, logit_cap: float = 0.0,
    scale: float | None = None, q_offset: int = 0,
    block_q: int = 512, block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q or Sk % block_k:
        # ragged shapes run the chunked jnp path, with a warning (lazy
        # import: ops imports this module lazily too)
        from repro.kernels.ops import FallbackWarning, flash_attention_jnp
        warnings.warn(
            f"flash_attention: Sq={Sq}, Sk={Sk} do not tile into blocks of "
            f"({block_q}, {block_k}); falling back to the jnp path",
            FallbackWarning, stacklevel=2)
        return flash_attention_jnp(q, k, v, causal=causal, window=window,
                                   logit_cap=logit_cap, scale=scale,
                                   q_offset=q_offset)
    n_q, n_k = Sq // block_q, Sk // block_k

    qt = q.transpose(0, 2, 1, 3)        # (B, Hq, Sq, D)
    kt = k.transpose(0, 2, 1, 3)        # (B, Hkv, Sk, D)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        logit_cap=logit_cap, block_q=block_q, block_k=block_k, n_k=n_k,
        q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # running max m
            pltpu.VMEM((block_q,), jnp.float32),      # running denom l
            pltpu.VMEM((block_q, Dv), jnp.float32),   # running numerator
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)    # back to (B, Sq, Hq, Dv)
