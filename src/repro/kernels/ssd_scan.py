"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

Tiling: grid = (B, H, n_chunks), chunk index minor-most so the carried SSM
state h (P x N, fp32) persists in VMEM scratch across a head's chunks —
the inter-chunk recurrence never touches HBM.  Per chunk the kernel does
the SSD dual form entirely on MXU-shaped (Q x Q) / (Q x N) / (Q x P)
blocks:

    1. L = exp(segsum(a))              intra-chunk decay, lower-tri
    2. y_diag = ((C B^T) .* L .* dt) x
    3. y_off  = C h_in  .* exp(cumsum a)
    4. h_out  = exp(total) h_in + B^T (dt .* rem .* x)

With Q = 128 (the config default), every operand aligns to the (8, 128)
TPU tile and VMEM use per (b, h) is Q*(2N + 2P + Q) * 4B ≈ 330 KB.

GQA-style B/C groups are folded into the index_map (head h reads group
h // (H // G)).  Validated against kernels/ref.py (exact sequential scan)
in tests/test_kernels.py.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, y_ref, h_ref, *,
                chunk: int, has_D: bool):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)        # (Q,)
    A = A_ref[0].astype(jnp.float32)                # ()
    Bm = B_ref[0, 0, 0].astype(jnp.float32)         # (Q, N)
    Cm = C_ref[0, 0, 0].astype(jnp.float32)         # (Q, N)

    a = dt * A                                      # (Q,)
    cs = jnp.cumsum(a)                              # (Q,)
    # 1. intra-chunk decay matrix
    L = cs[:, None] - cs[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(tri, jnp.exp(L), 0.0)
    # 2. diagonal block
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    M = G * L * dt[None, :]
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, P)
    # 3. carried-state contribution
    h = h_ref[...]                                  # (P, N)
    y += jnp.exp(cs)[:, None] * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)         # (Q, P)
    # 4. next state
    total = cs[-1]
    rem = jnp.exp(total - cs)                       # (Q,)
    w = (dt * rem)[:, None] * Bm                    # (Q, N)
    dBx = jax.lax.dot_general(x, w, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    h_ref[...] = jnp.exp(total) * h + dBx

    if has_D:
        y += D_ref[0].astype(jnp.float32) * x
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)


def ssd_pallas(
    x: jax.Array, dt: jax.Array, A: jax.Array,
    B_mat: jax.Array, C_mat: jax.Array, D: jax.Array | None = None, *,
    chunk: int = 128, initial_state: jax.Array | None = None,
    return_state: bool = False, interpret: bool = False,
):
    """Same contract as ops.ssd_chunked_jnp; initial_state/return_state fall
    back to the jnp path with a warning (the kernel is the steady-state
    training fast path)."""
    if initial_state is not None or return_state:
        from repro.kernels import ops
        warnings.warn(
            "ssd_pallas: the kernel neither takes nor returns SSM state; "
            "falling back to the chunked jnp path", ops.FallbackWarning,
            stacklevel=2)
        return ops.ssd_chunked_jnp(x, dt, A, B_mat, C_mat, D, chunk=chunk,
                                   initial_state=initial_state,
                                   return_state=return_state)
    Bb, S, H, P = x.shape
    Gg, N = B_mat.shape[2], B_mat.shape[3]
    rep = H // Gg
    chunk = min(chunk, S)
    assert S % chunk == 0, "pad sequence to the SSD chunk size"
    nc = S // chunk

    xt = x.transpose(0, 2, 1, 3).reshape(Bb, H, nc, chunk, P)
    dtt = dt.transpose(0, 2, 1).reshape(Bb, H, nc, chunk)
    Bt = B_mat.transpose(0, 2, 1, 3).reshape(Bb, Gg, nc, chunk, N)
    Ct = C_mat.transpose(0, 2, 1, 3).reshape(Bb, Gg, nc, chunk, N)
    D_in = D if D is not None else jnp.zeros((H,), jnp.float32)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, has_D=D is not None)
    y = pl.pallas_call(
        kernel,
        name="ssd_scan",
        grid=(Bb, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, 1, chunk, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, P),
                               lambda b, h, c: (b, h, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb, H, nc, chunk, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, A, Bt, Ct, D_in)
    return y.reshape(Bb, H, S, P).transpose(0, 2, 1, 3)
