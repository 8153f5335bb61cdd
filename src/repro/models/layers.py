"""Feed-forward layers: dense SwiGLU MLP and expert-parallel MoE.

MoE dispatch is sort-then-grouped-matmul, with no capacity and no dropped
token: the router spans all ``n_experts``, each token keeps its top-k, the
(token, choice) rows routed to the experts held here are sorted by expert
and run through ``jax.lax.ragged_dot`` (one grouped matmul per expert
weight), and the rows come back to their tokens weighted by their gates.
Rows routed to experts held elsewhere are computed nowhere here: that is
the chip-share cut (``ModelConfig.held_experts``), whose partial result
goes on to the next layer as an expert-parallel chip's would before its
exchange.

  * Without a mesh the layer holds ``held_experts`` experts (all of them by
    default) and returns their part of the result plus the shared experts.
  * With a mesh, tokens are batch-sharded over ("pod","data") and
    replicated over "model"; the held experts are sharded over "model"
    (EP).  Inside a shard_map each model shard runs the same dispatch over
    ITS experts (selection is local because tokens are replicated on the
    model axis), and the combine is a single psum over "model" — the same
    all-reduce pattern Megatron TP uses, so MoE adds no new collective
    phase.  Shared experts (DeepSeek) are computed in the same shard_map
    with their hidden dim sliced over "model", folded into the same psum.
  * ``moe_strategy="a2a"`` (``moe_forward_a2a``) owns experts by data rank
    and exchanges tokens; its all-to-all buffers are static, so that path
    keeps a capacity.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import common
from repro.models.attention import ParamLeaf, pl_
from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """How model code sees the mesh.  None mesh = single-process smoke path."""
    mesh: Any = None
    batch_axes: tuple[str, ...] = ("pod", "data")
    model_axis: str = "model"
    data_axis: str = "data"
    moe_strategy: str = "gather"   # gather | a2a (see moe_forward_a2a)

    @property
    def model_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def data_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape.get(self.data_axis, 1)


NO_MESH = ParallelCtx()


# ==========================================================================
# dense SwiGLU MLP
# ==========================================================================
def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None) -> dict[str, Any]:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    k1, k2, k3 = common.split_keys(key, 3)
    dt = cfg.param_dtype
    p = {
        "wi_up": pl_(k2, (d, ff), ("embed", "mlp"), dtype=dt),
        "wo": pl_(k3, (ff, d), ("mlp", "embed"), dtype=dt),
    }
    if cfg.gated_mlp:
        p["wi_gate"] = pl_(k1, (d, ff), ("embed", "mlp"), dtype=dt)
    return p


def mlp_forward(params, x, cfg: ModelConfig, constrain=None) -> jax.Array:
    adt = x.dtype
    act = common.activation(cfg.act)
    if "wi_gate" in params:
        h = act(x @ params["wi_gate"].astype(adt)) * (x @ params["wi_up"].astype(adt))
    else:
        h = act(x @ params["wi_up"].astype(adt))
    if constrain is not None:
        h = constrain(h, ("batch", None, "mlp_act"))
    out = h @ params["wo"].astype(adt)
    if constrain is not None:
        out = constrain(out, ("batch", None, "embed_act"))
    return out


# ==========================================================================
# MoE
# ==========================================================================
def init_moe(key, cfg: ModelConfig) -> dict[str, Any]:
    d = cfg.d_model
    E, ff = cfg.resolved_held_experts, cfg.resolved_moe_d_ff
    keys = common.split_keys(key, 6)
    dt = cfg.param_dtype
    # expert weights get DEDICATED logical axes so the sharding strategy can
    # re-map them without touching the rest of the model:
    #   gather: experts->model (EP), expert_d->data (FSDP), expert_ff->None
    #   a2a:    experts->data (EP ownership), expert_ff->model (Megatron
    #           within-expert TP), expert_d->None — zero weight gathers
    p = {
        "router": pl_(keys[0], (d, cfg.n_experts), ("embed", None), dtype=dt),
        "wi_gate": pl_(keys[1], (E, d, ff),
                       ("experts", "expert_d", "expert_ff"), dtype=dt),
        "wi_up": pl_(keys[2], (E, d, ff),
                     ("experts", "expert_d", "expert_ff"), dtype=dt),
        "wo": pl_(keys[3], (E, ff, d),
                  ("experts", "expert_ff", "expert_d"), dtype=dt),
    }
    if cfg.n_shared_experts:
        sff = cfg.resolved_shared_d_ff * cfg.n_shared_experts
        p["shared"] = {
            "wi_gate": pl_(keys[4], (d, sff), ("embed", "mlp"), dtype=dt),
            "wi_up": pl_(keys[5], (d, sff), ("embed", "mlp"), dtype=dt),
            "wo": pl_(common.split_keys(keys[4], 2)[1], (sff, d),
                      ("mlp", "embed"), dtype=dt),
        }
    return p


def _grouped_experts(x2d, gates, idx, wi_gate, wi_up, wo, first, act):
    """The held experts' part of the layer for every token: no capacity,
    no dropped row.

    x2d: (T, d); gates/idx: (T, k) top-k routing over all experts;
    wi_*: (E_h, d, ff) / wo: (E_h, ff, d) experts ``first .. first+E_h-1``.
    The (token, choice) rows routed to a held expert are sorted by expert
    (rows for other experts sort last, outside every group) and run as
    three grouped matmuls; each row comes back to its token weighted by
    its gate, the k choices summed in float32.  Returns the (T, d)
    partial output."""
    T, d = x2d.shape
    k = idx.shape[1]
    E_h = wi_gate.shape[0]
    e = idx.reshape(-1) - first                         # (T*k,)
    held = (e >= 0) & (e < E_h)
    order = jnp.argsort(jnp.where(held, e, E_h), stable=True)
    sizes = jnp.sum(jax.nn.one_hot(e, E_h, dtype=jnp.int32), axis=0)
    xs = x2d[order // k]
    h = act(jax.lax.ragged_dot(xs, wi_gate, sizes)) \
        * jax.lax.ragged_dot(xs, wi_up, sizes)
    ys = jax.lax.ragged_dot(h, wo, sizes)[jnp.argsort(order)]
    # rows outside every group are never read: select, not multiply
    w = jnp.where(held, gates.reshape(-1), 0.0)[:, None]
    ys = jnp.where(held[:, None], ys.astype(jnp.float32) * w, 0.0)
    return jnp.sum(ys.reshape(T, k, d), axis=1).astype(x2d.dtype)


def moe_forward(params, x, cfg: ModelConfig, ctx: ParallelCtx = NO_MESH,
                constrain=None, *, first_expert: int = 0):
    """Top-k MoE FFN over the held experts.  x: (B, S, d).

    Returns (out, aux_loss, rows): ``rows`` counts the (token, expert) rows
    the held experts computed.  ``first_expert`` is the first held expert's
    index among the router's ``n_experts`` (the layer's chip share)."""
    B, S, d = x.shape
    adt = x.dtype
    E, k = cfg.n_experts, cfg.experts_per_token
    act = common.activation(cfg.act)

    x2d = x.reshape(B * S, d)
    logits = (x2d @ params["router"].astype(adt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)             # (T, E)
    if cfg.route_group_limit and ctx.mesh is not None:
        # DeepSeek-V2 device-limited routing: each token's experts must live
        # on <= M device groups (chosen by the groups' summed affinity) —
        # this bounds the all-to-all fan-out to M ranks per token.
        n_groups = ctx.data_size if ctx.moe_strategy == "a2a" \
            else ctx.model_size
        if E % n_groups == 0 and n_groups > cfg.route_group_limit:
            gsz = E // n_groups
            gscore = probs.reshape(-1, n_groups, gsz).sum(-1)   # (T, G)
            _, top_g = jax.lax.top_k(gscore, cfg.route_group_limit)
            gmask = jnp.zeros_like(gscore).at[
                jnp.arange(gscore.shape[0])[:, None], top_g].set(1.0)
            probs = probs * jnp.repeat(gmask, gsz, axis=1)
    gates, idx = jax.lax.top_k(probs, k)                # (T, k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # Switch-style load-balance aux loss (computed identically on all shards)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs) * cfg.aux_loss_weight

    E_h = cfg.resolved_held_experts
    rows = jnp.sum((idx >= first_expert) & (idx < first_expert + E_h),
                   dtype=jnp.int32)
    if (ctx.mesh is not None and ctx.moe_strategy == "a2a"
            and E % max(ctx.data_size, 1) == 0):
        if E_h != E or first_expert:
            raise ValueError("the all-to-all MoE path owns every expert; "
                             "a chip share (held_experts) runs without it")
        out, aux = moe_forward_a2a(params, x, cfg, ctx, gates, idx, aux)
        return out, aux, rows

    if ctx.mesh is None:
        out2d = _grouped_experts(
            x2d, gates, idx, params["wi_gate"].astype(adt),
            params["wi_up"].astype(adt), params["wo"].astype(adt),
            first_expert, act)
        if "shared" in params:
            out2d = out2d + _shared_expert(params["shared"], x2d, act, adt)
        return out2d.reshape(B, S, d), aux, rows

    maxis = ctx.model_axis
    E_loc = E_h // ctx.model_size
    baxes = tuple(a for a in ctx.batch_axes if a in ctx.mesh.shape)
    bspec = P(baxes)          # shard dim 0 of (T, ...) over all batch axes

    def shard_fn(x2d_l, gates_l, idx_l, wig, wiu, wog, shared):
        lo = first_expert + jax.lax.axis_index(maxis) * E_loc
        out = _grouped_experts(x2d_l, gates_l, idx_l, wig.astype(adt),
                               wiu.astype(adt), wog.astype(adt), lo, act)
        if shared is not None:
            out = out + _shared_expert(shared, x2d_l, act, adt)
        return jax.lax.psum(out, maxis)

    shared_p = params.get("shared")
    shared_specs = None
    if shared_p is not None:
        # shared-expert hidden dim sliced over model; psum restores full out
        shared_specs = {"wi_gate": P(None, maxis), "wi_up": P(None, maxis),
                        "wo": P(maxis, None)}

    out2d = jax.shard_map(
        shard_fn, mesh=ctx.mesh,
        in_specs=(bspec, bspec, bspec,
                  P(maxis), P(maxis), P(maxis), shared_specs),
        out_specs=bspec,
        check_vma=False,
    )(x2d, gates, idx, params["wi_gate"], params["wi_up"], params["wo"],
      shared_p)
    return out2d.reshape(B, S, d), aux, rows


def _place(dest, sel, capacity, n_dest):
    """One-hot-cumsum slot assignment: returns (slot, keep) for scattering
    items into per-destination capacity buffers.  dest: (M,) ints; sel: (M,)
    bool.  slot in [0, n_dest*capacity)."""
    oh = jax.nn.one_hot(dest, n_dest, dtype=jnp.float32) * sel[:, None]
    pos = (jnp.cumsum(oh, axis=0) - oh) * oh
    pos_at = jnp.sum(pos, axis=1).astype(jnp.int32)
    keep = sel & (pos_at < capacity)
    slot = jnp.where(keep, dest * capacity + pos_at, n_dest * capacity)
    return slot, keep


def moe_forward_a2a(params, x, cfg: ModelConfig, ctx: ParallelCtx,
                    gates, idx, aux):
    """Dispatch-by-all-to-all expert parallelism (beyond-paper optimization).

    Layout: experts are OWNED by data ranks (E / n_data each) with their
    hidden dim ff sharded over the model axis (Megatron within-expert TP).
    Expert weights are therefore never gathered — the baseline "gather"
    strategy moves the full fp32 expert slab per layer per microbatch, which
    the dry-run showed dominating deepseek-v2's collective term.

    Per layer the wire cost is 2 token all-to-alls over "data" (send tokens
    to their experts' owners, return outputs) + 1 psum over "model" — token
    bytes instead of weight bytes.
    """
    B, S, d = x.shape
    adt = x.dtype
    E, k = cfg.n_experts, cfg.experts_per_token
    act = common.activation(cfg.act)
    mesh = ctx.mesh
    daxis, maxis = ctx.data_axis, ctx.model_axis
    n_data = ctx.data_size
    E_loc = E // n_data

    baxes = tuple(a for a in ctx.batch_axes if a in mesh.shape)
    n_batch_shards = 1
    for a in baxes:
        n_batch_shards *= mesh.shape[a]
    T_l = (B * S) // n_batch_shards                  # tokens per device
    # (token, dest) copies are DEDUPED, so the per-token wire fan-out is
    # min(k, n_data) — and route_group_limit (DeepSeek device-limited
    # routing) bounds it to M.  Capacities follow the effective fan-out.
    fan = min(k, n_data)
    if cfg.route_group_limit:
        fan = min(fan, cfg.route_group_limit)
    if T_l <= 256:                                    # decode/smoke: lossless
        cap_send = T_l * fan
        cap_exp = n_data * cap_send
    else:
        cap_send = max(1, int(cfg.capacity_factor * fan * T_l / n_data))
        cap_exp = max(1, int(cfg.capacity_factor * k * T_l * n_data / E))

    x2d = x.reshape(B * S, d)
    bspec = P(baxes)

    def shard_fn(x_l, gates_l, idx_l, wig, wiu, wog, shared):
        T, _ = x_l.shape
        dest = idx_l // E_loc                        # (T, k) owning data rank
        local_e = idx_l % E_loc

        # ---- dedup (token, dest) pairs: a token whose experts share an
        # owner is sent ONCE, carrying a gate VECTOR over that owner's
        # E_loc experts.  With DeepSeek-style device-limited routing
        # (route_group_limit = M) this bounds wire copies to M per token.
        first = jnp.ones((T, k), bool)
        for j in range(1, k):
            dup = jnp.zeros((T,), bool)
            for i in range(j):
                dup |= dest[:, j] == dest[:, i]
            first = first.at[:, j].set(~dup)
        # per-(token,k): gate vector contribution to (dest, local_e)
        flat_dest = dest.reshape(-1)
        flat_first = first.reshape(-1)
        tok = jnp.repeat(jnp.arange(T), k)

        slot, keep = _place(flat_dest, flat_first, cap_send, n_data)
        kf = keep[:, None].astype(adt)
        # map every (token,k) pair to the slot of its (token,dest) copy:
        # pairs suppressed by dedup reuse the FIRST copy's slot
        slot_map = jnp.full((T, n_data), n_data * cap_send, jnp.int32)
        slot_map = slot_map.at[tok, flat_dest].min(
            jnp.where(keep, slot, n_data * cap_send))
        pair_slot = slot_map[tok, flat_dest]          # (T*k,)
        pair_ok = pair_slot < n_data * cap_send

        send_x = jnp.zeros((n_data * cap_send, d), adt) \
            .at[slot].add(x_l[tok] * kf, mode="drop")
        # gate payload: (slots, E_loc) accumulated over the pairs
        send_g = jnp.zeros((n_data * cap_send, E_loc), adt) \
            .at[jnp.where(pair_ok, pair_slot, n_data * cap_send),
                local_e.reshape(-1)].add(
                gates_l.reshape(-1).astype(adt) * pair_ok, mode="drop")

        recv_x = jax.lax.all_to_all(send_x.reshape(n_data, cap_send, d),
                                    daxis, 0, 0, tiled=False)
        recv_g = jax.lax.all_to_all(send_g.reshape(n_data, cap_send, E_loc),
                                    daxis, 0, 0, tiled=False)

        # ---- dispatch received copies into my experts' buffers -------------
        rx = recv_x.reshape(-1, d)                   # (R, d)
        rg = recv_g.reshape(-1, E_loc)               # (R, E_loc)
        R = rx.shape[0]
        # every (copy, local expert) with nonzero gate is an assignment
        a_e = jnp.tile(jnp.arange(E_loc), R)
        a_copy = jnp.repeat(jnp.arange(R), E_loc)
        a_gate = rg.reshape(-1)
        sel2 = a_gate != 0
        slot2, keep2 = _place(a_e, sel2, cap_exp, E_loc)
        buf = jnp.zeros((E_loc * cap_exp, d), adt) \
            .at[slot2].add(rx[a_copy] * keep2[:, None].astype(adt),
                           mode="drop")
        buf = buf.reshape(E_loc, cap_exp, d)

        # ---- expert compute, ff sharded over model (partial sums) ----------
        h = act(jnp.einsum("ecd,edf->ecf", buf, wig.astype(adt))) * \
            jnp.einsum("ecd,edf->ecf", buf, wiu.astype(adt))
        out_buf = jnp.einsum("ecf,efd->ecd", h, wog.astype(adt)) \
            .reshape(E_loc * cap_exp, d)

        # ---- gate-weighted combine per copy, then return trip ----------------
        got = out_buf.at[jnp.where(keep2, slot2, 0)].get() \
            * (a_gate * keep2)[:, None].astype(adt)
        back = jnp.zeros((R, d), adt).at[a_copy].add(got)
        back = jax.lax.all_to_all(back.reshape(n_data, cap_send, d),
                                  daxis, 0, 0, tiled=False)
        back = back.reshape(n_data * cap_send, d)
        # copies are already gate-weighted; sum each token's copies
        copy_out = back.at[jnp.where(keep, slot, 0)].get() * kf
        out = jnp.zeros((T, d), adt).at[tok].add(
            copy_out * flat_first[:, None].astype(adt))

        # out is PARTIAL over the model axis (ff sharded); shared experts
        # contribute their own ff-sharded partial — one fused psum
        if shared is not None:
            out = out + _shared_expert(shared, x_l, act, adt)
        return jax.lax.psum(out, maxis)

    shared_p = params.get("shared")
    shared_specs = None
    if shared_p is not None:
        shared_specs = {"wi_gate": P(None, maxis), "wi_up": P(None, maxis),
                        "wo": P(maxis, None)}

    out2d = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(bspec, bspec, bspec,
                  P(daxis, None, maxis), P(daxis, None, maxis),
                  P(daxis, maxis, None), shared_specs),
        out_specs=bspec,
        check_vma=False,
    )(x2d, gates, idx, params["wi_gate"], params["wi_up"], params["wo"],
      shared_p)
    return out2d.reshape(B, S, d), aux


def _shared_expert(shared, x2d, act, adt):
    h = act(x2d @ shared["wi_gate"].astype(adt)) * (x2d @ shared["wi_up"].astype(adt))
    return h @ shared["wo"].astype(adt)
