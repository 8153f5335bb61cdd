"""Step builders: train_step (grad-accum microbatching + AdamW), prefill_step,
serve_step (one decode token), decode_loop (a whole multi-token block in one
lax.scan), and the speculative loops (K+1-token verify sweeps with in-scan
draft -> accept -> commit).  These are the functions the launcher jits with
in/out shardings and the dry-run lowers.

Overlap strategy: gradients are accumulated over ``n_micro`` microbatches
inside a lax.scan; the cross-replica psum XLA inserts for the DP axes then
happens ONCE on the accumulated grads (deferred-psum), and the XLA
latency-hiding scheduler can overlap the per-layer FSDP all-gathers of
microbatch i+1 with the compute of microbatch i.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.kernels.ops import KernelPolicy
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.models.layers import NO_MESH
from repro.models.transformer import RunCtx
from repro.optim import OptimizerConfig, adamw_init, adamw_update
from repro.optim.adamw import opt_state_sharding
from repro.runtime.sharding import ShardingRules, batch_sharding


@dataclasses.dataclass(frozen=True)
class StepConfig:
    """Runtime knobs orthogonal to the model config."""
    n_micro: int = 1                   # grad-accumulation microbatches
    remat: str = "dots"                # none | dots | full
    kernel_policy: KernelPolicy = KernelPolicy()
    optimizer: OptimizerConfig = OptimizerConfig()
    sequence_shard: bool = False
    moe_strategy: str = "gather"       # gather | a2a (see models.layers)


def with_decode_policy(step_cfg: StepConfig, *,
                       kv_splits: str | int | None = None,
                       decode_k_chunk: int | None = None,
                       kv_dtype: str | None = None) -> StepConfig:
    """Return ``step_cfg`` with decode-sweep knobs swapped on its
    ``KernelPolicy`` (both dataclasses are frozen, hence the replace
    dance).  ``None`` leaves a knob at its current value — callers thread
    CLI/engine config through without caring which knobs were set."""
    repl: dict[str, Any] = {}
    if kv_splits is not None:
        repl["kv_splits"] = kv_splits
    if decode_k_chunk is not None:
        repl["decode_k_chunk"] = int(decode_k_chunk)
    if kv_dtype is not None:
        repl["kv_dtype"] = str(kv_dtype)
    if not repl:
        return step_cfg
    policy = dataclasses.replace(step_cfg.kernel_policy, **repl)
    return dataclasses.replace(step_cfg, kernel_policy=policy)


def make_run_ctx(cfg: ModelConfig, rules: ShardingRules | None,
                 step_cfg: StepConfig) -> RunCtx:
    if rules is None:
        return RunCtx(parallel=NO_MESH, kernel_policy=step_cfg.kernel_policy,
                      constrain=None, remat=step_cfg.remat)
    return RunCtx(parallel=rules.parallel_ctx(),
                  kernel_policy=step_cfg.kernel_policy,
                  constrain=rules.constrain, remat=step_cfg.remat)


def init_train_state(key, cfg: ModelConfig, step_cfg: StepConfig):
    """(params, axes) + optimizer state, as one state dict."""
    params, axes = tfm.init_lm(key, cfg)
    opt = adamw_init(params, step_cfg.optimizer)
    return {"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)}, axes


def train_state_sharding(rules: ShardingRules, axes_tree,
                         state: dict[str, Any]) -> dict[str, Any]:
    """NamedShardings for ``init_train_state``'s state: params by the
    rules, optimizer moments mirroring them, counters replicated."""
    psh = rules.param_sharding(axes_tree)
    return {"params": psh,
            "opt": opt_state_sharding(psh, state["opt"], rules.mesh),
            "step": NamedSharding(rules.mesh, PartitionSpec())}


def make_train_step(cfg: ModelConfig, step_cfg: StepConfig,
                    rules: ShardingRules | None = None) -> Callable:
    """Returns step(state, batch) -> (state, metrics).

    batch = {"inputs": (B, S) [or (B,S,n_cb)], "targets": same,
             optional "image_embeds": (B, n_img, d)}.
    """
    ctx = make_run_ctx(cfg, rules, step_cfg)

    def loss_fn(params, inputs, targets, extra):
        return tfm.lm_loss_pre_shifted(params, inputs, targets, cfg, ctx,
                                       extra_embeds=extra)

    def train_step(state, batch):
        params = state["params"]
        n_micro = step_cfg.n_micro
        inputs, targets = batch["inputs"], batch["targets"]
        extra = batch.get("image_embeds")

        if n_micro == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, inputs,
                                                      targets, extra)
        else:
            B = inputs.shape[0]
            mb = B // n_micro

            def resh(x):
                return x.reshape((n_micro, mb) + x.shape[1:])

            micro_batches = (resh(inputs), resh(targets),
                             resh(extra) if extra is not None else None)

            def micro(carry, xs):
                gsum, lsum = carry
                mi, mt, me = xs
                l, g = jax.value_and_grad(loss_fn)(params, mi, mt, me)
                gsum = jax.tree.map(lambda a, b: a + b.astype(a.dtype), gsum, g)
                return (gsum, lsum + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = jax.lax.scan(
                micro, (g0, jnp.zeros((), jnp.float32)), micro_batches)
            grads = jax.tree.map(lambda g: g / n_micro, gsum)
            loss = lsum / n_micro

        new_params, new_opt, om = adamw_update(grads, state["opt"], params,
                                               step_cfg.optimizer)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = {"loss": loss, **om}
        return new_state, metrics

    return train_step


def jit_train_step(cfg: ModelConfig, step_cfg: StepConfig,
                   rules: ShardingRules | None, state: dict[str, Any],
                   axes_tree, batch0: Any, *, donate: bool = False):
    """Jit the train step and place its inputs.

    Returns ``(step, state, place_batch)``.  With ``rules`` the state is put
    on the mesh with ``train_state_sharding`` and every batch with
    ``batch_sharding``, and the step keeps those shardings on its inputs and
    outputs (nothing lands on one device to be resharded inside the step).
    ``rules=None`` is the plain one-device jit and leaves both where they
    are.  ``batch0`` only lends its structure to the batch sharding."""
    donate_argnums = (0,) if donate else ()
    if rules is None:
        step = jax.jit(make_train_step(cfg, step_cfg),
                       donate_argnums=donate_argnums)
        return step, state, lambda batch: batch
    state_sh = train_state_sharding(rules, axes_tree, state)
    bsh = batch_sharding(rules, batch0)
    step = jax.jit(make_train_step(cfg, step_cfg, rules),
                   in_shardings=(state_sh, bsh),
                   out_shardings=(state_sh, None),
                   donate_argnums=donate_argnums)
    return (step, jax.device_put(state, state_sh),
            lambda batch: jax.device_put(batch, bsh))


def make_prefill_step(cfg: ModelConfig, step_cfg: StepConfig,
                      rules: ShardingRules | None = None,
                      max_len: int = 0) -> Callable:
    """prefill(params, batch) -> (last_logits, cache)."""
    ctx = make_run_ctx(cfg, rules, step_cfg)

    def prefill_step(params, batch):
        logits, cache = tfm.prefill(params, batch["inputs"], cfg, ctx,
                                    max_len=max_len,
                                    extra_embeds=batch.get("image_embeds"))
        return logits[:, -1], cache

    return prefill_step


def make_serve_step(cfg: ModelConfig, step_cfg: StepConfig,
                    rules: ShardingRules | None = None,
                    greedy: bool = True) -> Callable:
    """serve(params, cache, tokens) -> (next_token_or_logits, cache).

    One new token per sequence against the ring-buffer cache — this is the
    graph the decode_32k / long_500k cells lower.
    """
    ctx = make_run_ctx(cfg, rules, step_cfg)

    def serve_step(params, cache, tokens):
        logits, cache = tfm.decode_step(params, cache, tokens, cfg, ctx)
        if greedy:
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return nxt, cache
        return logits, cache

    return serve_step


def _decode_loop_impl(params, cache, tokens, active, key, *, cfg, ctx,
                      n_tokens, greedy, temperature):
    """Shared fused-loop body: ``n_tokens`` decode steps (model forward,
    sampling, cache update) in ONE ``lax.scan``.  ``active`` is None for
    the ring layout; for the paged layout it gates the per-slot position
    advance (see ``make_paged_decode_loop``), and the loop also returns
    the rows its held experts computed over the steps (None without
    experts)."""
    if greedy:
        keys = None                            # no PRNG work on the hot path
    else:
        if key is None:
            key = jax.random.PRNGKey(0)
        keys = jax.random.split(key, n_tokens)

    def body(carry, key_t):
        cache, tok = carry
        if active is None:
            logits, cache = tfm.decode_step(params, cache, tok, cfg, ctx)
            rows = None
        else:
            logits, cache, rows = tfm.paged_decode_step(params, cache, tok,
                                                        cfg, ctx, active)
        last = logits[:, -1]                   # (B, V) or (B, n_cb, V)
        if greedy:
            nxt = jnp.argmax(last, axis=-1)
        else:
            nxt = jax.random.categorical(key_t, last / temperature, axis=-1)
        nxt = nxt.astype(jnp.int32)
        return (cache, nxt[:, None]), (nxt, rows)

    (cache, _), (toks, rows) = jax.lax.scan(body, (cache, tokens), keys,
                                            length=n_tokens)
    rows = None if rows is None else jnp.sum(rows)
    return jnp.moveaxis(toks, 0, 1), cache, rows   # (B, n_tokens[, n_cb])


def make_decode_loop(cfg: ModelConfig, step_cfg: StepConfig,
                     rules: ShardingRules | None = None,
                     n_tokens: int = 16, *, greedy: bool = True,
                     temperature: float = 1.0) -> Callable:
    """decode_loop(params, cache, tokens, key=None) -> (token_block, cache).

    Runs ``n_tokens`` decode steps (sampling + cache update) inside ONE
    jitted ``lax.scan`` — no host round-trip per token, which is what makes
    the serving loop dispatch-free (benchmarks/decode_throughput.py measures
    the gap vs the per-token ``make_serve_step`` host loop).  ``tokens`` is
    the (B, 1) [or (B, 1, n_cb)] token that *enters* the model first; the
    returned block (B, n_tokens[, n_cb]) holds the tokens sampled after it.
    Jit with ``donate_argnums`` on the cache so the ring buffers update in
    place across chunks.
    """
    ctx = make_run_ctx(cfg, rules, step_cfg)

    def decode_loop(params, cache, tokens, key=None):
        return _decode_loop_impl(params, cache, tokens, None, key, cfg=cfg,
                                 ctx=ctx, n_tokens=n_tokens, greedy=greedy,
                                 temperature=temperature)[:2]

    return decode_loop


def make_prefill_suffix_step(cfg: ModelConfig, step_cfg: StepConfig,
                             rules: ShardingRules | None = None) -> Callable:
    """suffix_step(params, cache, tokens, n_commit) -> (logits, cache,
    expert rows | None).

    One chunked-paged-prefill sweep (see ``transformer.prefill_suffix``):
    ``tokens`` is (n_slots, chunk) with the joining slot's row holding the
    next ``n_commit[slot]`` uncached prompt-suffix tokens (other rows are
    pad, ``n_commit == 0``).  The chunk size is whatever width the caller
    traces with — a fixed shape means ONE AOT executable covers every
    suffix length (the engine loops it and pads the tail).  Jit with
    ``donate_argnums=(1,)`` so the page pools update in place."""
    ctx = make_run_ctx(cfg, rules, step_cfg)
    blockers = tfm.chunked_prefill_blockers(cfg)
    if blockers:
        raise ValueError(f"{cfg.name}: chunked paged prefill blocked by "
                         f"{blockers[0]}")

    def suffix_step(params, cache, tokens, n_commit):
        return tfm.prefill_suffix(params, cache, tokens,
                                  jnp.asarray(n_commit, jnp.int32), cfg, ctx)

    return suffix_step


def _spec_accept_greedy(logits, drafts):
    """Greedy exact-match acceptance: per-row accepted-draft counts.

    logits: (B, Q, V) for the fed block [t_last, d_1..d_K]; row i scores
    the token AFTER position pos+i.  Draft d_{i+1} is accepted iff it
    equals argmax(row i) AND every earlier draft was accepted — the
    emitted block is then argmax rows 0..a (accepted drafts + the free
    "bonus" token), which is exactly the plain greedy stream."""
    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)        # (B, Q)
    match = (drafts == g[:, :-1]).astype(jnp.int32)          # (B, K)
    acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)        # (B,)
    return g, acc


def _spec_accept_sample(logits, drafts, acc_flags, a_vec, key, temperature):
    """Temperature rejection-sampling acceptance for point-mass (deterministic)
    drafters, per Leviathan et al.: accept d_{i+1} with probability
    p(d_{i+1}); at the first rejection resample from the residual
    max(0, p - q) (= p with the rejected draft's mass removed); when all K
    drafts survive, sample the bonus token from the last row.  Returns the
    emitted block with the correction/bonus token spliced in at ``a_vec``.

    The target distribution is preserved exactly — rejected drafts cost
    compute (charged as overhead in J/accepted-token) but never bias the
    stream."""
    B, Q, V = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32) / temperature, axis=-1)
    p_base = jnp.take_along_axis(
        probs, a_vec[:, None, None], axis=1)[:, 0]           # (B, V) row a
    d_pad = jnp.concatenate([drafts, drafts[:, -1:]], axis=1)  # (B, Q)
    d_at = jnp.take_along_axis(d_pad, a_vec[:, None], axis=1)[:, 0]
    is_bonus = a_vec == Q - 1
    # was row a_vec's draft itself accepted?  (ring lockstep can truncate a
    # row below its own acceptance count — the accepted draft IS a valid
    # sample from p and must be emitted, not resampled)
    f_pad = jnp.concatenate(
        [acc_flags, jnp.zeros((B, 1), acc_flags.dtype)], axis=1)
    accepted_here = jnp.take_along_axis(
        f_pad, a_vec[:, None], axis=1)[:, 0] > 0
    onehot = jax.nn.one_hot(d_at, V, dtype=probs.dtype)
    dist = jnp.where(is_bonus[:, None], p_base, p_base * (1.0 - onehot))
    samp = jax.random.categorical(key, jnp.log(dist + 1e-30), axis=-1)
    last_tok = jnp.where(is_bonus | ~accepted_here, samp,
                         d_at).astype(jnp.int32)
    emit = jnp.where(jnp.arange(Q)[None, :] == a_vec[:, None],
                     last_tok[:, None], d_pad)
    return emit


def _spec_loop_impl(params, cache, tokens, active, dstate, key, *, cfg, ctx,
                    drafter, n_steps, greedy, temperature, per_slot):
    """Shared speculative-loop body: ``n_steps`` x (draft -> verify ->
    accept -> commit) entirely inside ONE ``lax.scan``.  ``per_slot`` keeps
    per-row accepted counts (paged layout: every slot sits at its own
    depth); the ring layout's scalar ``pos`` forces the batch to advance in
    lockstep, so acceptance truncates to the batch minimum — still exact,
    just conservative (B=1 serving pays nothing)."""
    K = drafter.spec_k
    Q = K + 1
    if key is None:
        key = jax.random.PRNGKey(0)
    keys = jax.random.split(key, n_steps)    # cheap; unused rows DCE'd when greedy

    def body(carry, key_t):
        cache, tok, dstate = carry
        drafts = drafter.propose(dstate, tok[:, 0])          # (B, K)
        block = jnp.concatenate([tok, drafts], axis=1)       # (B, Q)
        logits, pending = tfm.verify_step(params, cache, block, cfg, ctx)
        if greedy:
            g, acc = _spec_accept_greedy(logits, drafts)
        else:
            probs = jax.nn.softmax(
                logits.astype(jnp.float32) / temperature, axis=-1)
            p_draft = jnp.take_along_axis(
                probs[:, :-1], drafts[..., None], axis=-1)[..., 0]  # (B, K)
            k_acc, k_emit = jax.random.split(key_t)
            u = jax.random.uniform(k_acc, p_draft.shape)
            ok = (u < p_draft).astype(jnp.int32)
            acc = jnp.sum(jnp.cumprod(ok, axis=1), axis=1)   # (B,)
        if per_slot:
            a_vec = acc
        else:
            a_vec = jnp.broadcast_to(jnp.min(acc), acc.shape)
        if greedy:
            emit = g
        else:
            emit = _spec_accept_sample(logits, drafts, ok, a_vec, k_emit,
                                       temperature)
        counts = a_vec + 1
        if per_slot:
            counts = jnp.where(active > 0, counts, 0)
            cache = tfm.commit_spec_paged(cache, pending, a_vec, active, cfg)
        else:
            cache = tfm.commit_spec(cache, pending, a_vec[0], cfg)
        dstate = drafter.observe(dstate, emit, counts)
        tok_next = jnp.take_along_axis(emit, a_vec[:, None], axis=1)
        return (cache, tok_next, dstate), (emit, counts)

    (cache, _, dstate), (toks, counts) = jax.lax.scan(
        body, (cache, tokens, dstate), keys, length=n_steps)
    # (n_steps, B, Q) -> (B, n_steps, Q); counts (n_steps, B) -> (B, n_steps)
    return jnp.moveaxis(toks, 0, 1), jnp.moveaxis(counts, 0, 1), cache, dstate


def make_speculative_decode_loop(cfg: ModelConfig, step_cfg: StepConfig,
                                 rules: ShardingRules | None = None,
                                 n_steps: int = 8, *, drafter,
                                 greedy: bool = True,
                                 temperature: float = 1.0) -> Callable:
    """spec_loop(params, cache, tokens, drafter_state, key=None)
    -> (token_blocks (B, n_steps, K+1), counts (B, n_steps), cache, state).

    The fused decode loop's speculative sibling over the ring cache:
    ``n_steps`` verify steps, each scoring K+1 tokens in ONE cache sweep
    (draft -> verify -> accept -> commit, all in-scan, zero host traffic).
    ``counts[:, s]`` is step s's emitted-token count (accepted drafts + 1);
    only the first ``counts`` entries of each block are real — greedy
    emission is bit-identical to ``make_decode_loop``'s stream, just
    delivered up to K+1 tokens per sweep.  The ring's scalar ``pos``
    advances the batch in lockstep (acceptance truncates to the batch
    minimum); the paged variant keeps per-slot counts.  Jit with
    ``donate_argnums`` on the cache, as with the plain loop."""
    ctx = make_run_ctx(cfg, rules, step_cfg)
    blockers = tfm.speculative_blockers(cfg)
    if blockers:
        raise ValueError(f"{cfg.name}: speculative decode blocked by "
                         f"{blockers[0]}")

    def spec_loop(params, cache, tokens, drafter_state, key=None):
        return _spec_loop_impl(params, cache, tokens, None, drafter_state,
                               key, cfg=cfg, ctx=ctx, drafter=drafter,
                               n_steps=n_steps, greedy=greedy,
                               temperature=temperature, per_slot=False)

    return spec_loop


def make_paged_speculative_decode_loop(cfg: ModelConfig, step_cfg: StepConfig,
                                       rules: ShardingRules | None = None,
                                       n_steps: int = 8, *, drafter,
                                       greedy: bool = True,
                                       temperature: float = 1.0) -> Callable:
    """spec_loop(params, cache, tokens, active, drafter_state, key=None)
    -> (token_blocks (B, n_steps, K+1), counts (B, n_steps), cache, state)
    over the *paged* cache layout — the serving engine's speculative inner
    loop.  ``pos`` is per-slot, so every slot keeps its own accepted
    prefix: the engine's harvest consumes a variable number of tokens per
    slot per step.  Parked slots verify scratch garbage (fixed grid, one
    executable) but neither commit nor advance, and their counts are 0."""
    ctx = make_run_ctx(cfg, rules, step_cfg)
    blockers = (tfm.speculative_blockers(cfg)
                or tfm.chunked_prefill_blockers(cfg))
    if blockers:
        raise ValueError(f"{cfg.name}: paged speculative decode blocked by "
                         f"{blockers[0]}")

    def spec_loop(params, cache, tokens, active, drafter_state, key=None):
        return _spec_loop_impl(params, cache, tokens,
                               jnp.asarray(active, jnp.int32), drafter_state,
                               key, cfg=cfg, ctx=ctx, drafter=drafter,
                               n_steps=n_steps, greedy=greedy,
                               temperature=temperature, per_slot=True)

    return spec_loop


def make_paged_decode_loop(cfg: ModelConfig, step_cfg: StepConfig,
                           rules: ShardingRules | None = None,
                           n_tokens: int = 16, *, greedy: bool = True,
                           temperature: float = 1.0) -> Callable:
    """decode_loop(params, cache, tokens, active, key=None)
    -> (token_block, cache, expert rows | None) over the *paged* cache
    layout; the rows are those the held experts computed over the chunk,
    parked slots included.

    The continuous-batching engine's inner loop: ``cache`` comes from
    ``transformer.init_paged_cache`` (per-slot positions + block tables +
    shared page pools) and ``active`` (B,) marks which slots hold a live
    request.  Every slot decodes every step — the grid is fixed so ONE
    executable serves all occupancy patterns — but only active slots
    advance their position; parked slots spin on their scratch page and
    their tokens are discarded by the engine at harvest.  Jit with
    ``donate_argnums`` on the cache so the pools update in place."""
    ctx = make_run_ctx(cfg, rules, step_cfg)

    def decode_loop(params, cache, tokens, active, key=None):
        return _decode_loop_impl(params, cache, tokens,
                                 jnp.asarray(active, jnp.int32), key,
                                 cfg=cfg, ctx=ctx, n_tokens=n_tokens,
                                 greedy=greedy, temperature=temperature)

    return decode_loop
