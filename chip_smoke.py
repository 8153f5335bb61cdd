"""Smoke test of the main path on a TPU, at published widths.

    python chip_smoke.py               # one chip: serving + kernel checks
    python chip_smoke.py --four-chips  # four chips: sharded train step only

One chip (``jax.devices()[0]``, even on a host that shows more):

  1. serve ~16 requests of SmolLM-135M (published config, seeded weights)
     through ``ServeEngine`` as ``repro.launch.serve`` does: 16 slots,
     page size 16, max_len 2048, decode chunk 8, prefix cache and chunked
     prefill on, bf16 KV, decode backend ``auto``; every request must
     finish with its token count and the compiled decode loop must hold a
     Pallas kernel (``tpu_custom_call``);
  2. one paged decode step's logits with ``decode="pallas"`` against
     ``decode="ref"``;
  3. the split-KV kernels against their ``kernels/ref.py`` oracles: paged
     decode and paged verify with two splits at SmolLM's shapes, and MLA
     paged decode at DeepSeek-V2's latent widths at batch 1 under ``auto``.

Four chips: a few AdamW steps of SmolLM-135M on a (2, 2) ("data", "model")
mesh against the same steps unsharded on one chip.

Every ``FallbackWarning`` (a requested path running as another) is an
error.  Without a TPU the script exits non-zero before printing a result.
The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The numbers printed before it are smoke output, not benchmark figures.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import warnings

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

SEED = 0
ARCH = "smollm-135m"
PALLAS = "pallas"          # the decode backend the logits check asks for

# Tolerances.  The logits and train limits sit between a sound reading and
# a planted fault's, both read on a TPU v5e at these sizes.  Logits: sound
# 0.0272; a kernel that reads page j+1 for page j (one page of 77 in one
# slot) 0.525.  Train: sound dloss 1.24e-5; a step that updates nothing
# 0.0173 in loss and 1.0 in update error; a step on half the batch 0.0154
# in loss and 0.955 in update error.
LOGITS_RTOL = 1e-1         # pallas vs ref logits, relative to max |ref logit|
KERNEL_ATOL = 5e-2         # kernel outputs (bf16) vs fp32 oracles, max |diff|
PARTIAL_ATOL = 2e-2        # stage-1 partials / lse (fp32) vs split oracle
TRAIN_LOSS_ATOL = 1e-3     # sharded vs unsharded loss, any step
TRAIN_DELTA_RTOL = 0.3     # per leaf |d_sharded - d_ref| / |d_ref|, d = p - p0


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------
def smoke_requests(vocab: int, *, unique_lens, shared_head: int,
                   shared_suffix_lens, new_tokens: int, seed: int):
    """Alternating unique and shared-head prompts, one arrival per step."""
    import numpy as np
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    head = rng.integers(0, vocab, shared_head, dtype=np.int32)
    prompts = []
    for u, s in zip(unique_lens, shared_suffix_lens):
        prompts.append(rng.integers(0, vocab, u, dtype=np.int32))
        prompts.append(np.concatenate(
            [head, rng.integers(0, vocab, s, dtype=np.int32)]))
    return [Request(rid=i, prompt=p, max_new_tokens=new_tokens,
                    arrival_step=i) for i, p in enumerate(prompts)]


def serve_phase(cfg, params, *, n_slots: int, max_len: int, requests):
    """Serve ``requests`` through the paged engine; check every request."""
    from repro.runtime.steps import StepConfig
    from repro.serving import EngineConfig, ServeEngine
    ecfg = EngineConfig(n_slots=n_slots, page_size=16, max_len=max_len,
                        decode_chunk=8, prefix_cache=True,
                        kv_dtype="bfloat16", kv_splits="auto")
    engine = ServeEngine(cfg, ecfg, params, step_cfg=StepConfig(remat="none"))
    t0 = time.perf_counter()
    rep = engine.run(requests)
    wall = time.perf_counter() - t0
    by_rid = {r.rid: r for r in rep.results}
    for req in requests:
        res = by_rid[req.rid]
        check(res.n_tokens == req.max_new_tokens
              and res.finish_reason == "max_new_tokens",
              f"request {req.rid}: {res.n_tokens}/{req.max_new_tokens} "
              f"tokens, finish {res.finish_reason!r}")
        check(all(0 <= t < cfg.vocab_size for t in res.tokens),
              f"request {req.rid}: token out of range")
    check(rep.prefill_tokens_saved > 0, "the prefix cache restored nothing")
    loop = engine._loops[(ecfg.decode_chunk, False)]
    mem = loop.memory_analysis()
    return {"requests": len(rep.results), "tokens": rep.tokens_kept,
            "prompt_tokens": rep.prompt_tokens,
            "prefix_tokens_saved": rep.prefill_tokens_saved,
            "chunks": rep.n_chunks, "wall_s": wall,
            "decode_loop_has_pallas": "tpu_custom_call" in loop.as_text(),
            "decode_loop_arg_bytes": mem.argument_size_in_bytes,
            "decode_loop_temp_bytes": mem.temp_size_in_bytes}


def random_paged_cache(cfg, key, *, n_slots: int, max_len: int,
                       page_size: int = 16):
    """A paged cache whose pools hold seeded random rows, behind shuffled
    block tables, with slots at assorted depths."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tfm
    nb = max_len // page_size
    n_pages = n_slots * nb
    cache = tfm.init_paged_cache(cfg, n_slots, n_pages, page_size, nb)
    leaves, tree = jax.tree.flatten(cache["units"])
    keys = jax.random.split(key, len(leaves) + 2)
    leaves = [jax.random.normal(k, x.shape, jnp.float32).astype(x.dtype)
              for k, x in zip(keys, leaves)]
    cache["units"] = jax.tree.unflatten(tree, leaves)
    perm = jax.random.permutation(keys[-2], n_pages).astype(jnp.int32)
    cache["block_tables"] = perm.reshape(n_slots, nb)
    cache["pos"] = jax.random.randint(keys[-1], (n_slots,), 1, max_len - 1)
    return cache


def logits_phase(cfg, params, *, n_slots: int, max_len: int, seed: int):
    """One paged decode step: ``decode=PALLAS`` logits vs ``decode="ref"``
    (fp32 oracle at highest matmul precision)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ops import KernelPolicy
    from repro.models import transformer as tfm
    from repro.runtime.steps import StepConfig, make_run_ctx
    key = jax.random.PRNGKey(seed)
    cache = random_paged_cache(cfg, key, n_slots=n_slots, max_len=max_len)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (n_slots, 1), 0,
                                cfg.vocab_size)
    active = jnp.ones((n_slots,), jnp.int32)

    def step_for(backend):
        ctx = make_run_ctx(cfg, None, StepConfig(
            remat="none", kernel_policy=KernelPolicy(decode=backend)))
        return jax.jit(lambda p, c, t: tfm.decode_step(
            p, c, t, cfg, ctx, active=active)[0][:, -1])

    # both sides at one matmul precision, so what differs is the attention
    # backend and not the projections around it
    with jax.default_matmul_precision("highest"):
        fast = step_for(PALLAS).lower(params, cache, tokens).compile()
        want = np.asarray(step_for("ref")(params, cache, tokens), np.float32)
    got = np.asarray(fast(params, cache, tokens), np.float32)
    check(np.isfinite(got).all(), "pallas logits not finite")
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    check(err <= LOGITS_RTOL, f"pallas vs ref logits: rel err {err:.3g} > "
          f"{LOGITS_RTOL}")
    return {"logits_rel_err": err,
            "logits_argmax_agree": float(np.mean(
                got.argmax(-1) == want.argmax(-1))),
            "logits_step_has_pallas": "tpu_custom_call" in fast.as_text()}


def _max_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    check(np.isfinite(got).all(), "kernel output not finite")
    return float(np.max(np.abs(got - np.asarray(want, np.float32))))


def gqa_kernel_phase(*, batch: int, q_heads: int, kv_heads: int,
                     head_dim: int, max_len: int, n_splits: int,
                     n_verify: int = 4, page_size: int = 16, seed: int):
    """Paged decode (stage 1 and merged) and paged verify with ``n_splits``
    against the fp32 oracles."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import decode_attention as da
    from repro.kernels import ops, ref
    nb = max_len // page_size
    n_pages = batch * nb + 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (batch, 1, q_heads, head_dim), bf)
    qv = jax.random.normal(ks[1], (batch, n_verify, q_heads, head_dim), bf)
    kp = jax.random.normal(ks[2], (n_pages, page_size, kv_heads, head_dim), bf)
    vp = jax.random.normal(ks[3], (n_pages, page_size, kv_heads, head_dim), bf)
    kn = jax.random.normal(ks[4], (batch, n_verify, kv_heads, head_dim), bf)
    vn = jax.random.normal(ks[5], (batch, n_verify, kv_heads, head_dim), bf)
    bt = jax.random.permutation(ks[6], n_pages)[:batch * nb] \
        .astype(jnp.int32).reshape(batch, nb)
    pos = jax.random.randint(ks[7], (batch,), 1, max_len - n_verify)
    policy = ops.KernelPolicy(decode=PALLAS, kv_splits=n_splits)
    interpret = PALLAS == "pallas_interpret"
    part, lse = jax.jit(lambda *a: da.paged_decode_attention_pallas_partials(
        *a, n_splits=n_splits, interpret=interpret))(q, kp, vp, bt, pos)
    dec = jax.jit(lambda *a: ops.paged_decode_attention(*a, policy=policy))(
        q, kp, vp, bt, pos)
    ver = jax.jit(lambda *a: ops.paged_verify_attention(*a, policy=policy))(
        qv, kp, vp, kn, vn, bt, pos)
    with jax.default_matmul_precision("highest"):
        part_w, lse_w = ref.paged_decode_attention_split_ref(
            q, kp, vp, bt, pos, n_splits=n_splits,
            block_pages=da.pages_per_block(page_size, kv_heads, head_dim,
                                           kp.dtype.itemsize, nb))
        dec_w = ref.paged_decode_attention_ref(q, kp, vp, bt, pos)
        ver_w = ref.paged_verify_attention_ref(qv, kp, vp, kn, vn, bt, pos)
    errs = {"decode_partial_err": _max_err(part, part_w),
            "decode_lse_err": _max_err(lse, lse_w),
            "decode_err": _max_err(dec, dec_w),
            "verify_err": _max_err(ver, ver_w)}
    for name, e in errs.items():
        tol = KERNEL_ATOL if name in ("decode_err", "verify_err") \
            else PARTIAL_ATOL
        check(e <= tol, f"paged {name} {e:.3g} > {tol} (splits {n_splits})")
    return errs


def mla_kernel_phase(*, q_heads: int, rank: int, r_kv: int, max_len: int,
                     scale: float, page_size: int = 16, seed: int):
    """MLA paged decode at batch 1 under ``auto`` against the fp32 oracle;
    the split count ``auto`` picks is reported and must exceed one."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    nb = max_len // page_size
    n_pages = nb + 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (1, 1, q_heads, rank), bf)
    lat = jax.random.normal(ks[1], (n_pages, page_size, rank), bf)
    bt = jax.random.permutation(ks[2], n_pages)[:nb].astype(jnp.int32)[None]
    pos = jnp.asarray([max_len - 7], jnp.int32)
    splits = ops.choose_kv_splits(1, nb * page_size, 1, block=page_size)
    check(splits > 1, f"auto chose {splits} split(s) for MLA at batch 1")
    got = jax.jit(lambda *a: ops.mla_decode_paged(
        *a, r_kv=r_kv, scale=scale))(q, lat, bt, pos)
    with jax.default_matmul_precision("highest"):
        want = ref.mla_decode_paged_ref(q, lat, bt, pos, r_kv=r_kv,
                                        scale=scale)
    err = _max_err(got, want)
    check(err <= KERNEL_ATOL, f"MLA paged decode err {err:.3g} > "
          f"{KERNEL_ATOL} (splits {splits})")
    return {"mla_splits": splits, "mla_err": err}


def one_chip() -> dict:
    import jax
    from repro.configs import get_arch
    from repro.models import transformer as tfm
    dev = jax.devices()[0]
    out = {}
    with jax.default_device(dev):
        cfg = get_arch(ARCH).config
        params, _ = tfm.init_lm(jax.random.PRNGKey(SEED), cfg)
        log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
            f"{cfg.n_heads}/{cfg.n_kv_heads} heads, vocab {cfg.vocab_size}, "
            f"{cfg.param_count() / 1e6:.1f}M params")
        reqs = smoke_requests(
            cfg.vocab_size, unique_lens=(64, 128, 200, 333, 512, 640, 800,
                                         1024),
            shared_head=256,
            shared_suffix_lens=(64, 96, 160, 256, 384, 512, 640, 768),
            new_tokens=32, seed=SEED)
        out.update(serve_phase(cfg, params, n_slots=16, max_len=2048,
                               requests=reqs))
        check(out["decode_loop_has_pallas"],
              "compiled decode loop has no tpu_custom_call")
        log(f"served {out['requests']} requests, {out['tokens']} tokens "
            f"({out['prompt_tokens']} prompt tokens, "
            f"{out['prefix_tokens_saved']} restored from the prefix cache) "
            f"in {out['chunks']} chunks, {out['wall_s']:.2f} s wall "
            f"(compiles included); decode loop: "
            f"{out['decode_loop_arg_bytes']} argument bytes, "
            f"{out['decode_loop_temp_bytes']} temporary bytes")
        out.update(logits_phase(cfg, params, n_slots=4, max_len=2048,
                                seed=SEED + 1))
        check(out["logits_step_has_pallas"],
              "pallas decode step has no tpu_custom_call")
        log(f"paged decode step, pallas vs ref logits: rel err "
            f"{out['logits_rel_err']:.3g} (tol {LOGITS_RTOL}), argmax "
            f"agreement {out['logits_argmax_agree']:.3f}")
        out.update(gqa_kernel_phase(
            batch=16, q_heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, max_len=2048, n_splits=2,
            seed=SEED + 2))
        log("paged decode/verify, 2 splits: partial err "
            f"{out['decode_partial_err']:.3g}, lse err "
            f"{out['decode_lse_err']:.3g}, decode err "
            f"{out['decode_err']:.3g}, verify err {out['verify_err']:.3g}")
        ds = get_arch("deepseek-v2-236b").config
        out.update(mla_kernel_phase(
            q_heads=ds.n_heads, rank=ds.kv_lora_rank + ds.rope_head_dim,
            r_kv=ds.kv_lora_rank, max_len=4096,
            scale=(ds.nope_head_dim + ds.rope_head_dim) ** -0.5,
            seed=SEED + 3))
        log(f"MLA paged decode (Hq {ds.n_heads}, R "
            f"{ds.kv_lora_rank + ds.rope_head_dim}, r_kv {ds.kv_lora_rank}, "
            f"B 1, auto -> {out['mla_splits']} splits): err "
            f"{out['mla_err']:.3g}")
    return out


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------
def train_setup(cfg, device, *, batch: int, seq: int, steps: int,
                seed: int):
    """Seeded AdamW state on ``device`` and ``steps`` seeded batches;
    returns (step_cfg, state0, axes, batches)."""
    import jax
    from repro.optim import OptimizerConfig
    from repro.runtime.steps import StepConfig, init_train_state
    step_cfg = StepConfig(remat="none", optimizer=OptimizerConfig(
        learning_rate=1e-3, warmup_steps=1, total_steps=10 * steps))
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (steps, batch, seq + 1), 0, cfg.vocab_size)
    batches = [{"inputs": t[:, :-1], "targets": t[:, 1:]} for t in toks]
    state0, axes = init_train_state(jax.random.PRNGKey(seed), cfg, step_cfg)
    return step_cfg, jax.device_put(state0, device), axes, batches


def train_run(cfg, step_cfg, rules, state0, axes, batches):
    """Run ``batches`` through ``jit_train_step`` (the launcher's own
    wiring); returns (losses, params)."""
    from repro.runtime.steps import jit_train_step
    step, state, place = jit_train_step(cfg, step_cfg, rules, state0, axes,
                                        batches[0])
    losses = []
    for b in batches:
        state, m = step(state, place(b))
        losses.append(float(m["loss"]))
    return losses, state["params"]


def train_readings(params0, ref, other) -> dict:
    """How far the run ``other`` = (losses, params) is from ``ref``: the
    largest loss difference over the steps, and per parameter leaf, with
    ``delta = params - params0``, the largest ``|delta_other -
    delta_ref| / |delta_ref|`` and the smallest ``|delta_other| /
    |delta_ref|`` (Frobenius norms)."""
    import jax
    import numpy as np
    dloss = max(abs(a - b) for a, b in zip(ref[0], other[0]))
    rel, moved = 0.0, np.inf
    for p0, a, b in zip(*(jax.tree.leaves(t) for t in
                          (params0, ref[1], other[1]))):
        p0 = np.asarray(p0, np.float32)
        d_ref = np.asarray(a, np.float32) - p0
        d_other = np.asarray(b, np.float32) - p0
        n_ref = float(np.linalg.norm(d_ref))
        check(n_ref > 0, "a parameter did not move in the reference run")
        rel = max(rel, float(np.linalg.norm(d_other - d_ref)) / n_ref)
        moved = min(moved, float(np.linalg.norm(d_other)) / n_ref)
    return {"dloss": dloss, "delta_rel_err": rel, "delta_moved": moved}


def train_phase(cfg, devices, *, batch: int, seq: int, steps: int, seed: int):
    """``steps`` AdamW steps on a (n/2, 2) ("data", "model") mesh over
    ``devices`` against the same steps unsharded on ``devices[0]``."""
    import jax
    import numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.sharding import build_rules
    step_cfg, state0, axes, batches = train_setup(
        cfg, devices[0], batch=batch, seq=seq, steps=steps, seed=seed)
    ref = train_run(cfg, step_cfg, None, state0, axes, batches)
    mesh = make_host_mesh(devices, model=2)
    sharded = train_run(cfg, step_cfg, build_rules(cfg, mesh), state0, axes,
                        batches)

    want = set(devices)
    leaves = jax.tree.leaves(sharded[1])
    check(all(x.sharding.device_set == want for x in leaves),
          "a sharded parameter is not spread over every mesh device")
    n_split = sum(x.addressable_shards[0].data.shape != x.shape
                  for x in leaves)
    check(n_split > 0, "no parameter is partitioned across the mesh")
    check(all(np.isfinite(ref[0] + sharded[0])), "non-finite loss")
    r = train_readings(state0["params"], ref, sharded)
    check(r["delta_moved"] >= 1 - TRAIN_DELTA_RTOL,
          f"a sharded parameter barely moved ({r['delta_moved']:.3g} of "
          "the reference update)")
    check(r["delta_rel_err"] <= TRAIN_DELTA_RTOL,
          f"parameter update mismatch {r['delta_rel_err']:.3g}")
    check(r["dloss"] <= TRAIN_LOSS_ATOL, f"loss mismatch {r['dloss']:.3g}")
    return {"mesh": dict(mesh.shape), "losses_unsharded": ref[0],
            "losses_sharded": sharded[0], **r,
            "params_partitioned": n_split, "params": len(leaves)}


def four_chips() -> dict:
    import jax
    from repro.configs import get_arch
    devices = jax.devices()[:4]
    check(len(devices) == 4, f"--four-chips needs 4 devices, found "
          f"{len(jax.devices())}")
    cfg = get_arch(ARCH).config
    out = train_phase(cfg, devices, batch=8, seq=256, steps=3, seed=SEED)
    log(f"{cfg.name} AdamW x{len(out['losses_sharded'])} on mesh "
        f"{out['mesh']}: losses unsharded {out['losses_unsharded']}, sharded "
        f"{out['losses_sharded']}; max |dloss| {out['dloss']:.3g} (tol "
        f"{TRAIN_LOSS_ATOL}), update rel err {out['delta_rel_err']:.3g} "
        f"(tol {TRAIN_DELTA_RTOL}), moved {out['delta_moved']:.3g}; "
        f"{out['params_partitioned']}/"
        f"{out['params']} parameters partitioned over 4 devices")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on four chips")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    import jax
    from repro.kernels.ops import FallbackWarning
    warnings.simplefilter("error", FallbackWarning)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 1
    meter = CompileMeter()
    log(f"device {dev.device_kind} ({len(jax.devices())} visible), jax "
        f"{jax.__version__}, compile cache {cache_dir}")
    t0 = time.perf_counter()
    four_chips() if args.four_chips else one_chip()
    wall = time.perf_counter() - t0
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", -1)
    log(f"compile {meter.seconds:.1f} s ({meter.cache_hits} persistent-cache "
        f"hits), wall {wall:.1f} s, peak bytes in use on device 0: {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
