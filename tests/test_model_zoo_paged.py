"""Model-zoo paged serving validation.

Every config in ``src/repro/configs`` must ride the paged engine: paged
init + decode must SUCCEED (bit-identical to the ring-cache path) or
raise the named capability error — no silent skips.  On top of the
per-family cache layouts (latent MLA pages, private windowed rings, SSM
state slots, the hybrid shared buffer, stacked first-dense pools), the
engine's greedy streams must stay bit-identical to the solo ring-cache
reference, with prefix sharing, preemption-fold and snapshot/restore
riding along unchanged.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_SPECS, get_arch
from repro.models import transformer as tfm
from repro.runtime.steps import StepConfig, make_run_ctx
from repro.serving import (EngineConfig, PagedKVCache, Request, ServeEngine,
                           batch_trace, poisson_trace)

# float32 pools so paged-vs-ring parity is exact rounding-for-rounding
ECFG = EngineConfig(n_slots=2, page_size=4, max_len=48, decode_chunk=4,
                    cache_dtype="float32")

# one representative per newly unlocked family (dense GQA is covered by
# test_serving.py): MLA + first-dense, sliding-window, local/global,
# pure-SSM, hybrid-SSM, multi-codebook
ZOO = ["deepseek-v2-236b", "h2o-danube-3-4b", "gemma2-27b", "mamba2-370m",
       "zamba2-1.2b", "musicgen-medium"]


def _params(cfg):
    params, _ = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    return params


# --------------------------------------------------------------------------
# every config: paged init + decode, or the NAMED capability error
# --------------------------------------------------------------------------
@pytest.mark.parametrize("arch", sorted(ARCH_SPECS))
def test_every_config_pages_or_names_its_blocker(arch):
    """Paged init + a short decode run succeeds for EVERY shipped config —
    bit-identical logits to the ring cache at float32 — or raises a
    ValueError naming the specific blocking feature.  A config that can do
    neither (silent skip, unnamed crash) fails the zoo."""
    cfg = get_arch(arch).smoke
    blockers = tfm.paged_cache_blockers(cfg)
    n_slots, ps, max_blocks = 2, 4, 8
    n_pages = n_slots + n_slots * max_blocks
    if blockers:
        with pytest.raises(ValueError, match=blockers[0]):
            tfm.init_paged_cache(cfg, n_slots, n_pages, ps, max_blocks)
        return

    params = _params(cfg)
    ctx = make_run_ctx(cfg, None, StepConfig(remat="none"))
    pcache = tfm.init_paged_cache(cfg, n_slots, n_pages, ps, max_blocks,
                                  dtype="float32")
    tables = np.stack([n_slots + s * max_blocks + np.arange(max_blocks)
                       for s in range(n_slots)]).astype(np.int32)
    pcache = {**pcache, "block_tables": jnp.asarray(tables)}
    rcache = tfm.init_cache(cfg, n_slots, ps * max_blocks, dtype="float32")

    rng = np.random.default_rng(0)
    shape = (n_slots, 1) + ((cfg.n_codebooks,) if cfg.n_codebooks else ())
    step = jax.jit(lambda p, c, t: tfm.decode_step(p, c, t, cfg, ctx))
    for _ in range(3):
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, shape), jnp.int32)
        pl_, pcache = step(params, pcache, tok)
        rl_, rcache = step(params, rcache, tok)
        np.testing.assert_array_equal(np.asarray(pl_), np.asarray(rl_))


def test_capability_routers_cover_the_zoo():
    """The per-feature routers agree with the shipped configs: nothing
    blocks plain paged serving any more, while int8 pools / speculative /
    chunked prefill each name their specific blocker per family."""
    for arch in sorted(ARCH_SPECS):
        cfg = get_arch(arch).smoke
        assert tfm.paged_cache_blockers(cfg) == ()
    dsk = get_arch("deepseek-v2-236b").smoke
    assert "use_mla" in tfm.int8_paged_blockers(dsk)
    assert "use_mla" in tfm.speculative_blockers(dsk)
    assert tfm.chunked_prefill_blockers(dsk) == ()      # prefix cache rides
    ssm = get_arch("mamba2-370m").smoke
    assert "uses_ssm" in tfm.int8_paged_blockers(ssm)
    assert "uses_ssm" in tfm.chunked_prefill_blockers(ssm)
    win = get_arch("h2o-danube-3-4b").smoke
    assert "sliding_window" in tfm.int8_paged_blockers(win)
    assert tfm.int8_paged_blockers(get_arch("smollm-135m").smoke) == ()


# --------------------------------------------------------------------------
# engine greedy streams == solo ring-cache reference, per family
# --------------------------------------------------------------------------
def _ring_reference(cfg, params, req):
    """Solo ring-cache run: jitted prefill (the engine's prefill is jitted
    too — XLA fusion changes bf16 rounding vs op-by-op eager) + jitted
    per-token decode."""
    ctx = make_run_ctx(cfg, None, StepConfig(remat="none"))
    pf = jax.jit(lambda p, t: tfm.prefill(p, t, cfg, ctx,
                                          max_len=ECFG.max_len))
    logits, cache = pf(params, jnp.asarray(req.prompt)[None])
    nxt = jnp.argmax(logits[:, req.prompt_len - 1], -1).astype(jnp.int32)
    toks = [np.asarray(nxt[0]).tolist()]
    step = jax.jit(lambda p, c, t: tfm.decode_step(p, c, t, cfg, ctx))
    for _ in range(req.max_new_tokens - 1):
        lg, cache = step(params, cache, nxt[:, None])
        nxt = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
        toks.append(np.asarray(nxt[0]).tolist())
    return toks


@pytest.mark.parametrize("arch", ZOO)
def test_engine_streams_match_ring_reference(arch):
    """A mid-stream-interleaving Poisson trace through the paged engine
    emits EXACTLY each request's solo ring-cache greedy stream — latent
    MLA pages, windowed private rings, SSM state slots, the hybrid shared
    buffer and stacked first-dense pools are all invisible in the output."""
    cfg = get_arch(arch).smoke
    params = _params(cfg)
    reqs = poisson_trace(3, rate_per_step=0.3, seed=7,
                         vocab_size=cfg.vocab_size, prompt_len=(3, 13),
                         max_new_tokens=(4, 8),
                         n_codebooks=cfg.n_codebooks)
    rep = ServeEngine(cfg, ECFG, params).run(reqs)
    for r, req in zip(rep.results, reqs):
        np.testing.assert_array_equal(
            np.asarray(r.tokens), np.asarray(_ring_reference(cfg, params,
                                                             req)),
            err_msg=f"{arch} rid {r.rid}")


def test_deepseek_prefix_sharing_parity():
    """MLA latent pages ride the prefix cache: a shared-prefix trace saves
    prefill tokens while every greedy stream stays bit-identical to the
    no-sharing engine (the first-dense stacked pools share the same
    page-id space, so the CoW copy covers them too)."""
    cfg = get_arch("deepseek-v2-236b").smoke
    params = _params(cfg)
    reqs = poisson_trace(4, rate_per_step=0.3, seed=7,
                         vocab_size=cfg.vocab_size, prompt_len=(3, 9),
                         max_new_tokens=(4, 8), shared_prefix_len=11,
                         prompt_pools=2)
    ecfg = dataclasses.replace(ECFG, max_len=64)
    share = ServeEngine(cfg, dataclasses.replace(ecfg, prefix_cache=True),
                        params).run(reqs)
    plain = ServeEngine(cfg, dataclasses.replace(ecfg, prefix_cache=False,
                                                 preempt=False),
                        params).run(reqs)
    assert share.prefill_tokens_saved > 0
    for a, b in zip(share.results, plain.results):
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      np.asarray(b.tokens),
                                      err_msg=f"rid {a.rid}")


def test_deepseek_preemption_and_snapshot_restore(tmp_path):
    """Page-pressure preemption (tokens folded into the requeued prompt)
    and a mid-run crash-restore from snapshot both leave deepseek's greedy
    streams bit-identical to the ample fault-free run.

    fp32 activations: the fold recomputes the folded tokens' latent rows
    through the jitted PREFILL, and XLA's bf16 fusion of the scanned units
    rounds that path differently from the decode step that first wrote
    them (dense GQA rows don't hit this — their per-row matmuls round
    identically either way, which is why test_serving's fold parity holds
    at bf16).  fp32 removes the rounding so the fold itself is tested
    exactly."""
    from repro.runtime.chaos import FaultInjector
    cfg = dataclasses.replace(get_arch("deepseek-v2-236b").smoke,
                              dtype="float32")
    params = _params(cfg)
    reqs = batch_trace(3, seed=5, vocab_size=cfg.vocab_size, prompt_len=6,
                       max_new_tokens=10)
    ample = ServeEngine(cfg, dataclasses.replace(ECFG, prefix_cache=False,
                                                 preempt=False),
                        params).run(reqs)
    base = {r.rid: list(np.asarray(r.tokens).ravel()) for r in ample.results}

    # 2 scratch + 6 usable pages; each context needs ceil((6+10)/4) = 4
    tight = dataclasses.replace(ECFG, n_pages=2 + 6, preempt=True,
                                prefix_cache=False)
    rep = ServeEngine(cfg, tight, params).run(reqs)
    assert rep.n_preemptions > 0
    assert {r.rid: list(np.asarray(r.tokens).ravel())
            for r in rep.results} == base

    inj = FaultInjector()
    inj.schedule("engine_crash", 6)
    eng = ServeEngine(cfg, ECFG, params, injector=inj,
                      snapshot_dir=str(tmp_path), snapshot_every=2)
    from repro.serving import EngineCrash
    try:
        rep2 = eng.run(reqs)
    except EngineCrash:
        eng = ServeEngine.restore(cfg, ECFG, params, str(tmp_path),
                                  injector=inj, snapshot_every=2)
        rep2 = eng.resume()
    assert rep2.n_restores == 1
    assert {r.rid: list(np.asarray(r.tokens).ravel())
            for r in rep2.results} == base


def test_ssm_host_tier_disabled_with_warning():
    """State-slot families have no page pool behind the block tables: the
    host KV tier degrades to off with ONE RuntimeWarning instead of
    paging garbage."""
    cfg = get_arch("mamba2-370m").smoke
    params = _params(cfg)
    ecfg = dataclasses.replace(ECFG, host_tier=True)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        eng = ServeEngine(cfg, ecfg, params)
    msgs = [str(w.message) for w in rec
            if issubclass(w.category, RuntimeWarning)
            and "host KV tier disabled" in str(w.message)]
    assert len(msgs) == 1
    assert not eng.kv.tables_active


def test_speculative_blocked_by_named_feature():
    """Speculative serving on a non-GQA family raises naming the feature,
    not a generic unsupported error."""
    cfg = get_arch("deepseek-v2-236b").smoke
    params = _params(cfg)
    with pytest.raises(ValueError, match="use_mla"):
        ServeEngine(cfg, dataclasses.replace(ECFG, spec_k=2), params)


def test_windowed_paged_cache_is_o_window():
    """A windowed layer's private ring holds ceil(window/page_size) pages
    per slot — O(window), not O(max_len): the whole point of the per-layer
    page-table groups."""
    cfg = get_arch("h2o-danube-3-4b").smoke
    w = cfg.sliding_window
    assert w and w > 0
    n_slots, ps, max_len = 2, 4, 4 * w
    max_blocks = max_len // ps
    cache = tfm.init_paged_cache(cfg, n_slots,
                                 n_slots + n_slots * max_blocks, ps,
                                 max_blocks)
    nbw = -(-w // ps)
    for name, sub in cache["units"].items():
        if "k" in sub:
            assert sub["k"].shape[1] == n_slots * nbw, name
    kv = PagedKVCache(cfg, n_slots=n_slots, page_size=ps, max_len=max_len)
    assert not kv.tables_active
