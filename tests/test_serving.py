"""Continuous-batching engine validation: mid-stream join/finish parity
against solo runs, paged-loop occupancy isolation, page-manager invariants,
admission control, and sampling determinism of the fused decode loops."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import transformer as tfm
from repro.runtime.steps import StepConfig, make_decode_loop
from repro.serving import (EnergyAwareAdmission, EngineConfig, PagedKVCache,
                           Request, ServeEngine, batch_trace, poisson_trace)


@pytest.fixture(scope="module")
def smollm():
    cfg = get_arch("smollm-135m").smoke
    params, _ = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


ECFG = EngineConfig(n_slots=2, page_size=4, max_len=48, decode_chunk=4)


def test_engine_join_finish_parity(smollm):
    """Requests joining and finishing mid-decode produce EXACTLY the tokens
    of running each request alone: slot masking, page isolation, and the
    prefill-on-join bucket make other slots' traffic invisible."""
    cfg, params = smollm
    reqs = poisson_trace(5, rate_per_step=0.3, seed=7,
                         vocab_size=cfg.vocab_size, prompt_len=(3, 13),
                         max_new_tokens=(4, 10))
    rep = ServeEngine(cfg, ECFG, params).run(reqs)
    # the trace actually interleaves: some request waited for a slot
    assert any(r.wait_steps > 0 for r in rep.results)
    assert all(r.n_tokens == r.max_new_tokens for r in rep.results)
    for r, req in zip(rep.results, reqs):
        solo = ServeEngine(cfg, ECFG, params).run(
            [dataclasses.replace(req, arrival_step=0)])
        np.testing.assert_array_equal(
            np.asarray(r.tokens), np.asarray(solo.results[0].tokens),
            err_msg=f"rid {r.rid}")


def test_engine_eos_frees_slot(smollm):
    """EOS mid-chunk truncates the request, frees its slot/pages, and the
    next queued request takes the slot."""
    cfg, params = smollm
    base = batch_trace(3, seed=5, vocab_size=cfg.vocab_size, prompt_len=6,
                       max_new_tokens=12)
    probe = ServeEngine(cfg, ECFG, params).run([base[0]])
    tokens = probe.results[0].tokens
    # pick an "EOS" whose FIRST occurrence is mid-stream (greedy smoke
    # models repeat tokens, so scan rather than index blindly)
    k = next(i for i in range(1, len(tokens)) if tokens[i] not in tokens[:i])
    eos = tokens[k]
    reqs = [dataclasses.replace(base[0], eos_id=eos)] + base[1:]
    rep = ServeEngine(cfg, ECFG, params).run(reqs)
    r0 = rep.results[0]
    assert r0.finish_reason == "eos"
    assert r0.n_tokens == k + 1 and r0.tokens[-1] == eos
    assert all(r.n_tokens == r.max_new_tokens for r in rep.results[1:])


def test_engine_report_accounting(smollm):
    """Occupancy, kept-vs-computed tokens, and occupied-slots-only energy
    attribution add up."""
    cfg, params = smollm
    reqs = poisson_trace(4, rate_per_step=0.15, seed=2,
                         vocab_size=cfg.vocab_size, prompt_len=(4, 10),
                         max_new_tokens=(3, 8))
    energy_per_chunk = 2.5
    rep = ServeEngine(cfg, ECFG, params,
                      on_chunk=lambda s: energy_per_chunk).run(reqs)
    assert rep.tokens_computed >= rep.tokens_kept > 0
    assert 0.0 < rep.occupancy <= 1.0
    assert rep.energy_j == pytest.approx(energy_per_chunk * rep.n_chunks)
    # every chunk's joules land on the requests that kept its tokens
    assert sum(r.energy_j for r in rep.results) == pytest.approx(rep.energy_j)
    # kept tokens = everything the results hold minus the prefill-sampled one
    assert sum(r.n_tokens - 1 for r in rep.results) == rep.tokens_kept


@pytest.mark.parametrize("drafter,spec_k", [("ngram", 2), ("ngram", 4),
                                            ("repeat", 2)])
def test_engine_speculative_token_parity(smollm, drafter, spec_k):
    """Speculative engine mode emits EXACTLY the plain engine's per-request
    token streams (greedy): per-slot accept counts, masked paged commits,
    drafter-state mirrors, and variable-token harvest change only how fast
    tokens arrive, never which tokens."""
    cfg, params = smollm
    reqs = poisson_trace(5, rate_per_step=0.3, seed=7,
                         vocab_size=cfg.vocab_size, prompt_len=(3, 13),
                         max_new_tokens=(4, 10))
    plain = ServeEngine(cfg, ECFG, params).run(reqs)
    ecfg = dataclasses.replace(ECFG, spec_k=spec_k, drafter=drafter)
    rep = ServeEngine(cfg, ecfg, params).run(reqs)
    for r, rp in zip(rep.results, plain.results):
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      np.asarray(rp.tokens),
                                      err_msg=f"rid {r.rid}")
    # fewer sweeps for the same tokens is the whole point
    assert rep.n_chunks <= plain.n_chunks
    assert rep.spec_k == spec_k
    assert rep.drafts_proposed > 0
    assert 0.0 <= rep.acceptance_rate <= 1.0
    # kept/slot-sweep: can dip below 1.0 when the device overruns finished
    # requests, never above K+1
    assert 0.0 < rep.tokens_per_step <= spec_k + 1
    assert rep.j_per_accepted_token == rep.j_per_token


def test_engine_speculative_eos_and_energy(smollm):
    """EOS truncation and occupied-slots-only energy attribution survive
    variable tokens-per-slot-per-step harvesting."""
    cfg, params = smollm
    base = batch_trace(3, seed=5, vocab_size=cfg.vocab_size, prompt_len=6,
                       max_new_tokens=12)
    probe = ServeEngine(cfg, ECFG, params).run([base[0]])
    tokens = probe.results[0].tokens
    k = next(i for i in range(1, len(tokens)) if tokens[i] not in tokens[:i])
    eos = tokens[k]
    reqs = [dataclasses.replace(base[0], eos_id=eos)] + base[1:]
    ecfg = dataclasses.replace(ECFG, spec_k=2)
    rep = ServeEngine(cfg, ecfg, params,
                      on_chunk=lambda s: 2.5).run(reqs)
    r0 = rep.results[0]
    assert r0.finish_reason == "eos"
    assert r0.n_tokens == k + 1 and r0.tokens[-1] == eos
    assert all(r.n_tokens == r.max_new_tokens for r in rep.results[1:])
    assert rep.energy_j == pytest.approx(2.5 * rep.n_chunks)
    assert sum(r.energy_j for r in rep.results) == pytest.approx(rep.energy_j)


def test_engine_report_zero_guards(smollm):
    """Empty runs (no requests / no kept tokens) keep every report figure
    finite — 0.0, not NaN/inf leaking into benchmark CSVs."""
    from repro.serving import EngineReport
    cfg, params = smollm
    rep = ServeEngine(cfg, ECFG, params).run([])
    assert rep.tok_per_s == 0.0
    assert rep.j_per_token == 0.0
    assert rep.acceptance_rate == 0.0
    assert rep.tokens_per_step == 0.0
    assert rep.latency_percentiles((50, 95)) == {50: 0.0, 95: 0.0}
    assert rep.occupancy == 0.0
    blank = EngineReport(results=[])
    for v in (blank.tok_per_s, blank.j_per_token, blank.j_per_accepted_token,
              blank.acceptance_rate, blank.tokens_per_step,
              *blank.latency_percentiles().values()):
        assert v == 0.0 and np.isfinite(v)


def test_prefix_sharing_token_parity(smollm):
    """Prefix sharing is invisible in the output: on a shared-prefix trace
    the sharing engine emits EXACTLY the no-sharing engine's per-request
    greedy streams while prefilling far fewer prompt tokens.  The 11-token
    shared head over page_size 4 ends mid-page, so the copy-on-write path
    (partial shared page duplicated into a private page) is exercised."""
    cfg, params = smollm
    reqs = poisson_trace(6, rate_per_step=0.3, seed=7,
                         vocab_size=cfg.vocab_size, prompt_len=(3, 9),
                         max_new_tokens=(4, 10), shared_prefix_len=11,
                         prompt_pools=2)
    ecfg = dataclasses.replace(ECFG, max_len=64)
    share = ServeEngine(cfg, dataclasses.replace(ecfg, prefix_cache=True),
                        params).run(reqs)
    plain = ServeEngine(cfg, dataclasses.replace(ecfg, prefix_cache=False,
                                                 preempt=False),
                        params).run(reqs)
    for a, b in zip(share.results, plain.results):
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      np.asarray(b.tokens),
                                      err_msg=f"rid {a.rid}")
    assert share.prefill_tokens_saved > 0
    assert 0.0 < share.prefix_hit_rate <= 1.0
    assert share.prompt_tokens == plain.prompt_tokens
    assert plain.prefill_tokens_saved == 0 and plain.prefix_hit_rate == 0.0
    # at least one join saved tokens on a request-level counter too
    assert sum(r.prefill_tokens_saved for r in share.results) \
        == share.prefill_tokens_saved


def test_preemption_requeue_parity(smollm):
    """A page pool too small for every admitted context forces mid-decode
    preemption: the victim's generated tokens fold into its prompt, it
    re-queues, and its final stream is STILL bit-identical to the
    ample-pool engine — with the prefix cache restoring the requeue, and
    without it (full recompute)."""
    cfg, params = smollm
    reqs = batch_trace(3, seed=5, vocab_size=cfg.vocab_size, prompt_len=6,
                       max_new_tokens=14)
    ample = ServeEngine(cfg, dataclasses.replace(ECFG, prefix_cache=False,
                                                 preempt=False),
                        params).run(reqs)
    # 2 scratch + 6 usable pages; each context needs ceil((6+14)/4) = 5
    tight = dataclasses.replace(ECFG, n_pages=2 + 6, preempt=True)
    for prefix in (True, False):
        rep = ServeEngine(cfg, dataclasses.replace(tight,
                                                   prefix_cache=prefix),
                          params).run(reqs)
        assert rep.n_preemptions > 0
        for a, b in zip(rep.results, ample.results):
            np.testing.assert_array_equal(
                np.asarray(a.tokens), np.asarray(b.tokens),
                err_msg=f"rid {a.rid} prefix={prefix}")
        assert sum(r.n_preemptions for r in rep.results) == rep.n_preemptions
        if prefix:
            # the requeue found its own pages in the cache
            assert rep.prefill_tokens_saved > 0


def test_scheduler_skip_ahead(smollm):
    """Head-of-line fix: when the queue head cannot get pages, a bounded
    skip-ahead admits smaller requests behind it; with max_skip=0 the old
    strict-FIFO stall is preserved, and admitted order stays FIFO among
    the requests that fit."""
    from repro.serving import RequestQueue, Scheduler
    cfg, _ = smollm

    def mk_reqs():
        return [
            Request(rid=0, prompt=np.zeros(13, np.int32), max_new_tokens=8),
            Request(rid=1, prompt=np.zeros(5, np.int32), max_new_tokens=4),
            Request(rid=2, prompt=np.zeros(5, np.int32), max_new_tokens=4),
        ]

    def mk_kv():
        # 4 usable pages; rid 0 needs 5 (13 + 8 - 1 -> 20 tokens), rids
        # 1/2 need 2 each
        return PagedKVCache(cfg, n_slots=2, page_size=4, max_len=32,
                            n_pages=2 + 4)

    sched = Scheduler(2, mk_kv(), max_skip=1)
    joins = sched.poll(RequestQueue(mk_reqs()), 0)
    assert [j[1].rid for j in joins] == [1, 2]      # FIFO among admissible

    strict = Scheduler(2, mk_kv(), max_skip=0)
    assert strict.poll(RequestQueue(mk_reqs()), 0) == []

    # when the head fits, ordering is plain FIFO regardless of max_skip
    fifo = Scheduler(2, mk_kv(), max_skip=3)
    queue = RequestQueue(mk_reqs()[1:])
    assert [j[1].rid for j in fifo.poll(queue, 0)] == [1, 2]


def test_paged_kv_prefix_sharing_unit(smollm):
    """admit_with_prefix maps cached full pages read-only (refcounted),
    emits a copy-on-write spec at partial-page boundaries, and trie-held
    pages survive release until evicted."""
    cfg, _ = smollm
    kv = PagedKVCache(cfg, n_slots=2, page_size=4, max_len=32, n_pages=12)
    tokens = np.arange(12, dtype=np.int32)          # 3 full pages
    m, copy = kv.admit_with_prefix(0, tokens, 12)
    assert m == 0 and copy is None                  # cold cache
    kv.register_prefix(0, tokens)                   # index pages 0/1/2
    p0, p1, p2 = (int(kv.tables[0, j]) for j in range(3))
    assert kv.refcount[p0] == 2 and kv.refcount[p2] == 2   # slot + trie
    kv.release(0)
    assert kv.refcount[p0] == 1 and kv.refcount[p2] == 1   # trie keeps them

    # 11-token prompt sharing the head: 2 full pages restored read-only,
    # then rows 8/9 of the cached third page via copy-on-write (the match
    # is capped at L-1 = 10, so at most 2 of page 2's rows can match)
    m, copy = kv.admit_with_prefix(1, tokens[:11], 11)
    assert m == 10                                  # 8 full + 2 CoW rows
    assert copy is not None and copy.n_rows == 2
    assert copy.src_page == p2
    assert copy.dst_page == kv.tables[1, 2]
    assert kv.tables[1, 0] == p0 and kv.tables[1, 1] == p1
    assert kv.refcount[p0] == 2                     # shared read-only again
    assert kv.refcount[p2] == 2                     # trie + pending copy
    kv.copy_done(copy.src_page)
    assert kv.refcount[p2] == 1
    kv.release(1)

    # diverging prompt: only the common full pages match, no CoW
    other = np.concatenate([tokens[:8], np.full(6, 77, np.int32)])
    assert kv.can_admit_with_prefix(other, 14)
    m2, copy2 = kv.admit_with_prefix(1, other, 14)
    assert m2 == 8 and copy2 is None
    kv.release(1)

    # eviction reclaims trie-only pages when the pool runs dry
    kv2 = PagedKVCache(cfg, n_slots=1, page_size=4, max_len=16, n_pages=5)
    kv2.admit_with_prefix(0, np.arange(8, dtype=np.int32), 8)
    kv2.register_prefix(0, np.arange(8, dtype=np.int32))
    kv2.release(0)
    assert kv2.n_free == 2 and kv2.n_evictable() == 2
    kv2.admit(0, 16)                                # needs all 4 -> evicts
    assert kv2.n_free == 0 and kv2.n_evictable() == 0


def test_paged_kv_prefix_match_needs_a_whole_page(smollm):
    """A prompt that shares fewer than page_size leading tokens with a
    cached one restores nothing and copies nothing: it prefills cold,
    where the match would have sent it through the suffix sweeps."""
    cfg, _ = smollm
    kv = PagedKVCache(cfg, n_slots=2, page_size=4, max_len=32, n_pages=12)
    tokens = np.arange(12, dtype=np.int32)
    kv.admit_with_prefix(0, tokens, 12)
    kv.register_prefix(0, tokens)
    kv.release(0)
    for shared in (1, 3):
        other = np.concatenate([tokens[:shared], np.full(9, 77, np.int32)])
        m, copy = kv.admit_with_prefix(1, other, 12)
        assert (m, copy) == (0, None), shared
        kv.release(1)
    m, copy = kv.admit_with_prefix(1, tokens[:6], 6)   # a page and a row
    assert m == 5 and copy is not None and copy.n_rows == 1
    kv.copy_done(copy.src_page)
    kv.release(1)


def test_paged_kv_manager_invariants(smollm):
    cfg, _ = smollm
    kv = PagedKVCache(cfg, n_slots=2, page_size=4, max_len=32, n_pages=8)
    assert kv.n_free == 6                       # pages 0/1 are slot scratch
    pages = kv.admit(0, 9)                      # 3 pages
    assert len(pages) == 3 and all(p >= 2 for p in pages)
    assert (kv.tables[0, :3] == pages).all()
    assert (kv.tables[0, 3:] == 0).all()        # tail parked on scratch 0
    assert (kv.tables[1] == 1).all()
    with pytest.raises(ValueError):
        kv.admit(0, 4)                          # double-admit
    assert not kv.can_admit(4 * 4)              # 4 pages > 3 free
    kv.release(0)
    assert kv.n_free == 6 and (kv.tables[0] == 0).all()
    rows = kv.inject_rows(1, bucket_len=8, n_valid=5)
    kv.admit(1, 5)
    rows = kv.inject_rows(1, bucket_len=8, n_valid=5)
    assert (rows[5:] == kv.n_pages * kv.page_size).all()   # pad rows dropped
    assert len(set(rows[:5].tolist())) == 5

    # SSM families build state-slot pools instead of page tables: the
    # manager accepts them but reports the block tables as inactive (the
    # host tier has no page pool to ride)
    kv_ssm = PagedKVCache(get_arch("mamba2-370m").smoke, n_slots=2,
                          page_size=4, max_len=32)
    assert not kv_ssm.tables_active
    assert kv.tables_active


def test_energy_aware_admission(smollm):
    """The hook admits while predicted draw fits the budget, under the cap
    in force."""
    from repro.core import PowerCappedDevice, TPU_V5E
    from repro.launch.serve import decode_workload
    cfg, _ = smollm

    class Backend:
        cap = 1.0

        def current_cap(self):
            return self.cap

    device = PowerCappedDevice(TPU_V5E)
    backend = Backend()
    p1 = device.estimate(decode_workload(cfg, 1), 1.0).power_w
    hook = EnergyAwareAdmission(device, lambda n: decode_workload(cfg, n),
                                budget_w=p1 + 1e-6, backend=backend)
    req = Request(rid=0, prompt=np.zeros(4, np.int32), max_new_tokens=4)
    assert hook(req, 1)
    assert not hook(req, 10**6)                 # far past the budget
    backend.cap = 0.3                           # deep cap -> lower draw
    assert hook(req, 1)


def test_decode_loop_nongreedy_deterministic(smollm):
    """Non-greedy fused decode: same key -> same stream, different key ->
    different stream (CLI --temperature/--sample-seed path)."""
    cfg, params = smollm
    step_cfg = StepConfig(remat="none")
    from repro.runtime.steps import make_prefill_step
    prefill = jax.jit(make_prefill_step(cfg, step_cfg, max_len=32))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    last_logits, cache = prefill(params, {"inputs": prompts})
    tok0 = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)[:, None]
    loop = jax.jit(make_decode_loop(cfg, step_cfg, n_tokens=8, greedy=False,
                                    temperature=0.9))
    k1, k2 = jax.random.PRNGKey(3), jax.random.PRNGKey(4)
    a, _ = loop(params, cache, tok0, k1)
    b, _ = loop(params, cache, tok0, k1)
    c, _ = loop(params, cache, tok0, k2)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.any(np.asarray(a) != np.asarray(c))


def test_decode_loop_nongreedy_multicodebook():
    """The n_cb (musicgen) path: non-greedy sampling stays deterministic
    per codebook under a fixed key."""
    cfg = get_arch("musicgen-medium").smoke
    step_cfg = StepConfig(remat="none")
    params, _ = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    from repro.runtime.steps import make_prefill_step
    prefill = jax.jit(make_prefill_step(cfg, step_cfg, max_len=24))
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (2, 8, cfg.n_codebooks), 0, cfg.vocab_size)
    last_logits, cache = prefill(params, {"inputs": prompts})
    tok0 = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)[:, None]
    assert tok0.shape == (2, 1, cfg.n_codebooks)
    loop = jax.jit(make_decode_loop(cfg, step_cfg, n_tokens=5, greedy=False,
                                    temperature=1.0))
    key = jax.random.PRNGKey(9)
    a, _ = loop(params, cache, tok0, key)
    b, _ = loop(params, cache, tok0, key)
    assert a.shape == (2, 5, cfg.n_codebooks)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
