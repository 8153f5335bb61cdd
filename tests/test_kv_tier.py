"""Two-tier KV hierarchy tests: fused-dequant kernel parity, host-tier
invariants under random traffic, engine stream equivalence, and
crash-restore with demoted host pages.

The contract: int8 pages with per-row fp32 scales are bit-stable (rows
quantize once, at write time) and the dequant fused into every split-KV
sweep family matches the quantized ref oracle — so turning the hierarchy
on must not move a single greedy token, and a crash must not lose a page
parked in the host tier.
"""
import dataclasses
import functools
import tempfile
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant
from repro.kernels import decode_attention as da
from repro.kernels import ops, ref

C = 80                 # ring capacity: 5 blocks of 16
BLOCK_K = 16
PS, NB = 16, 5         # paged: 5 pages of 16
D = DV = 16
Q = 4                  # verify block (K+1)
TOL = 5e-6

_HEADS = [(4, 2), (4, 1)]                # (Hq, Hkv): GQA and MQA
_POS = {"wrap": C + 15, "partial": 10}   # wrapped ring / mostly-empty cache
_BACKENDS = ["jnp", "pallas_interpret"]


def _arrays(B, Hq, Hkv, *, seed=0):
    """Random q/candidates plus int8-quantized ring caches and page pools
    with their per-row fp32 scale arrays."""
    rng = np.random.default_rng(seed)
    r = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    a = {
        "q1": r(B, 1, Hq, D), "qv": r(B, Q, Hq, D),
        "kn": r(B, Q, Hkv, D), "vn": r(B, Q, Hkv, DV),
        "bt": jnp.asarray(rng.permutation(16)[:B * NB].reshape(B, NB),
                          jnp.int32),
        "head": r(Hq * DV, 64),
    }
    a["k"], a["ks"] = quant.quantize_int8_rows(r(B, C, Hkv, D))
    a["v"], a["vs"] = quant.quantize_int8_rows(r(B, C, Hkv, DV))
    a["kp"], a["kps"] = quant.quantize_int8_rows(r(16, PS, Hkv, D))
    a["vp"], a["vps"] = quant.quantize_int8_rows(r(16, PS, Hkv, DV))
    return a


def _argmax(out, head):
    return jnp.argmax(out.reshape(out.shape[0], -1, out.shape[2] * out.shape[3])
                      .sum(axis=1) @ head, axis=-1)


def _policy(backend, n_splits):
    return ops.KernelPolicy(decode=backend, kv_splits=n_splits,
                            decode_k_chunk=BLOCK_K)


# --------------------------------------------------------------------------
# quantizer contract
# --------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_quantize_rows_error_bounded_by_half_step(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((3, 5, 2, 8))
                    * 10.0 ** rng.integers(-3, 3), jnp.float32)
    q, s = quant.quantize_int8_rows(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == x.shape[:-1] + (1,)
    err = jnp.abs(quant.dequantize_int8_rows(q, s) - x)
    # symmetric absmax + round-to-nearest: per-row error <= scale / 2
    assert float(jnp.max(err - 0.5 * s)) <= 1e-6


# --------------------------------------------------------------------------
# fused-dequant parity: all four sweep families vs the quantized oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("Hq,Hkv", _HEADS)
@pytest.mark.parametrize("backend", _BACKENDS)
@pytest.mark.parametrize("n_splits", [1, 2])
def test_ring_decode_int8_matches_quantized_oracle(Hq, Hkv, backend,
                                                   n_splits):
    a = _arrays(2, Hq, Hkv, seed=Hq * 10 + n_splits)
    for pos_v in _POS.values():
        pos = jnp.int32(pos_v)
        k_pos = ops.ring_positions(pos, C)
        oracle = ref.decode_attention_ref(a["q1"], a["k"], a["v"], k_pos,
                                          pos, k_scale=a["ks"],
                                          v_scale=a["vs"])
        got = ops.decode_attention(a["q1"], a["k"], a["v"], pos,
                                   k_scale=a["ks"], v_scale=a["vs"],
                                   policy=_policy(backend, n_splits))
        assert float(jnp.max(jnp.abs(got - oracle))) < TOL
        assert bool(jnp.all(_argmax(got, a["head"])
                            == _argmax(oracle, a["head"])))


@pytest.mark.parametrize("Hq,Hkv", _HEADS)
@pytest.mark.parametrize("backend", _BACKENDS)
@pytest.mark.parametrize("n_splits", [1, 2])
def test_ring_verify_int8_matches_quantized_oracle(Hq, Hkv, backend,
                                                   n_splits):
    a = _arrays(2, Hq, Hkv, seed=Hq * 20 + n_splits)
    for pos_v in _POS.values():
        pos = jnp.int32(pos_v)
        k_pos = ops.ring_positions(pos - 1, C)
        oracle = ref.verify_attention_ref(a["qv"], a["k"], a["v"], a["kn"],
                                          a["vn"], k_pos, pos,
                                          k_scale=a["ks"], v_scale=a["vs"])
        got = ops.verify_attention(a["qv"], a["k"], a["v"], a["kn"],
                                   a["vn"], pos, k_scale=a["ks"],
                                   v_scale=a["vs"],
                                   policy=_policy(backend, n_splits))
        assert float(jnp.max(jnp.abs(got - oracle))) < TOL
        assert bool(jnp.all(_argmax(got, a["head"])
                            == _argmax(oracle, a["head"])))


@pytest.mark.parametrize("Hq,Hkv", _HEADS)
@pytest.mark.parametrize("backend", _BACKENDS)
@pytest.mark.parametrize("n_splits", [1, 2])
@pytest.mark.parametrize("ppc", [None, 2])
def test_paged_decode_int8_matches_quantized_oracle(Hq, Hkv, backend,
                                                    n_splits, ppc,
                                                    monkeypatch):
    if ppc is not None:
        # the Pallas sweep's pages per grid step, forced through its VMEM
        # tile budget: 2 pages a block, the table's last block short
        token = da._vmem_bytes(Hkv, D, 1) + da._vmem_bytes(Hkv, 1, 4)
        monkeypatch.setattr(da, "_KV_TILE_BYTES", ppc * PS * token)
        assert da.pages_per_block(PS, Hkv, D, 1, NB, quantized=True) == ppc
    a = _arrays(3, Hq, Hkv, seed=Hq * 30 + n_splits)
    pos = jnp.asarray([3, 37, 79], jnp.int32)          # ragged occupancy
    oracle = ref.paged_decode_attention_ref(a["q1"], a["kp"], a["vp"],
                                            a["bt"], pos, k_scale=a["kps"],
                                            v_scale=a["vps"])
    got = ops.paged_decode_attention(a["q1"], a["kp"], a["vp"], a["bt"],
                                     pos, k_scale=a["kps"],
                                     v_scale=a["vps"],
                                     policy=_policy(backend, n_splits))
    assert float(jnp.max(jnp.abs(got - oracle))) < TOL
    assert bool(jnp.all(_argmax(got, a["head"])
                        == _argmax(oracle, a["head"])))


@pytest.mark.parametrize("Hq,Hkv", _HEADS)
@pytest.mark.parametrize("backend", _BACKENDS)
@pytest.mark.parametrize("n_splits", [1, 2])
def test_paged_verify_int8_matches_quantized_oracle(Hq, Hkv, backend,
                                                    n_splits):
    a = _arrays(3, Hq, Hkv, seed=Hq * 40 + n_splits)
    pos = jnp.asarray([5, 41, 76], jnp.int32)
    oracle = ref.paged_verify_attention_ref(a["qv"], a["kp"], a["vp"],
                                            a["kn"], a["vn"], a["bt"], pos,
                                            k_scale=a["kps"],
                                            v_scale=a["vps"])
    got = ops.paged_verify_attention(a["qv"], a["kp"], a["vp"], a["kn"],
                                     a["vn"], a["bt"], pos,
                                     k_scale=a["kps"], v_scale=a["vps"],
                                     policy=_policy(backend, n_splits))
    assert float(jnp.max(jnp.abs(got - oracle))) < TOL
    assert bool(jnp.all(_argmax(got, a["head"])
                        == _argmax(oracle, a["head"])))


def test_ring_decode_int8_window_and_softcap():
    a = _arrays(2, 4, 2, seed=5)
    pos = jnp.int32(_POS["wrap"])
    k_pos = ops.ring_positions(pos, C)
    for kw in ({"window": 24}, {"logit_cap": 30.0}):
        oracle = ref.decode_attention_ref(a["q1"], a["k"], a["v"], k_pos,
                                          pos, k_scale=a["ks"],
                                          v_scale=a["vs"], **kw)
        got = ops.decode_attention(a["q1"], a["k"], a["v"], pos,
                                   k_scale=a["ks"], v_scale=a["vs"],
                                   policy=_policy("pallas_interpret", 2),
                                   **kw)
        assert float(jnp.max(jnp.abs(got - oracle))) < TOL


# --------------------------------------------------------------------------
# engine level: the hierarchy must not move a single greedy token
# --------------------------------------------------------------------------
# (the hypothesis property test for the two-tier pool lives in
# tests/test_properties.py::test_two_tier_invariants_under_random_ops,
# behind the dev-extra hypothesis gate)
N_SLOTS, PAGE, CHUNK = 4, 8, 8
SHARED, SUFFIX, GEN = 44, (4, 12), (6, 16)
MAX_LEN = SHARED + SUFFIX[1] + GEN[1]
FULL_PAGES = N_SLOTS + 2 * -(-MAX_LEN // PAGE)       # roomy: no pressure
TIGHT_PAGES = N_SLOTS + -(-MAX_LEN // PAGE) + 2      # ~1 context + slack


@functools.lru_cache(maxsize=1)
def _tier_model():
    from repro.configs import get_arch
    from repro.models import transformer as tfm
    spec = get_arch("smollm-135m")
    cfg = dataclasses.replace(spec.smoke, d_model=64, d_ff=128, head_dim=16)
    params, _ = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _trace(cfg):
    from repro.serving import poisson_trace
    return poisson_trace(8, rate_per_step=0.5, seed=23,
                         vocab_size=cfg.vocab_size, prompt_len=SUFFIX,
                         max_new_tokens=GEN, shared_prefix_len=SHARED,
                         prompt_pools=1)


def _run(**kw):
    from repro.serving import EngineConfig, ServeEngine
    cfg, params = _tier_model()
    kw.setdefault("n_pages", FULL_PAGES)
    ecfg = EngineConfig(n_slots=N_SLOTS, page_size=PAGE, max_len=MAX_LEN,
                        decode_chunk=CHUNK, **kw)
    eng = ServeEngine(cfg, ecfg, params)
    rep = eng.run([dataclasses.replace(r) for r in _trace(cfg)])
    return eng, rep


def _streams(rep):
    return [list(np.asarray(r.tokens).ravel()) for r in rep.results]


@functools.lru_cache(maxsize=1)
def _baseline():
    return _run()                    # bf16, roomy pool, no tier


def test_default_path_has_no_tier_state():
    eng, rep = _baseline()
    for c in eng.cache["units"].values():
        assert "k_scale" not in c and "v_scale" not in c
    assert not eng.kv.host_tier and eng.kv._fetch_page is None
    assert rep.transfer_j == 0.0
    assert rep.n_demotions == 0 and rep.n_promotions == 0


def test_int8_engine_streams_match_bf16():
    eng, rep = _run(kv_dtype="int8")
    assert _streams(rep) == _streams(_baseline()[1])
    for c in eng.cache["units"].values():
        assert c["k"].dtype == jnp.int8 and c["v"].dtype == jnp.int8
        assert c["k_scale"].dtype == jnp.float32
        assert c["v_scale"].dtype == jnp.float32


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_host_tier_streams_match_and_charge_transfer(kv_dtype):
    eng, rep = _run(kv_dtype=kv_dtype, n_pages=TIGHT_PAGES, host_tier=True,
                    host_pages=16)
    assert _streams(rep) == _streams(_baseline()[1])
    assert rep.n_demotions > 0                       # tight pool paged out
    assert rep.transfer_j > 0.0
    assert rep.energy_j >= rep.transfer_j            # folded into the ledger
    assert eng.kv.verify_invariants() == []


def test_crash_restore_preserves_host_tier_pages():
    """Crash after pages demoted: the snapshot must carry the host-tier
    blobs, and the restored engine's streams must stay bit-identical to
    the fault-free roomy-pool baseline."""
    from repro.runtime.chaos import FaultInjector
    from repro.serving import EngineConfig, EngineCrash, ServeEngine
    cfg, params = _tier_model()
    ecfg = EngineConfig(n_slots=N_SLOTS, page_size=PAGE, max_len=MAX_LEN,
                        decode_chunk=CHUNK, n_pages=TIGHT_PAGES,
                        kv_dtype="int8", host_tier=True, host_pages=16)
    inj = FaultInjector(seed=0)
    inj.schedule("engine_crash", 12)
    snap = tempfile.mkdtemp(prefix="kvtier_chaos_")
    eng = ServeEngine(cfg, ecfg, params, injector=inj,
                      snapshot_dir=snap, snapshot_every=2)
    with pytest.raises(EngineCrash):
        eng.run([dataclasses.replace(r) for r in _trace(cfg)])
    eng2 = ServeEngine.restore(cfg, ecfg, params, snap,
                               injector=inj, snapshot_every=2)
    assert eng2.kv.n_host_used() > 0         # demoted pages survived
    rep = eng2.resume()
    assert rep.n_restores == 1
    assert _streams(rep) == _streams(_baseline()[1])
    assert rep.n_demotions > 0
    assert eng2.kv.verify_invariants() == []


def test_kv_dtype_fallback_warns_once():
    """int8 on a family whose cache has no full-length k/v page pools
    (``int8_paged_blockers`` names the feature) degrades to the cache
    dtype with ONE RuntimeWarning, not per-engine spam.  musicgen —
    blocked before the zoo paged rework because the old gate keyed on the
    speculative seam — now carries real scale rows: its pools are plain
    GQA, only the token side is multi-codebook."""
    from repro.configs import get_arch
    from repro.models import transformer as tfm
    from repro.serving import EngineConfig, ServeEngine
    spec = get_arch("h2o-danube-3-4b")
    cfg = dataclasses.replace(spec.smoke, d_model=64, d_ff=128, head_dim=16)
    params, _ = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    ops._KV_DTYPE_FALLBACK_WARNED.discard(cfg.name)
    ecfg = EngineConfig(n_slots=2, page_size=8, max_len=32, kv_dtype="int8")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        eng = ServeEngine(cfg, ecfg, params)
        ServeEngine(cfg, ecfg, params)
    hits = [w for w in rec if "kv_dtype=int8" in str(w.message)]
    assert len(hits) == 1 and issubclass(hits[0].category, RuntimeWarning)
    assert "sliding_window" in str(hits[0].message)
    for c in eng.cache["units"].values():    # degraded: no scale rows
        assert "k_scale" not in c

    mg = get_arch("musicgen-medium")
    cfg_mg = dataclasses.replace(mg.smoke, d_model=64, d_ff=128, head_dim=16)
    params_mg, _ = tfm.init_lm(jax.random.PRNGKey(0), cfg_mg)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        eng_mg = ServeEngine(cfg_mg, ecfg, params_mg)
    assert not [w for w in rec if "kv_dtype=int8" in str(w.message)]
    assert all("k_scale" in c for c in eng_mg.cache["units"].values())
