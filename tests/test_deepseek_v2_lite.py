"""DeepSeek-V2-Lite on the served path, against the benchmark's plain
float32 reference (``bench/references/deepseek_v2.py``), at smoke widths
with seeded weights.

Tolerances, with the reason for each:

  * float32 compute and pages (``LOGIT_TOL_F32`` 1e-4): prefill, then
    teacher-forced decode through the paged latent pool.  The absorbed
    latent attend and the grouped matmuls sum in another order than the
    reference's expanded heads: about 7e-6 on logits of std 3.  The same
    path in bfloat16 misses by 0.7-1.2, a float8 reference by 1.9-2.6.
  * bfloat16 compute and pages, served by ``ServeEngine``
    (``GAP_TOL_BF16`` 0.4): the widest gap between the reference's best
    logit and its logit of the served token, as the benchmark's check
    takes it, over 96 served tokens; bf16 rounding read 0.01-0.11 on
    three seeds, and a float8 (e4m3) reference choosing the tokens
    0.74-1.09.
  * layer identities (shares, top-k renormalisation): float32, 1e-5.
"""
import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import attention as attn
from repro.models import common
from repro.models import transformer as tfm
from repro.models.layers import moe_forward
from repro.runtime.steps import StepConfig, make_run_ctx
from repro.serving import EngineConfig, Request, ServeEngine
from repro.serving.spans import SpanRecorder

ROOT = pathlib.Path(__file__).resolve().parents[1]
LOGIT_TOL_F32 = 1e-4
GAP_TOL_BF16 = 0.4
LAYER_TOL = 1e-5


def _load_reference():
    path = ROOT / "bench" / "references" / "deepseek_v2.py"
    spec = importlib.util.spec_from_file_location("ref_deepseek_v2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def _smoke(**kw):
    """The arch's smoke widths, by default with 4 of its 8 routed experts
    held."""
    return dataclasses.replace(get_arch("deepseek-v2-lite").smoke,
                               **{"held_experts": 4, **kw})


def _config_file(cfg) -> dict:
    """The configuration file's published keys for ``cfg``."""
    y = cfg.rope_scaling
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "kv_lora_rank": cfg.kv_lora_rank, "q_lora_rank": None,
        "qk_nope_head_dim": cfg.nope_head_dim,
        "qk_rope_head_dim": cfg.rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "intermediate_size": cfg.dense_d_ff,
        "moe_intermediate_size": cfg.moe_d_ff,
        "n_routed_experts": cfg.resolved_held_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.norm_topk_prob,
        "n_shared_experts": cfg.n_shared_experts,
        "first_k_dense_replace": cfg.first_dense_layers,
        "num_hidden_layers": cfg.n_layers, "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": cfg.tie_embeddings,
        "scoring_func": "softmax", "topk_method": "greedy",
        "moe_layer_freq": 1, "routed_scaling_factor": 1,
        "rope_scaling": {"type": "yarn", "factor": y.factor,
                         "original_max_position_embeddings":
                             y.original_max_position,
                         "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                         "mscale": y.mscale,
                         "mscale_all_dim": y.mscale_all_dim},
        "bench": {"published": {"n_routed_experts": cfg.n_experts}}}


def _weights(cfg, seed=0):
    s = ref.sizes(_config_file(cfg))
    ref.check_program(s, cfg)
    w = ref.init_weights(jax.random.PRNGKey(seed), s)
    want = jax.eval_shape(lambda: tfm.init_lm(jax.random.PRNGKey(0), cfg)[0])
    assert jax.tree.map(lambda x: x.shape, w) == \
        jax.tree.map(lambda x: tuple(x.shape), want)
    return s, w


def _ref_logits(w, s, tokens, first, n):
    return ref.logits(w, np.asarray(tokens, np.int32), first, n, s)


def _paged_teacher_forced(cfg, params, tokens, L, *, dtype):
    """Logits of prefill (the 512 bucket, positions 0..L-1) and then of
    decode steps through the paged latent pool, fed ``tokens[L:]``."""
    ps, nb = 8, 96
    ctx = make_run_ctx(cfg, None, StepConfig(remat="none"))
    bucket = 512
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :L] = tokens[:L]
    pf = jax.jit(lambda p, t: tfm.prefill_counted(p, t, cfg, ctx,
                                                  max_len=bucket,
                                                  full_cache=True))
    logits, pc, rows = pf(params, jnp.asarray(padded))
    cache = tfm.init_paged_cache(cfg, 1, 1 + nb, ps, nb, dtype=dtype)
    table = np.arange(1, 1 + nb, dtype=np.int32)[None]
    flat_rows = table[0, np.arange(L) // ps] * ps + np.arange(L) % ps

    def inject(pool, lat):            # pool (n, P, ps, R), lat (n, S, R)
        flat = pool.reshape(pool.shape[0], -1, pool.shape[-1])
        flat = flat.at[:, flat_rows].set(lat[:, :L].astype(flat.dtype))
        return flat.reshape(pool.shape)

    cache = {**cache, "block_tables": jnp.asarray(table),
             "pos": jnp.asarray([L], jnp.int32),
             "units": {"sub0": {"lat": inject(
                 cache["units"]["sub0"]["lat"],
                 pc["units"]["sub0"]["lat"][:, 0])}},
             "dense": {"lat": inject(
                 cache["dense"]["lat"],
                 jnp.stack([c["lat"][0] for c in pc["dense"]]))}}
    step = jax.jit(lambda p, c, t: tfm.paged_decode_step(p, c, t, cfg, ctx,
                                                         None))
    out = [np.asarray(logits[0, :L], np.float32)]
    for t in tokens[L:]:
        lg, cache, _ = step(params, cache, jnp.asarray([[t]], jnp.int32))
        out.append(np.asarray(lg[:, 0], np.float32))
    return np.concatenate(out)[:, :cfg.vocab_size], rows


@pytest.mark.parametrize("L", [40, 300])
def test_prefill_then_paged_decode_matches_reference(L):
    """Prefill, then decode through the paged latent pool: float32 logits
    within LOGIT_TOL_F32 of the reference at every position (a prompt
    over 256 tokens crosses the old capacity path); the same path in
    bfloat16 does not meet that tolerance."""
    n_dec = 6
    rng = np.random.default_rng(L)
    cfg32 = _smoke(dtype="float32")
    s, w = _weights(cfg32)
    tokens = rng.integers(0, cfg32.vocab_size, L + n_dec).astype(np.int32)
    want = _ref_logits(w, s, tokens, 0, L + n_dec)
    got, rows = _paged_teacher_forced(cfg32, w, tokens, L, dtype="float32")
    err = np.abs(got[:L + n_dec] - want).max()
    assert err <= LOGIT_TOL_F32, err
    assert int(rows) > 0
    bf16, _ = _paged_teacher_forced(_smoke(), w, tokens, L,
                                    dtype="bfloat16")
    assert np.abs(bf16[:L + n_dec] - want).max() > LOGIT_TOL_F32


def test_engine_serves_within_the_reference_gap():
    """ServeEngine (bucketed prefill, paged latent pool, fused decode loop,
    prefix cache on) at the program's bf16 compute: the served tokens'
    reference logit gaps stay within GAP_TOL_BF16, and a float8 reference
    choosing the tokens does not.  The expert-row counters come back with
    the chunks and joins."""
    cfg = _smoke()
    s, w = _weights(cfg)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, L)
                    .astype(np.int32), max_new_tokens=48, arrival_step=0)
            for i, L in enumerate((300, 37))]
    ecfg = EngineConfig(n_slots=2, page_size=8, max_len=512, decode_chunk=4)
    spans = SpanRecorder()
    rep = ServeEngine(cfg, ecfg, w, spans=spans).run(reqs)
    worst = control = 0.0
    for r, q in zip(rep.results, reqs):
        toks = np.asarray(r.tokens, np.int32)
        fed = np.concatenate([q.prompt, toks[:-1]])
        lg = _ref_logits(w, s, fed, q.prompt_len - 1, len(toks))
        worst = max(worst, float((lg.max(-1) - lg[np.arange(len(toks)),
                                                  toks]).max()))
        fp8 = ref.logits(w, fed, q.prompt_len - 1, len(toks), s,
                         precision="fp8").argmax(-1)
        control = max(control, float((lg.max(-1) - lg[np.arange(len(toks)),
                                                      fp8]).max()))
    assert worst <= GAP_TOL_BF16 < control, (worst, control)
    assert rep.expert_rows_prefill > 0 and rep.expert_rows_decode > 0
    chunk_rows = sum(sp.counts.get("expert_rows", 0) for sp in spans.spans
                     if sp.name == "chunk")
    join_rows = sum(sp.counts.get("expert_rows", 0) for sp in spans.spans
                    if sp.name == "join")
    assert chunk_rows == rep.expert_rows_decode
    assert join_rows == rep.expert_rows_prefill


def test_expert_rows_count_every_held_assignment():
    """With every expert held, each token's top-k is computed here: the
    counters read tokens x k x expert layers, pad rows and parked slots
    included, and a model without experts counts nothing."""
    cfg = dataclasses.replace(get_arch("deepseek-v2-lite").smoke)
    params, _ = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(n_slots=2, page_size=8, max_len=64, decode_chunk=4,
                        prefix_cache=False)
    req = Request(rid=0, prompt=np.arange(1, 12, dtype=np.int32),
                  max_new_tokens=9, arrival_step=0)
    rep = ServeEngine(cfg, ecfg, params).run([req])
    per_token = cfg.experts_per_token * (cfg.n_layers
                                         - cfg.first_dense_layers)
    assert rep.expert_rows_prefill == 16 * per_token      # bucket of 16
    assert rep.expert_rows_decode == \
        rep.n_chunks * ecfg.decode_chunk * ecfg.n_slots * per_token
    dense = get_arch("smollm-135m").smoke
    dp, _ = tfm.init_lm(jax.random.PRNGKey(0), dense)
    drep = ServeEngine(dense, ecfg, dp).run([req])
    assert drep.expert_rows_prefill == drep.expert_rows_decode == 0


def _layer_inputs(cfg, T=300, seed=5):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, T, cfg.d_model),
                             jnp.float32)


def test_chip_shares_sum_to_the_uncut_layer():
    """16 experts split into two shares of 8: what each share's experts
    give, with the shared experts counted once, sums to the uncut layer,
    and to the reference's uncut layer; no token is dropped at 300
    tokens, and the shares' rows add up to every assignment."""
    full = _smoke(dtype="float32", n_experts=16, held_experts=0)
    share = dataclasses.replace(full, held_experts=8)
    s_full, w = _weights(full)
    ffn = jax.tree.map(lambda a: a[0], w["layers"]["sub0"]["ffn"])
    x = _layer_inputs(full)
    out_full, _, rows_full = moe_forward(ffn, x, full)
    parts = []
    for k in range(2):
        p = {key: (v[8 * k:8 * (k + 1)] if key in ("wi_gate", "wi_up", "wo")
                   else v) for key, v in ffn.items()
             if key != "shared" or k == 0}
        parts.append(moe_forward(p, x, share, first_expert=8 * k))
    summed = parts[0][0] + parts[1][0]
    np.testing.assert_allclose(np.asarray(summed), np.asarray(out_full),
                               atol=LAYER_TOL, rtol=LAYER_TOL)
    assert int(parts[0][2]) + int(parts[1][2]) == int(rows_full) \
        == x.shape[1] * full.experts_per_token
    with jax.default_matmul_precision("highest"):
        want = ref._experts(x[0], ffn, s_full, lambda a: a)
    np.testing.assert_allclose(np.asarray(summed[0]), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_norm_topk_prob_false_against_true():
    """Without renormalisation the routed part is the renormalised one
    times the token's kept probability mass; the reference agrees on both."""
    cfg = _smoke(dtype="float32", held_experts=0)
    s, w = _weights(cfg)
    ffn = jax.tree.map(lambda a: a[0], w["layers"]["sub0"]["ffn"])
    ffn.pop("shared")
    x = _layer_inputs(cfg, T=64)
    off, _, _ = moe_forward(ffn, x, cfg)
    on, _, _ = moe_forward(ffn, x, dataclasses.replace(cfg,
                                                       norm_topk_prob=True))
    probs = jax.nn.softmax(x[0] @ ffn["router"], -1)
    mass = jnp.sum(jax.lax.top_k(probs, cfg.experts_per_token)[0], -1)
    np.testing.assert_allclose(np.asarray(on[0] * mass[:, None]),
                               np.asarray(off[0]), atol=LAYER_TOL,
                               rtol=1e-4)
    assert float(jnp.abs(on - off).max()) > 1e-2
    for flag, got in ((False, off), (True, on)):
        with jax.default_matmul_precision("highest"):
            gates = ref.route(x[0], ffn["router"], {**s, "norm_topk": flag})
        y = jnp.einsum("se,esd->sd", gates, jnp.einsum(
            "esf,efd->esd", jax.nn.silu(jnp.einsum(
                "sd,edf->esf", x[0], ffn["wi_gate"])) * jnp.einsum(
                "sd,edf->esf", x[0], ffn["wi_up"]), ffn["wo"]))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(y),
                                   atol=1e-4, rtol=1e-4)


# DeepSeek-V2-Lite's rope dims (64, theta 1e4) under YaRN factor 40 over an
# original 4096 positions, beta_fast 32, beta_slow 1: correction dims 10
# and 23; computed from the published formula.
YARN_INV_FREQ = np.array([
    1.0000000000e+00, 7.4989420933e-01, 5.6234132519e-01, 4.2169650343e-01,
    3.1622776602e-01, 2.3713737057e-01, 1.7782794100e-01, 1.3335214322e-01,
    1.0000000000e-01, 7.4989420933e-02, 5.6234132519e-02, 3.9006926567e-02,
    2.6879360111e-02, 1.8378146219e-02, 1.2447955870e-02, 8.3345089510e-03,
    5.5000000000e-03, 3.5619974943e-03, 2.2493653008e-03, 1.3705136361e-03,
    7.9056941504e-04, 4.1499039849e-04, 1.7782794100e-04, 3.3338035804e-05,
    2.5000000000e-05, 1.8747355233e-05, 1.4058533130e-05, 1.0542412586e-05,
    7.9056941504e-06, 5.9284342642e-06, 4.4456985251e-06, 3.3338035804e-06])
# 192^-0.5 * (0.1 * 0.707 * ln 40 + 1)^2
YARN_SOFTMAX_SCALE = 0.1147213867929261


def test_yarn_frequencies_and_scale_follow_the_published_formula():
    cfg = get_arch("deepseek-v2-lite").config
    y = cfg.rope_scaling
    got = common.rope_frequencies(cfg.rope_head_dim, cfg.rope_theta, y)
    np.testing.assert_allclose(got, YARN_INV_FREQ, rtol=1e-9)
    s = ref.sizes({**_config_file(cfg), "bench": {"published": {}}})
    np.testing.assert_allclose(ref.yarn_inv_freq(s), YARN_INV_FREQ,
                               rtol=1e-9)
    assert attn._mla_scale(cfg) == pytest.approx(YARN_SOFTMAX_SCALE,
                                                 rel=1e-12)
    assert ref.softmax_scale(s) == pytest.approx(YARN_SOFTMAX_SCALE,
                                                 rel=1e-12)
    # mscale == mscale_all_dim: cos and sin are not rescaled
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 1, 64), jnp.float32)
    pos = jnp.arange(5)
    ang = np.arange(5)[:, None] * YARN_INV_FREQ[None]
    want = np.concatenate(
        [x[..., :32] * np.cos(ang)[:, None] - x[..., 32:] * np.sin(ang)[:, None],
         x[..., 32:] * np.cos(ang)[:, None] + x[..., :32] * np.sin(ang)[:, None]],
        -1)
    np.testing.assert_allclose(np.asarray(common.apply_rope(x, pos, 1e4, y)),
                               want, atol=1e-5)
    # without rope_scaling nothing changes
    np.testing.assert_array_equal(common.rope_frequencies(64, 1e4),
                                  common.rope_frequencies(64, 1e4, None))
    plain = dataclasses.replace(cfg, rope_scaling=None)
    assert attn._mla_scale(plain) == 192 ** -0.5
