"""Per-kernel validation: shape/dtype sweeps, Pallas (interpret) and the
chunked-jnp fast paths, all against the pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_verify_attention_pallas,
                                            verify_attention_pallas)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_pallas


def _qkv(key, B, Sq, Sk, Hq, Hkv, D, Dv, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, Dv), dtype)
    return q, k, v


ATTN_SHAPES = [
    # B, Sq, Sk, Hq, Hkv, D, Dv
    (1, 128, 128, 4, 4, 32, 32),      # MHA
    (2, 128, 128, 8, 2, 32, 32),      # GQA 4:1
    (1, 256, 256, 9, 3, 64, 64),      # smollm's awkward 9/3 heads
    (1, 128, 128, 4, 1, 48, 16),      # MQA, Dv != D (MLA-shaped)
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_chunked_vs_ref(shape, dtype):
    B, Sq, Sk, Hq, Hkv, D, Dv = shape
    q, k, v = _qkv(jax.random.PRNGKey(1), B, Sq, Sk, Hq, Hkv, D, Dv,
                   jnp.dtype(dtype))
    tol = 2e-5 if dtype == "float32" else 2e-2
    for kwargs in [dict(causal=True), dict(causal=True, window=64),
                   dict(causal=True, logit_cap=30.0), dict(causal=False)]:
        o_ref = ref.attention_ref(q, k, v, **kwargs)
        o = ops.flash_attention_jnp(q, k, v, q_chunk=64, k_chunk=64, **kwargs)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(o_ref, np.float32),
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("shape", ATTN_SHAPES[:3])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_pallas_vs_ref(shape, dtype):
    B, Sq, Sk, Hq, Hkv, D, Dv = shape
    q, k, v = _qkv(jax.random.PRNGKey(2), B, Sq, Sk, Hq, Hkv, D, Dv,
                   jnp.dtype(dtype))
    tol = 2e-5 if dtype == "float32" else 2e-2
    for kwargs in [dict(causal=True), dict(causal=True, window=32),
                   dict(causal=True, logit_cap=50.0)]:
        o_ref = ref.attention_ref(q, k, v, **kwargs)
        o = flash_attention(q, k, v, block_q=32, block_k=64, interpret=True,
                            **kwargs)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(o_ref, np.float32),
                                   atol=tol, rtol=tol)


def test_flash_q_offset_decode_chunk():
    """Chunked prefill continuation: q block at an absolute offset."""
    q, k, v = _qkv(jax.random.PRNGKey(3), 2, 64, 256, 4, 2, 32, 32,
                   jnp.float32)
    o_ref = ref.attention_ref(q, k, v, causal=True, q_offset=192)
    o = ops.flash_attention_jnp(q, k, v, causal=True, q_offset=192,
                                q_chunk=32, k_chunk=64)
    np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=2e-5)


def test_decode_attention_ring_cache():
    """Ring-buffer decode == full attention at the same absolute position —
    for every backend behind ops.decode_attention."""
    B, S, Hq, Hkv, D = 2, 64, 4, 2, 32
    q, k, v = _qkv(jax.random.PRNGKey(4), B, S, S, Hq, Hkv, D, D, jnp.float32)
    # cache smaller than history with window: slot p % C
    C, window = 32, 24
    pos = S - 1
    k_cache = jnp.zeros((B, C, Hkv, D))
    v_cache = jnp.zeros((B, C, Hkv, D))
    for p in range(S):
        k_cache = k_cache.at[:, p % C].set(k[:, p])
        v_cache = v_cache.at[:, p % C].set(v[:, p])
    k_pos = ops.ring_positions(jnp.asarray(pos), C)
    o_ref = ref.attention_ref(q[:, -1:], k, v, causal=True, window=window,
                              q_offset=pos)
    o = ops.decode_attention_jnp(q[:, -1:], k_cache, v_cache, k_pos,
                                 jnp.asarray(pos), window=window)
    np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=2e-5)
    for backend in ("ref", "jnp", "pallas_interpret"):
        pol = ops.KernelPolicy(decode=backend, decode_k_chunk=16)
        o_b = ops.decode_attention(q[:, -1:], k_cache, v_cache,
                                   jnp.asarray(pos), window=window, policy=pol)
        np.testing.assert_allclose(o_b, o_ref, atol=2e-5, rtol=2e-5,
                                   err_msg=backend)


def _ring_cache(key, B, C, Hkv, D, Dv, pos, dtype):
    """Full history of length pos+1 folded into a slot = p % C ring."""
    S = pos + 1
    ks = jax.random.split(key, 2)
    k = jax.random.normal(ks[0], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[1], (B, S, Hkv, Dv), dtype)
    k_cache = jnp.zeros((B, C, Hkv, D), dtype)
    v_cache = jnp.zeros((B, C, Hkv, Dv), dtype)
    for p in range(S):
        k_cache = k_cache.at[:, p % C].set(k[:, p])
        v_cache = v_cache.at[:, p % C].set(v[:, p])
    return k_cache, v_cache


DECODE_SHAPES = [
    # B, C, Hq, Hkv, D, Dv
    (1, 64, 4, 4, 32, 32),      # MHA
    (2, 64, 8, 2, 32, 32),      # GQA 4:1
    (1, 96, 9, 3, 64, 64),      # smollm's awkward 9/3 heads
    (2, 64, 4, 1, 32, 16),      # MQA, Dv != D (MLA-shaped)
]

DECODE_CASES = [
    # pos, window, logit_cap — pos < C-1 leaves unwritten (invalid) slots;
    # pos >= C exercises ring wrap-around
    dict(pos=30, window=0, logit_cap=0.0),      # partial fill, invalid slots
    dict(pos=63, window=0, logit_cap=0.0),      # exactly full
    dict(pos=150, window=48, logit_cap=0.0),    # wrapped + sliding window
    dict(pos=100, window=0, logit_cap=30.0),    # wrapped + tanh softcap
]


@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_pallas_vs_ref(shape, dtype):
    """Split-K Pallas decode kernel (interpret) and the chunk-free jnp path
    vs the whole-cache fp32 oracle, across GQA group sizes, ring wrap,
    sliding window, logit cap, and bf16 storage."""
    B, C, Hq, Hkv, D, Dv = shape
    dt = jnp.dtype(dtype)
    tol = 2e-5 if dtype == "float32" else 2e-2
    for case in DECODE_CASES:
        pos, window, logit_cap = case["pos"], case["window"], case["logit_cap"]
        q = jax.random.normal(jax.random.PRNGKey(pos), (B, 1, Hq, D), dt)
        k_cache, v_cache = _ring_cache(jax.random.PRNGKey(pos + 1),
                                       B, C, Hkv, D, Dv, pos, dt)
        k_pos = ops.ring_positions(jnp.asarray(pos), C)
        o_ref = ref.decode_attention_ref(q, k_cache, v_cache, k_pos,
                                         jnp.asarray(pos), window=window,
                                         logit_cap=logit_cap)
        o_jnp = ops.decode_attention_jnp(q, k_cache, v_cache, k_pos,
                                         jnp.asarray(pos), window=window,
                                         logit_cap=logit_cap)
        # block_k=16 forces a multi-block split-K grid for every C here
        o_pl = decode_attention_pallas(q, k_cache, v_cache, jnp.asarray(pos),
                                       window=window, logit_cap=logit_cap,
                                       block_k=16, interpret=True)
        np.testing.assert_allclose(np.asarray(o_jnp, np.float32),
                                   np.asarray(o_ref, np.float32),
                                   atol=tol, rtol=tol, err_msg=str(case))
        np.testing.assert_allclose(np.asarray(o_pl, np.float32),
                                   np.asarray(o_ref, np.float32),
                                   atol=tol, rtol=tol, err_msg=str(case))


def test_decode_invalid_slots_masked():
    """Slots marked invalid (k_pos = -1, e.g. never written) carry no
    weight, whatever garbage their k/v rows hold."""
    B, C, Hkv, D = 1, 16, 2, 32
    pos = 40
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, 4, D))
    k_cache, v_cache = _ring_cache(jax.random.PRNGKey(1), B, C, Hkv, D, D, pos,
                                   jnp.float32)
    k_pos = ops.ring_positions(jnp.asarray(pos), C)
    # poison three slots: mark them invalid and fill with huge values
    bad = jnp.asarray([1, 5, 11])
    k_pos_bad = k_pos.at[bad].set(-1)
    k_poison = k_cache.at[:, bad].set(1e4)
    v_poison = v_cache.at[:, bad].set(1e4)
    o_clean = ops.decode_attention_jnp(
        q, k_cache, v_cache,
        k_pos.at[bad].set(-1), jnp.asarray(pos))
    o_poison = ops.decode_attention_jnp(q, k_poison, v_poison, k_pos_bad,
                                        jnp.asarray(pos))
    np.testing.assert_allclose(o_poison, o_clean, atol=2e-5, rtol=2e-5)
    o_ref = ref.decode_attention_ref(q, k_poison, v_poison, k_pos_bad,
                                     jnp.asarray(pos))
    np.testing.assert_allclose(o_poison, o_ref, atol=2e-5, rtol=2e-5)


def _paged_cache(key, B, ps, nb, P, Hkv, D, Dv, pos, dtype):
    """Per-request linear histories scattered into a shuffled page pool.
    Returns (k_pages, v_pages, tables, ring_caches) where ring_caches[b]
    holds the same history in the canonical slot = p % C ring layout."""
    rng = np.random.default_rng(int(np.sum(pos)))
    k_pages = np.zeros((P, ps, Hkv, D), dtype)
    v_pages = np.zeros((P, ps, Hkv, Dv), dtype)
    perm = rng.permutation(P)
    tables = perm[:B * nb].reshape(B, nb).astype(np.int32)
    rings = []
    C = nb * ps
    for b in range(B):
        S = int(pos[b]) + 1
        ks = jax.random.split(jax.random.fold_in(key, b), 2)
        k = np.asarray(jax.random.normal(ks[0], (S, Hkv, D), dtype))
        v = np.asarray(jax.random.normal(ks[1], (S, Hkv, Dv), dtype))
        for p in range(S):
            page, off = tables[b, p // ps], p % ps
            k_pages[page, off] = k[p]
            v_pages[page, off] = v[p]
        k_ring = np.zeros((C, Hkv, D), dtype)
        v_ring = np.zeros((C, Hkv, Dv), dtype)
        for p in range(S):
            k_ring[p % C] = k[p]
            v_ring[p % C] = v[p]
        rings.append((k_ring, v_ring))
    return jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(tables), rings


@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_decode_vs_ring(shape, dtype):
    """Paged decode == ring decode on the same (ragged) histories, for every
    backend behind ops.paged_decode_attention — block-table gather through a
    shuffled physical page layout, per-request positions, partially-filled
    final pages, window and logit-cap flavours, bf16 storage."""
    B, C, Hq, Hkv, D, Dv = shape
    ps, nb, P = 8, C // 8, C // 8 * B + B + 3
    dt = jnp.dtype(dtype)
    tol = 2e-5 if dtype == "float32" else 2e-2
    # ragged depths incl. a page-boundary-1 and a partially-filled page
    pos = np.asarray([(C - 1) if b == 0 else (ps * (2 + b) + b) % (C - 1)
                      for b in range(B)])
    key = jax.random.PRNGKey(11)
    k_pages, v_pages, tables, rings = _paged_cache(
        key, B, ps, nb, P, Hkv, D, Dv, pos, dt)
    q = jax.random.normal(jax.random.PRNGKey(12), (B, 1, Hq, D), dt)
    for case in [dict(window=0, logit_cap=0.0),
                 dict(window=ps * 2, logit_cap=0.0),
                 dict(window=0, logit_cap=30.0)]:
        # ring ground truth, one request at a time (scalar pos)
        o_ring = jnp.concatenate([
            ops.decode_attention_jnp(
                q[b:b + 1], jnp.asarray(rings[b][0])[None],
                jnp.asarray(rings[b][1])[None],
                ops.ring_positions(jnp.asarray(int(pos[b])), nb * ps),
                jnp.asarray(int(pos[b])), **case)
            for b in range(B)], axis=0)
        for backend in ("ref", "jnp", "pallas_interpret"):
            pol = ops.KernelPolicy(decode=backend)
            o = ops.paged_decode_attention(q, k_pages, v_pages, tables,
                                           jnp.asarray(pos), policy=pol,
                                           **case)
            np.testing.assert_allclose(
                np.asarray(o, np.float32), np.asarray(o_ring, np.float32),
                atol=tol, rtol=tol, err_msg=f"{backend} {case}")


VERIFY_CASES = [
    # pos, Q, window, logit_cap — pos < C leaves invalid slots; pos >= C
    # exercises ring wrap (incl. the eviction-semantics mask unique to the
    # verify path: entries the sequential loop would have overwritten)
    dict(pos=20, Q=4, window=0, logit_cap=0.0),     # partial fill
    dict(pos=100, Q=5, window=0, logit_cap=0.0),    # wrapped
    dict(pos=150, Q=3, window=24, logit_cap=0.0),   # wrapped + window
    dict(pos=90, Q=4, window=0, logit_cap=30.0),    # wrapped + softcap
    dict(pos=1, Q=3, window=0, logit_cap=0.0),      # near-empty cache
]


@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_verify_pallas_vs_full_attention(shape, dtype):
    """Speculative verify == full attention over the same history, for every
    backend: Q = K+1 queries at positions pos..pos+Q-1 against a ring
    committed through pos-1 plus the fed block's in-flight k/v.  The ground
    truth is ``attention_ref`` with an effective window of the cache
    capacity — exactly what the sequential decode loop's eviction gives."""
    B, C, Hq, Hkv, D, Dv = shape
    dt = jnp.dtype(dtype)
    tol = 2e-5 if dtype == "float32" else 2e-2
    for case in VERIFY_CASES:
        pos, Q = case["pos"], case["Q"]
        window, logit_cap = case["window"], case["logit_cap"]
        S = pos + Q
        ks = jax.random.split(jax.random.PRNGKey(pos + Q), 3)
        q_full = jax.random.normal(ks[0], (B, S, Hq, D), dt)
        k_full = jax.random.normal(ks[1], (B, S, Hkv, D), dt)
        v_full = jax.random.normal(ks[2], (B, S, Hkv, Dv), dt)
        k_cache = jnp.zeros((B, C, Hkv, D), dt)
        v_cache = jnp.zeros((B, C, Hkv, Dv), dt)
        for p in range(pos):                        # committed prefix only
            k_cache = k_cache.at[:, p % C].set(k_full[:, p])
            v_cache = v_cache.at[:, p % C].set(v_full[:, p])
        q = q_full[:, pos:]
        k_new, v_new = k_full[:, pos:], v_full[:, pos:]
        weff = C if window == 0 else min(window, C)
        o_true = ref.attention_ref(q, k_full, v_full, causal=True,
                                   window=weff, logit_cap=logit_cap,
                                   q_offset=pos)
        k_pos = ops.ring_positions(jnp.asarray(pos - 1), C)
        outs = {
            "ref": ref.verify_attention_ref(
                q, k_cache, v_cache, k_new, v_new, k_pos, jnp.asarray(pos),
                window=window, logit_cap=logit_cap),
            "jnp": ops.verify_attention_jnp(
                q, k_cache, v_cache, k_new, v_new, k_pos, jnp.asarray(pos),
                window=window, logit_cap=logit_cap),
            "pallas": verify_attention_pallas(
                q, k_cache, v_cache, k_new, v_new, jnp.asarray(pos),
                window=window, logit_cap=logit_cap, block_k=16,
                interpret=True),
        }
        for name, o in outs.items():
            np.testing.assert_allclose(
                np.asarray(o, np.float32), np.asarray(o_true, np.float32),
                atol=tol, rtol=tol, err_msg=f"{name} {case}")


@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_verify_pallas_vs_full_attention(shape, dtype):
    """Paged speculative verify == full attention per request: shuffled page
    layout, ragged per-request depths, in-flight candidates, window and
    softcap flavours — for every backend behind ops.paged_verify_attention."""
    B, C, Hq, Hkv, D, Dv = shape
    ps, nb = 8, C // 8
    P = B * nb + B + 3
    dt = jnp.dtype(dtype)
    tol = 2e-5 if dtype == "float32" else 2e-2
    Q = 4
    pos = np.asarray([(ps * (2 + b) + 3 * b + 1) % (nb * ps - Q)
                      for b in range(B)])
    rng = np.random.default_rng(int(pos.sum()))
    tables = rng.permutation(P)[:B * nb].reshape(B, nb).astype(np.int32)
    for case in [dict(window=0, logit_cap=0.0),
                 dict(window=ps * 2, logit_cap=0.0),
                 dict(window=0, logit_cap=30.0)]:
        k_pages = np.zeros((P, ps, Hkv, D), dtype)
        v_pages = np.zeros((P, ps, Hkv, Dv), dtype)
        fulls = []
        for b in range(B):
            S = int(pos[b]) + Q
            ks = jax.random.split(jax.random.fold_in(
                jax.random.PRNGKey(17), b), 3)
            qf = jax.random.normal(ks[0], (S, Hq, D), dt)
            kf = jax.random.normal(ks[1], (S, Hkv, D), dt)
            vf = jax.random.normal(ks[2], (S, Hkv, Dv), dt)
            for p in range(int(pos[b])):            # committed rows only
                k_pages[tables[b, p // ps], p % ps] = kf[p]
                v_pages[tables[b, p // ps], p % ps] = vf[p]
            fulls.append((qf, kf, vf))
        q = jnp.stack([f[0][int(pos[b]):] for b, f in enumerate(fulls)])
        k_new = jnp.stack([f[1][int(pos[b]):] for b, f in enumerate(fulls)])
        v_new = jnp.stack([f[2][int(pos[b]):] for b, f in enumerate(fulls)])
        o_true = jnp.stack([
            ref.attention_ref(f[0][None, int(pos[b]):], f[1][None],
                              f[2][None], causal=True, q_offset=int(pos[b]),
                              **case)[0]
            for b, f in enumerate(fulls)])
        kp, vp = jnp.asarray(k_pages), jnp.asarray(v_pages)
        bt, pa = jnp.asarray(tables), jnp.asarray(pos, dtype=jnp.int32)
        outs = {
            "ref": ref.paged_verify_attention_ref(
                q, kp, vp, k_new, v_new, bt, pa, **case),
            "jnp": ops.paged_verify_attention_jnp(
                q, kp, vp, k_new, v_new, bt, pa, **case),
            "pallas": paged_verify_attention_pallas(
                q, kp, vp, k_new, v_new, bt, pa, interpret=True, **case),
        }
        for name, o in outs.items():
            np.testing.assert_allclose(
                np.asarray(o, np.float32), np.asarray(o_true, np.float32),
                atol=tol, rtol=tol, err_msg=f"{name} {case}")


def test_flash_pallas_ragged_fallback():
    """Ragged Sq/Sk no longer assert: the Pallas wrapper falls back to the
    chunked jnp path, matching its behaviour, and says so with a
    FallbackWarning."""
    q, k, v = _qkv(jax.random.PRNGKey(11), 1, 100, 100, 4, 2, 32, 32,
                   jnp.float32)
    o_ref = ref.attention_ref(q, k, v, causal=True)
    with pytest.warns(ops.FallbackWarning, match="falling back"):
        o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=2e-5)


def test_decode_loop_matches_serve_step():
    """The fused lax.scan decode loop produces the exact token stream of the
    per-token host loop from the same prefill state."""
    from repro.configs import get_arch
    from repro.models import transformer as tfm
    from repro.runtime.steps import (StepConfig, make_decode_loop,
                                     make_prefill_step, make_serve_step)
    cfg = get_arch("smollm-135m").smoke
    step_cfg = StepConfig(remat="none")
    n_tokens = 6
    params, _ = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    prefill = jax.jit(make_prefill_step(cfg, step_cfg, max_len=32))
    serve = jax.jit(make_serve_step(cfg, step_cfg))
    loop = jax.jit(make_decode_loop(cfg, step_cfg, n_tokens=n_tokens))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    last_logits, cache = prefill(params, {"inputs": prompts})
    tok0 = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)[:, None]

    tok, c = tok0, cache
    stream = []
    for _ in range(n_tokens):
        nxt, c = serve(params, c, tok)
        stream.append(np.asarray(nxt))
        tok = nxt[:, None]
    per_token = np.stack(stream, axis=1)            # (B, n_tokens)

    fused, c2 = loop(params, cache, tok0)
    np.testing.assert_array_equal(np.asarray(fused), per_token)
    np.testing.assert_allclose(np.asarray(c2["pos"]), np.asarray(c["pos"]))


SSD_SHAPES = [
    # B, S, H, P, G, N, chunk
    (1, 64, 2, 8, 1, 16, 16),
    (2, 128, 4, 16, 2, 24, 32),
    (1, 128, 8, 64, 1, 128, 64),      # mamba2-370m-like head shape
]


@pytest.mark.parametrize("shape", SSD_SHAPES)
def test_ssd_chunked_vs_ref(shape):
    B, S, H, P, G, N, chunk = shape
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    D = jax.random.normal(ks[5], (H,))
    y_ref, h_ref = ref.ssd_ref(x, dt, A, Bm, Cm, D, return_state=True)
    y, h = ops.ssd_chunked_jnp(x, dt, A, Bm, Cm, D, chunk=chunk,
                               return_state=True)
    np.testing.assert_allclose(y, y_ref, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(h, h_ref, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("shape", SSD_SHAPES[:2])
def test_ssd_pallas_vs_ref(shape):
    B, S, H, P, G, N, chunk = shape
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    D = jax.random.normal(ks[5], (H,))
    y_ref = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    y = ssd_pallas(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    np.testing.assert_allclose(y, y_ref, atol=2e-3, rtol=2e-3)


def test_ssd_pallas_with_state_warns_and_matches_ref():
    """The kernel carries no state in or out: asking for it runs the
    chunked jnp path, with a FallbackWarning, and still matches the
    exact scan."""
    B, S, H, P, G, N, chunk = SSD_SHAPES[0]
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    y_ref, h_ref = ref.ssd_ref(x, dt, A, Bm, Cm, return_state=True)
    with pytest.warns(ops.FallbackWarning, match="SSM state"):
        y, h = ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk, return_state=True,
                          interpret=True)
    np.testing.assert_allclose(y, y_ref, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(h, h_ref, atol=2e-3, rtol=2e-3)


def test_ssd_initial_state_and_decode_chain():
    """Chunked prefill with carried state == one long exact scan; then the
    O(1) decode steps continue it exactly."""
    B, S, H, P, G, N = 1, 96, 2, 8, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    split = 64
    y1, h1 = ops.ssd_chunked_jnp(x[:, :split], dt[:, :split], A,
                                 Bm[:, :split], Cm[:, :split], None,
                                 chunk=32, return_state=True)
    ys = [y1]
    h = h1
    for t in range(split, S):
        h, yt = ops.ssd_decode_step(h, x[:, t], dt[:, t], A, Bm[:, t],
                                    Cm[:, t], None)
        ys.append(yt[:, None])
    y_chain = jnp.concatenate(ys, axis=1)
    y_ref = ref.ssd_ref(x, dt, A, Bm, Cm, None)
    np.testing.assert_allclose(y_chain, y_ref, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("rows,d", [(32, 64), (100, 96), (256, 128)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("gemma", [False, True])
def test_rmsnorm_pallas(rows, d, dtype, gemma):
    x = jax.random.normal(jax.random.PRNGKey(8), (rows, d), jnp.dtype(dtype))
    w = jax.random.normal(jax.random.PRNGKey(9), (d,))
    o_ref = ref.rmsnorm_ref(x, w, gemma_style=gemma)
    o = rmsnorm_pallas(x, w, gemma_style=gemma, block_rows=32, interpret=True)
    tol = 1e-6 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=tol, rtol=tol)
