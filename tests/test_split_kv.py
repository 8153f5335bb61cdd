"""Two-stage split-KV contract tests (interpret-mode parity sweeps).

Every case runs the stage-1 partial sweep + stage-2 LSE merge against BOTH
the single-split kernel (``n_splits=1``, today's bit-exact path) and the
whole-cache ``ref.py`` oracle, across GQA/MQA x ring wrap-around x partial
occupancy x ragged paged ``pos`` x non-divisible split counts.  Greedy
argmax through a projection head must be identical — that is what keeps
the PR 4/5 exactness canaries green when the engine turns splits on.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as da
from repro.kernels import ops, ref

C = 80                 # ring capacity: 5 blocks of 16
BLOCK_K = 16
PS, NB = 16, 5         # paged: 5 pages of 16
D = DV = 16
Q = 4                  # verify block (K+1)
SPLITS = [1, 2, 5]     # 2 does not divide 5 blocks; 5 = one block per split
TOL = 5e-6

_HEADS = [(4, 2), (4, 1)]          # (Hq, Hkv): GQA and MQA
_POS = {"wrap": C + 15, "partial": 10}   # wrapped ring / mostly-empty cache


def _rng_arrays(B, Hq, Hkv, *, seed=0):
    rng = np.random.default_rng(seed)
    r = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return {
        "q1": r(B, 1, Hq, D), "qv": r(B, Q, Hq, D),
        "k": r(B, C, Hkv, D), "v": r(B, C, Hkv, DV),
        "kn": r(B, Q, Hkv, D), "vn": r(B, Q, Hkv, DV),
        "kp": r(16, PS, Hkv, D), "vp": r(16, PS, Hkv, DV),
        "bt": jnp.asarray(rng.permutation(16)[:B * NB].reshape(B, NB),
                          jnp.int32),
        "head": r(Hq * DV, 64),
    }


def _argmax(out, head):
    return jnp.argmax(out.reshape(out.shape[0], -1, out.shape[2] * out.shape[3])
                      .sum(axis=1) @ head, axis=-1)


# --------------------------------------------------------------------------
# ring decode
# --------------------------------------------------------------------------
@pytest.mark.parametrize("Hq,Hkv", _HEADS)
@pytest.mark.parametrize("pos_kind", ["wrap", "partial"])
@pytest.mark.parametrize("n_splits", SPLITS)
def test_ring_decode_two_stage_parity(Hq, Hkv, pos_kind, n_splits):
    a = _rng_arrays(2, Hq, Hkv, seed=Hq * 10 + n_splits)
    pos = jnp.int32(_POS[pos_kind])
    k_pos = ops.ring_positions(pos, C)
    oracle = ref.decode_attention_ref(a["q1"], a["k"], a["v"], k_pos, pos)
    single = da.decode_attention_pallas(a["q1"], a["k"], a["v"], pos,
                                        block_k=BLOCK_K, interpret=True)
    two = da.decode_attention_pallas(a["q1"], a["k"], a["v"], pos,
                                     block_k=BLOCK_K, n_splits=n_splits,
                                     interpret=True)
    assert float(jnp.max(jnp.abs(two - oracle))) < TOL
    assert float(jnp.max(jnp.abs(two - single))) < TOL
    assert bool(jnp.all(_argmax(two, a["head"]) == _argmax(oracle, a["head"])))
    if n_splits == 1:              # splits=1 must be bit-for-bit the old path
        assert bool(jnp.all(two == single))


@pytest.mark.parametrize("n_splits", [2, 5])
def test_ring_decode_stage1_matches_split_ref(n_splits):
    """Stage-1 contract: Pallas partials/LSE == the split oracle, including
    empty splits (partial-occupancy cache leaves whole splits without a
    single valid key -> zero partial, NEG_INF lse)."""
    a = _rng_arrays(2, 4, 2, seed=3)
    for pos_v in _POS.values():
        pos = jnp.int32(pos_v)
        k_pos = ops.ring_positions(pos, C)
        pr, lr = ref.decode_attention_split_ref(
            a["q1"], a["k"], a["v"], k_pos, pos, n_splits=n_splits,
            block_k=BLOCK_K)
        pp, lp = da.decode_attention_pallas_partials(
            a["q1"], a["k"], a["v"], pos, n_splits=n_splits,
            block_k=BLOCK_K, interpret=True)
        assert pr.shape == pp.shape and lr.shape == lp.shape
        assert float(jnp.max(jnp.abs(pr - pp))) < TOL
        assert float(jnp.max(jnp.abs(lr - lp))) < 1e-5
        merged = ref.merge_kv_splits_ref(pr, lr)
        oracle = ref.decode_attention_ref(a["q1"], a["k"], a["v"], k_pos, pos)
        assert float(jnp.max(jnp.abs(
            merged[:, :, 0] - oracle[:, 0]))) < TOL


def test_ring_decode_window_and_softcap():
    a = _rng_arrays(2, 4, 2, seed=5)
    pos = jnp.int32(_POS["wrap"])
    k_pos = ops.ring_positions(pos, C)
    for kw in ({"window": 24}, {"logit_cap": 30.0}):
        oracle = ref.decode_attention_ref(a["q1"], a["k"], a["v"], k_pos,
                                          pos, **kw)
        two = da.decode_attention_pallas(a["q1"], a["k"], a["v"], pos,
                                         block_k=BLOCK_K, n_splits=2,
                                         interpret=True, **kw)
        assert float(jnp.max(jnp.abs(two - oracle))) < TOL


# --------------------------------------------------------------------------
# ring verify (candidates fold into the last split)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("Hq,Hkv", _HEADS)
@pytest.mark.parametrize("pos_kind", ["wrap", "partial"])
@pytest.mark.parametrize("n_splits", SPLITS)
def test_ring_verify_two_stage_parity(Hq, Hkv, pos_kind, n_splits):
    a = _rng_arrays(2, Hq, Hkv, seed=Hq * 20 + n_splits)
    pos = jnp.int32(_POS[pos_kind])
    k_pos = ops.ring_positions(pos - 1, C)
    oracle = ref.verify_attention_ref(a["qv"], a["k"], a["v"], a["kn"],
                                      a["vn"], k_pos, pos)
    single = da.verify_attention_pallas(a["qv"], a["k"], a["v"], a["kn"],
                                        a["vn"], pos, block_k=BLOCK_K,
                                        interpret=True)
    two = da.verify_attention_pallas(a["qv"], a["k"], a["v"], a["kn"],
                                     a["vn"], pos, block_k=BLOCK_K,
                                     n_splits=n_splits, interpret=True)
    assert float(jnp.max(jnp.abs(two - oracle))) < TOL
    assert float(jnp.max(jnp.abs(two - single))) < TOL
    assert bool(jnp.all(_argmax(two, a["head"]) == _argmax(oracle, a["head"])))
    if n_splits == 1:
        assert bool(jnp.all(two == single))


# --------------------------------------------------------------------------
# paged decode / paged verify (ragged per-request pos)
# --------------------------------------------------------------------------
# paged decode grid: one step per slot x block of ``ppc`` pages, all heads.
# ``ppc`` follows from the page's VMEM footprint; at these test widths the
# whole 5-page table is one block, so the tests also shrink the tile budget
# to force 2 pages a block (3 blocks, the last one short).
_PAGED_HEADS = _HEADS + [(4, 4), (6, 2)]        # + MHA (G = 1) and G = 3
_PPC = [None, 2]
# slot ragged depths: pos 0, a page's last and next position, a 2-page
# block's last and next position, the table's last position, a mid-page
# tail; then a parked slot whose whole row points at one scratch page
_PAGED_POS = [0, 15, 16, 31, 32, NB * PS - 1, 45, 0]
_PAGED_CASES = {"plain": {}, "window": {"window": 20},
                "cap": {"logit_cap": 5.0}}


def _force_ppc(monkeypatch, ppc, Hkv, itemsize=4, quantized=False):
    """Pages per block the kernel derives, forced through its tile budget;
    ``None`` leaves the derivation alone."""
    if ppc is None:
        return da.pages_per_block(PS, Hkv, D, itemsize, NB,
                                  quantized=quantized)
    token = da._vmem_bytes(Hkv, D, itemsize) \
        + (da._vmem_bytes(Hkv, 1, 4) if quantized else 0)
    monkeypatch.setattr(da, "_KV_TILE_BYTES", ppc * PS * token)
    assert da.pages_per_block(PS, Hkv, D, itemsize, NB,
                              quantized=quantized) == ppc
    return ppc


def _paged_arrays(Hq, Hkv, *, seed):
    B = len(_PAGED_POS)
    rng = np.random.default_rng(seed)
    r = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    P = B * NB + 1
    bt = rng.permutation(P - 1)[:B * NB].reshape(B, NB)
    bt[-1] = P - 1                                   # parked on scratch
    return {"q1": r(B, 1, Hq, D), "kp": r(P, PS, Hkv, D),
            "vp": r(P, PS, Hkv, DV), "bt": jnp.asarray(bt, jnp.int32),
            "pos": jnp.asarray(_PAGED_POS, jnp.int32),
            "head": r(Hq * DV, 64)}


@pytest.mark.parametrize("Hq,Hkv", _PAGED_HEADS)
@pytest.mark.parametrize("n_splits", SPLITS)
@pytest.mark.parametrize("ppc", _PPC)
@pytest.mark.parametrize("case", list(_PAGED_CASES))
def test_paged_decode_two_stage_parity(Hq, Hkv, n_splits, ppc, case,
                                       monkeypatch):
    _force_ppc(monkeypatch, ppc, Hkv)
    a = _paged_arrays(Hq, Hkv, seed=Hq * 30 + Hkv + n_splits)
    kw = _PAGED_CASES[case]
    args = (a["q1"], a["kp"], a["vp"], a["bt"], a["pos"])
    oracle = ref.paged_decode_attention_ref(*args, **kw)
    single = da.paged_decode_attention_pallas(*args, interpret=True, **kw)
    two = da.paged_decode_attention_pallas(*args, n_splits=n_splits,
                                           interpret=True, **kw)
    assert float(jnp.max(jnp.abs(two - oracle))) < TOL
    assert float(jnp.max(jnp.abs(two - single))) < TOL
    assert bool(jnp.all(_argmax(two, a["head"]) == _argmax(oracle, a["head"])))
    if n_splits == 1:
        assert bool(jnp.all(two == single))


@pytest.mark.parametrize("n_splits", [2, 5])
@pytest.mark.parametrize("ppc", _PPC)
def test_paged_decode_stage1_matches_split_ref(n_splits, ppc, monkeypatch):
    ppc = _force_ppc(monkeypatch, ppc, 2)
    a = _paged_arrays(4, 2, seed=7)
    args = (a["q1"], a["kp"], a["vp"], a["bt"], a["pos"])
    pr, lr = ref.paged_decode_attention_split_ref(
        *args, n_splits=n_splits, block_pages=ppc)
    pp, lp = da.paged_decode_attention_pallas_partials(
        *args, n_splits=n_splits, interpret=True)
    assert pr.shape == pp.shape and lr.shape == lp.shape
    assert pp.shape[2] == min(n_splits, -(-NB // ppc))
    assert float(jnp.max(jnp.abs(pr - pp))) < TOL
    assert float(jnp.max(jnp.abs(lr - lp))) < 1e-5


@pytest.mark.parametrize("Hq,Hkv", _HEADS)
@pytest.mark.parametrize("n_splits", SPLITS)
def test_paged_verify_two_stage_parity(Hq, Hkv, n_splits):
    a = _rng_arrays(3, Hq, Hkv, seed=Hq * 40 + n_splits)
    pos = jnp.asarray([5, 41, 76], jnp.int32)
    oracle = ref.paged_verify_attention_ref(a["qv"][:3], a["kp"], a["vp"],
                                            a["kn"][:3], a["vn"][:3],
                                            a["bt"], pos)
    single = da.paged_verify_attention_pallas(a["qv"][:3], a["kp"], a["vp"],
                                              a["kn"][:3], a["vn"][:3],
                                              a["bt"], pos, interpret=True)
    two = da.paged_verify_attention_pallas(a["qv"][:3], a["kp"], a["vp"],
                                           a["kn"][:3], a["vn"][:3],
                                           a["bt"], pos, n_splits=n_splits,
                                           interpret=True)
    assert float(jnp.max(jnp.abs(two - oracle))) < TOL
    assert float(jnp.max(jnp.abs(two - single))) < TOL
    assert bool(jnp.all(_argmax(two, a["head"]) == _argmax(oracle, a["head"])))
    if n_splits == 1:
        assert bool(jnp.all(two == single))


# --------------------------------------------------------------------------
# jnp backend split path + policy dispatch + heuristic + fallback warning
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n_splits", [2, 3, 5])
def test_jnp_split_paths_match_single(n_splits):
    a = _rng_arrays(2, 4, 2, seed=11)
    pos = jnp.int32(_POS["wrap"])
    k_pos = ops.ring_positions(pos, C)
    pairs = [
        (ops.decode_attention_jnp(a["q1"], a["k"], a["v"], k_pos, pos),
         ops.decode_attention_jnp(a["q1"], a["k"], a["v"], k_pos, pos,
                                  n_splits=n_splits)),
        (ops.verify_attention_jnp(a["qv"], a["k"], a["v"], a["kn"], a["vn"],
                                  ops.ring_positions(pos - 1, C), pos),
         ops.verify_attention_jnp(a["qv"], a["k"], a["v"], a["kn"], a["vn"],
                                  ops.ring_positions(pos - 1, C), pos,
                                  n_splits=n_splits)),
    ]
    ppos = jnp.asarray([3, 79], jnp.int32)
    bt2 = a["bt"][:2]
    pairs += [
        (ops.paged_decode_attention_jnp(a["q1"], a["kp"], a["vp"], bt2, ppos),
         ops.paged_decode_attention_jnp(a["q1"], a["kp"], a["vp"], bt2, ppos,
                                        n_splits=n_splits)),
        (ops.paged_verify_attention_jnp(a["qv"], a["kp"], a["vp"], a["kn"],
                                        a["vn"], bt2, ppos),
         ops.paged_verify_attention_jnp(a["qv"], a["kp"], a["vp"], a["kn"],
                                        a["vn"], bt2, ppos,
                                        n_splits=n_splits)),
    ]
    for one, many in pairs:
        assert float(jnp.max(jnp.abs(one - many))) < TOL


def test_policy_kv_splits_dispatch():
    """``kv_splits`` on the policy reaches every backend and changes no
    output; ``auto`` equals an explicit 1 on an oversubscribed host."""
    a = _rng_arrays(2, 4, 2, seed=13)
    pos = jnp.int32(_POS["wrap"])
    outs = []
    for kv_splits in ("auto", 1, 4):
        for decode in ("jnp", "pallas_interpret"):
            pol = ops.KernelPolicy(decode=decode, kv_splits=kv_splits,
                                   decode_k_chunk=BLOCK_K)
            outs.append(ops.decode_attention(a["q1"], a["k"], a["v"], pos,
                                             policy=pol))
    base = outs[0]
    for o in outs[1:]:
        assert float(jnp.max(jnp.abs(o - base))) < TOL


def test_choose_kv_splits_occupancy_model():
    # oversubscribed grid: never split (1 = today's behaviour, exactly)
    assert ops.choose_kv_splits(8, 32768, 4, 4) == 1
    # single block: nothing to split
    assert ops.choose_kv_splits(1, 256, 4, 64) == 1
    # deep cache, low batch, idle cores: split to ~2x coverage
    assert ops.choose_kv_splits(1, 32768, 4, 8) == 4
    # never more splits than blocks
    assert ops.choose_kv_splits(1, 512, 1, 64, block=256) <= 2
    # cap at max_splits
    assert ops.choose_kv_splits(1, 10 ** 6, 1, 512) == 16


def test_choose_kv_splits_mla_grid():
    """MLA decode grids have q_heads = 1: all 128 heads share ONE latent
    row per token, so the page DMA is shared and the occupancy cell count
    is just ``batch * splits`` — the deepest underfill in the zoo at low
    batch, exactly where splitting pays."""
    # B=1, one shared kv row, 8 executors: split hard to cover the machine
    assert ops.choose_kv_splits(1, 32768, 1, 8) == 16
    # moderate batch still underfills (8 cells < 2*8): split a little
    assert ops.choose_kv_splits(8, 32768, 1, 8) == 2
    # high batch oversubscribes even at one kv head: never split
    assert ops.choose_kv_splits(16, 32768, 1, 8) == 1
    # never more splits than latent pages
    assert ops.choose_kv_splits(1, 8 * PS, 1, 64, block=PS) <= 8


def test_effective_kv_len_clips_windowed_caches():
    """The split heuristic must see the CLIPPED length on windowed layers:
    a deep sliding-window position is a shallow sweep, and splitting it
    only adds merge traffic."""
    assert ops.effective_kv_len(32768, 512) == 512
    assert ops.effective_kv_len(100, 512) == 100    # min(pos, window)
    assert ops.effective_kv_len(100, 0) == 100      # full attention
    deep_full = ops.choose_kv_splits(1, 32768, 4, 8)
    deep_win = ops.choose_kv_splits(
        1, ops.effective_kv_len(32768, 512), 4, 8, block=256)
    assert deep_full > 1
    # 512 keys = 2 blocks of 256: at most 2 splits, far below the full
    # sweep's choice — the clip is what keeps windowed layers cheap
    assert deep_win <= 2 < deep_full


def test_k_pos_fallback_warns_once():
    a = _rng_arrays(1, 4, 2, seed=17)
    pos = jnp.int32(30)
    k_pos = ops.ring_positions(pos, C)
    pol = ops.KernelPolicy(decode="pallas_interpret", decode_k_chunk=BLOCK_K)
    ops._KPOS_FALLBACK_WARNED.discard("decode_attention")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        ops.decode_attention(a["q1"], a["k"], a["v"], pos, k_pos=k_pos,
                             policy=pol)
        ops.decode_attention(a["q1"], a["k"], a["v"], pos, k_pos=k_pos,
                             policy=pol)
    hits = [w for w in rec if "Pallas decode" in str(w.message)]
    assert len(hits) == 1 and issubclass(hits[0].category, RuntimeWarning)


# --------------------------------------------------------------------------
# merge kernel in isolation
# --------------------------------------------------------------------------
def test_merge_kernel_matches_ref():
    rng = np.random.default_rng(23)
    partial = jnp.asarray(rng.standard_normal((2, 4, 3, Q, DV)), jnp.float32)
    lse = jnp.asarray(rng.standard_normal((2, 4, 3, Q)), jnp.float32)
    # one split per row empty, as a ragged stage-1 would leave it
    lse = lse.at[:, :, 2, :].set(ref.NEG_INF)
    partial = partial.at[:, :, 2].set(0.0)
    got = da.merge_kv_splits_pallas(partial, lse, out_dtype=jnp.float32,
                                    interpret=True)
    want = ref.merge_kv_splits_ref(partial, lse)
    assert got.shape == (2, 4, Q, DV)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
