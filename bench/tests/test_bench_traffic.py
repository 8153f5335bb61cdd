"""The general traffic generator: determinism, the same work for every
seed, and mixes found by name."""
import json

import numpy as np
import pytest

from harness import spec, traffic

MIXES = ["chat", "batch"]


def _mix(name):
    mix = json.loads((spec.BENCH / "traffic" / f"{name}.json").read_text())
    if mix["rate"]["knee_req_per_s"] is None:      # not yet measured
        mix["rate"]["knee_req_per_s"] = 5.0
    return mix


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_trace(name):
    mix = _mix(name)
    a, wa = traffic.make_trace(mix, seed=2**31 + 5, seconds=20, vocab=1000)
    b, wb = traffic.make_trace(mix, seed=2**31 + 5, seconds=20, vocab=1000)
    for x, y in zip(a + wa, b + wb):
        assert x.arrival_s == y.arrival_s
        assert x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work_in_another_order(name):
    mix = _mix(name)
    a, _ = traffic.make_trace(mix, seed=1, seconds=20, vocab=1000)
    b, _ = traffic.make_trace(mix, seed=2, seconds=20, vocab=1000)
    assert len(a) == len(b) == round(traffic.rate_per_s(mix) * 20) \
        + mix.get("backlog", 0)
    assert sum(p.arrival_s == 0 for p in a) == mix.get("backlog", 0)
    assert sorted(p.max_new_tokens for p in a) == \
        sorted(p.max_new_tokens for p in b)
    # the same requests at the same times: only the token ids differ
    assert [p.arrival_s for p in a] == [p.arrival_s for p in b]
    assert [p.max_new_tokens for p in a] == [p.max_new_tokens for p in b]
    assert [len(p.prompt) for p in a] == [len(p.prompt) for p in b]
    assert [p.pooled for p in a] == [p.pooled for p in b]
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert len(set(p.max_new_tokens for p in a)) > 1
    assert all(0 <= p.arrival_s < 20 for p in a)
    assert all(len(p.prompt) + p.max_new_tokens <= mix["max_context"]
               for p in a)


def test_chat_pools_repeat_and_warmup_asks_every_one():
    mix = _mix("chat")
    window, warm = traffic.make_trace(mix, seed=3, seconds=51, vocab=49152)
    n = len(window)
    with_doc = [p for p in window if "document" in p.pooled]
    assert len(with_doc) == round(0.5 * n)
    uses = np.bincount([p.pooled["document"] for p in with_doc], minlength=8)
    assert uses.max() - uses.min() <= 1
    sys_heads = {p.prompt[:512].tobytes() for p in window}
    assert {p.prompt[:512].tobytes() for p in warm} == sys_heads
    assert len(sys_heads) == 8
    docs = {p.prompt[512:512 + 256].tobytes() for p in with_doc}
    warm_docs = {p.prompt[512:512 + 256].tobytes() for p in warm
                 if "document" in p.pooled}
    assert warm_docs == docs and len(docs) == 8


def test_setup_trace_draws_the_window_pool_items():
    mix = json.loads((spec.BENCH / "tests" / "data" / "tiny_chat.json")
                     .read_text())
    window, warm = traffic.make_trace(mix, seed=3, seconds=1, vocab=1000)
    ps = {p.rid: p for p in window}
    for w in warm:
        for pool, item in w.pooled.items():
            twin = next(p for p in ps.values() if p.pooled.get(pool) == item)
            start = 32 if pool == "document" else 0
            n = 16
            np.testing.assert_array_equal(w.prompt[start:start + n],
                                          twin.prompt[start:start + n])


def test_quantiles_are_clipped_heavy_tails():
    d = {"dist": "lognormal", "median": 64, "sigma": 0.6, "lo": 16, "hi": 256}
    q = traffic.quantiles(d, 1001)
    assert q.min() >= 16 and q.max() <= 256 and q[500] == 64
    assert q.mean() > 64


def test_a_new_mix_is_found_by_name(tmp_path):
    mix = _mix("batch")
    mix["rate"] = {"knee_req_per_s": 2.0, "share_of_knee": 1.0}
    (tmp_path / "newmix.json").write_text(json.dumps(mix))
    bench = json.loads((spec.BENCH / "tests" / "data" / "BENCHMARK.json")
                       .read_text())
    bench["workloads"].append({"name": "tiny.newmix", "config": "tiny",
                               "traffic": "newmix", "chips": 1, "why": "t"})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    cell = spec.load_cell(spec.BENCH.parent, "tiny.newmix",
                          benchmark_file=path, traffic_dir=tmp_path)
    assert traffic.rate_per_s(cell.traffic) == 2.0
    assert [m["name"] for m in cell.end_to_end][:2] == ["ttft_p90_s",
                                                        "tpot_p90_s"]


def test_documents_are_asked_under_their_own_system_prompt():
    mix = _mix("chat")
    window, warm = traffic.make_trace(mix, seed=7, seconds=51, vocab=49152)
    pairs = {(p.pooled["system"], p.pooled["document"]) for p in warm + window
             if "document" in p.pooled}
    assert all(s == d % 8 for s, d in pairs)
    # every pair the window asks was asked in set-up, so it is a cached prefix
    warm_pairs = {(p.pooled["system"], p.pooled["document"]) for p in warm
                  if "document" in p.pooled}
    assert pairs <= warm_pairs
    heads = {tuple(p.prompt[:512 + 256]) for p in window
             if "document" in p.pooled}
    assert heads <= {tuple(p.prompt[:512 + 256]) for p in warm}
