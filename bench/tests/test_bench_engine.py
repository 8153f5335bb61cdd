"""The harness driving the real engine at a tiny size on the CPU.

Covers the open-loop admission against ``ServeEngine`` itself, the
correctness check and its control (the reference in float8 put in the
program's place must fail it), and runs with the timed path broken
underneath, which must come out not correct."""
import time

import numpy as np
import pytest

from harness import cell, check, spec, traffic

ROOT = spec.BENCH.parent
DATA = spec.BENCH / "tests" / "data"
SECONDS = 1.0


def _cell():
    return spec.load_cell(ROOT, "tiny.chat",
                          benchmark_file=DATA / "BENCHMARK.json",
                          traffic_dir=DATA)


@pytest.fixture(scope="module")
def served():
    c = _cell()
    seed = 2**31 + 11
    s = cell.Session(ROOT, c, seed, allow_cpu=True)
    planned, warm = traffic.make_trace(c.traffic, seed=seed, seconds=SECONDS,
                                       vocab=s.sizes["vocab"])
    s.warm(planned, warm)
    w = s.window(planned, SECONDS)
    s.free()
    return c, s, planned, w


def test_open_loop_admits_fifo_and_serves_every_request(served):
    c, s, planned, w = served
    assert len(w.reqs) == len(planned) == round(
        traffic.rate_per_s(c.traffic) * SECONDS)
    order = sorted(w.reqs, key=lambda r: r.admitted)
    assert [r.rid for r in order] == sorted(r.rid for r in w.reqs)
    assert all(r.admitted >= r.due for r in w.reqs)
    assert all(r.due <= r.admitted <= r.first <= r.finish for r in w.reqs)
    assert all(r.n_tokens == r.max_new_tokens for r in w.reqs)
    assert w.report.prefill_tokens_saved > 0          # the prefix path ran
    assert all(late < 0.05 for late in w.late)


def test_sound_run_passes_and_control_fails(served):
    c, s, planned, w = served
    sound = check.compare(s.ref, c.config, s.sizes, s.key, w.reqs,
                          seed=s.seed, failed=0)
    control = check.compare(s.ref, c.config, s.sizes, s.key, w.reqs,
                            seed=s.seed, failed=0, chooser="fp8")
    assert sound.correct, sound.checks
    assert not control.correct, control.checks
    assert control.checks["max_logit_gap"]["value"] > \
        3 * sound.checks["max_logit_gap"]["value"]


def test_sample_holds_the_longest_request(served):
    _, s, _, w = served
    picked = check.sample(w.reqs, seed=s.seed, min_tokens=48)
    longest = max(w.reqs, key=lambda r: r.prompt_len + r.n_tokens)
    assert picked[0].prompt_len + picked[0].n_tokens == \
        longest.prompt_len + longest.n_tokens
    assert sum(r.n_tokens for r in picked) >= 48


def _run(engine_hook, seed):
    return cell.run(ROOT, "tiny.chat", seed=seed, seconds=SECONDS,
                    trace=False, t_process=time.perf_counter(),
                    allow_cpu=True, benchmark_file=DATA / "BENCHMARK.json",
                    traffic_dir=DATA, engine_hook=engine_hook)


def test_a_token_altered_where_it_is_produced_is_not_correct():
    def alter(engine):
        harvest = engine._harvest
        done = []

        def broken(toks, t0):
            toks = np.array(toks)
            if not done and engine.scheduler.active_slots():
                slot = engine.scheduler.active_slots()[0]
                toks[slot, 0] = (toks[slot, 0] + 1) % 256
                done.append(slot)
            return harvest(toks, t0)

        engine._harvest = broken

    out = _run(alter, 2**31 + 12)
    assert out["correct"] is False, out["checks"]


def test_a_decode_step_that_leaves_the_cache_unchanged_is_not_correct(
        monkeypatch):
    from repro.models import attention

    def unchanged(params, x, pos, cache_kv, *a, **k):
        out, _ = real(params, x, pos, cache_kv, *a, **k)
        return out, cache_kv

    real = attention.gqa_decode_paged

    def stale(engine):
        monkeypatch.setattr(attention, "gqa_decode_paged", unchanged)
        engine._loops.clear()        # recompile the loop with the fault

    out = _run(stale, 2**31 + 13)
    assert out["correct"] is False, out["checks"]


def test_result_line_holds_the_contract_keys():
    out = _run(None, 2**31 + 14)
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"ttft_p90_s", "tpot_p90_s",
                                   "tokens_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
