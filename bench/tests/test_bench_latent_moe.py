"""The DeepSeek cell's two kernel readers against hand counts, on a
recorded-shape window: the latent sweep's and the grouped matmuls'
operations and bytes, and which trace ops each one times."""
import types

import pytest

from harness import records, spec, trace

S = {"d": 8, "hq": 2, "r_kv": 4, "dr": 2, "moe_ff": 6, "held": 2,
     "top_k": 3, "layers": 3, "dense_layers": 1}
ENGINE = {"n_slots": 2, "page_size": 4, "max_len": 64, "decode_chunk": 2}
PEAKS = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
CUSTOM = 'custom_call_target="tpu_custom_call"'
MLA = ("%mla_paged_decode_sweep.3 = bf16[2,2,4]{2,1,0} custom-call("
       "s32[2,16]{1,0} %a, s32[2]{0} %b), " + CUSTOM)
GATE = ("%ragged-dot-none.1 = bf16[6,6]{1,0} custom-call(s32[1]{0} %m, "
        "bf16[6,8]{1,0} %x, bf16[2,8,6]{2,1,0} %w), " + CUSTOM)
DOWN = ("%ragged-dot-none.2 = bf16[6,8]{1,0} custom-call(s32[1]{0} %m, "
        "bf16[6,6]{1,0} %h, bf16[2,6,8]{2,1,0} %w), " + CUSTOM)
PREFILL = ("%ragged-dot-none.9 = bf16[96,6]{1,0} custom-call(s32[1]{0} %m, "
           "bf16[96,8]{1,0} %x, bf16[2,8,6]{2,1,0} %w), " + CUSTOM)


def _run():
    """Two requests of prompt 5 joining before the first of two chunks of
    two steps, each making 5 tokens (two chunks), all traced."""
    reqs = [records.Req(rid=i, due=0.0, prompt_len=5, max_new_tokens=5,
                        admitted=0.0, first=0.5, finish=2.0, n_tokens=5)
            for i in range(2)]
    chunks = [records.Chunk(end=1.0, wall_s=0.4, n_active=2, tokens_kept=4,
                            steps=2),
              records.Chunk(end=2.0, wall_s=0.4, n_active=2, tokens_kept=4,
                            steps=2)]
    report = types.SimpleNamespace(n_chunks=2, expert_rows_decode=40)
    window = records.Window(0.0, 3.0, reqs, chunks, [], [], report)
    ops = [(MLA, 0.6, 0.7), (GATE, 0.7, 0.75), (GATE, 0.75, 0.8),
           (DOWN, 0.8, 0.85), (PREFILL, 0.3, 0.4)]
    summary = trace.Summary(3.0, 0.4, {}, ops, [], 0.0, 3.0)
    return types.SimpleNamespace(sizes=S, engine=ENGINE, window=window,
                                 peaks=PEAKS, trace=summary)


def test_latent_sweep_hand_count():
    read = spec.metric_reader("mla_roofline.long_batch")
    need = spec.load_module(spec.BENCH / "metrics"
                            / "mla_roofline.long_batch.py").need
    # 5 positions over pages of 4 -> 2 pages of 6-lane rows; q 2x6, out 2x4
    assert need(5, hq=2, r_kv=4, dr=2, page_size=4) == (
        2 * 2 * (6 + 4) * 5, 2 * 4 * 6 * 2 + 2 * (6 + 4) * 2)
    # chunk k attends 6 + 2k, then one more a step, for both slots
    f = b = 0
    for ctx in (6, 7, 8, 9):
        fi, bi = need(ctx, hq=2, r_kv=4, dr=2, page_size=4)
        f, b = f + 2 * fi, b + 2 * bi
    want = 100.0 * max(3 * f / 1e3, 3 * b / 1e3) / 0.1
    assert read(_run()) == pytest.approx(want)


def test_grouped_matmul_hand_count():
    read = spec.metric_reader("moe_roofline.long_batch")
    # 40 rows over 4 decode steps, 4 steps traced; 2 expert layers
    rows, steps, layers = 40.0, 4, 2
    f = 6 * rows * 8 * 6
    b = 3 * 2 * 8 * 6 * 2 * steps * layers + rows * 6 * (8 + 6)
    # the decode calls take 0.15 s; the prefill-shaped call is not counted
    want = 100.0 * max(f / 1e3, b / 1e3) / 0.15
    assert read(_run()) == pytest.approx(want)


def test_readers_are_silent_without_a_trace():
    run = _run()
    run.trace = None
    for name in ("mla_roofline.long_batch", "moe_roofline.long_batch"):
        assert spec.metric_reader(name)(run) is None
