"""The trace reduction on a small trace recorded on a TPU v5e (SmolLM-135M,
16 slots x 2048: two joins' prefill and inject, then the start of a fused
decode chunk), and the attention roofline reader on it."""
import numpy as np
import pytest

from harness import records, spec, trace

DATA = spec.BENCH / "tests" / "data" / "trace_smollm_v5e.json.gz"
OFF = 1000.0                       # harness clock = trace seconds + OFF


@pytest.fixture(scope="module")
def events():
    return trace.load_events(str(DATA))


def _reduce(events, spans=(), t0=0.060, t1=0.285):
    marks = [OFF + m * 1e-9 for m in events["marks"]]
    return trace.reduce(events, marks, OFF + t0, OFF + t1, list(spans))


def _grid_busy(events, t0, t1, step=1e-6):
    """Busy seconds by marking a microsecond grid: no interval merging."""
    n = int(round((t1 - t0) / step))
    busy = np.zeros(n, bool)
    for _, s, d in events["ops"]:
        a = int(np.floor((s * 1e-9 - t0) / step))
        b = int(np.ceil((s * 1e-9 + d * 1e-9 - t0) / step))
        busy[max(a, 0):max(min(b, n), 0)] = True
    return busy.sum() * step


def test_busy_is_the_union_of_device_ops(events):
    s = _reduce(events)
    assert s.window_s == pytest.approx(0.225)
    assert s.busy_s == pytest.approx(_grid_busy(events, 0.060, 0.285),
                                     abs=2e-4)
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_share() < 100


def test_self_times_add_up_to_busy_time(events):
    s = _reduce(events)
    assert sum(s.ops.values()) == pytest.approx(s.busy_s, rel=0.02)
    assert all(v >= -1e-9 for v in s.ops.values())
    top = s.breakdown["device_ops"]
    assert len(top) == 10 and top[0][1] >= top[-1][1]
    assert any("tpu_custom_call" in name for name, _ in top)


def test_gaps_are_named_by_the_covering_span(events):
    bare = _reduce(events)
    assert [name for name, _ in bare.gaps] == ["engine host"]
    spans = [records.Span("join", OFF + 0.060, OFF + 0.200),
             records.Span("chunk", OFF + 0.200, OFF + 0.285)]
    named = dict(_reduce(events, spans).gaps)
    assert set(named) <= {"join", "chunk"}
    assert sum(named.values()) == pytest.approx(
        sum(s for _, s in bare.gaps))


def test_clock_markers_map_the_trace_onto_the_harness_clock(events):
    shifted = trace.reduce(events, [5.0 + m * 1e-9 for m in events["marks"]],
                           5.060, 5.285, [])
    assert shifted.busy_s == pytest.approx(_reduce(events).busy_s)
    with pytest.raises(RuntimeError):
        trace.reduce({"ops": events["ops"], "marks": []}, [], 0.0, 1.0, [])


def test_short_names():
    assert trace.short_name('%closed_call.29 = bf16[16,9,1,64]{3,2,1,0} '
                            'custom-call(s32[16,128]{1,0} %a), '
                            'custom_call_target="tpu_custom_call"') == \
        "closed_call.29 tpu_custom_call bf16[16,9,1,64]{3,2,1,0}"
    assert trace.short_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") \
        == "fusion.3 f32[8]{0}"


def test_roofline_reader_picks_the_paged_decode_sweep(events):
    s = _reduce(events)
    read = spec.metric_reader("attn_roofline.batch")
    kernel = [e for e in s.op_events if "tpu_custom_call" in e[0]]
    assert len(kernel) == 6

    class Run:
        pass

    run = Run()
    run.trace = s
    run.peaks = spec.peaks("TPU v5 lite")
    run.sizes = {"hq": 9, "hkv": 3, "hd": 64, "layers": 30}
    run.engine = {"n_slots": 16, "page_size": 16, "max_len": 2048,
                  "decode_chunk": 8}
    # one chunk inside the traced interval with 12 active slots at 700
    # cached positions: only the kernel calls the trace holds are timed, so
    # the share is of those calls against one step's need per call
    run.window = records.Window(
        start=OFF, seconds=10.0,
        reqs=[records.Req(rid=i, due=OFF, prompt_len=700, max_new_tokens=9,
                          first=OFF + 0.1, n_tokens=9) for i in range(12)],
        chunks=[records.Chunk(end=OFF + 0.284, wall_s=0.02, n_active=12,
                              tokens_kept=96, steps=8)],
        spans=[], late=[])
    share = read(run)
    assert share is not None and 0 < share < 100
    run.window.chunks[0].n_active = 11          # bookkeeping disagrees
    assert read(run) is None
