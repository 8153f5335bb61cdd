"""Kernel and program names on the device trace, the two readers that use
them, and the readings of the engine's own spans, on synthetic events and
on the trace recorded before kernels had names."""
import dataclasses

import pytest

from harness import engine_spans, programs, records, spec, trace

OLD = spec.BENCH / "tests" / "data" / "trace_smollm_v5e.json.gz"


def _op(kernel, shape="bf16[8,32,1,64]"):
    return (f"%{kernel}.3 = {shape}{{3,2,1,0}} custom-call(s32[8,128]{{1,0}}"
            f" %a), custom_call_target=\"tpu_custom_call\"")


FUSION = "%fusion.7 = bf16[8,2048]{1,0} fusion(bf16[8,2048]{1,0} %p)"


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    parent: int = -1
    rid: int | None = None
    counts: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self):
        return self.end - self.start


class Run:
    def __init__(self, op_events, t_start, t_stop, chunks=(), firsts=(),
                 layers=2):
        self.trace = trace.Summary(t_stop - t_start, 0.0, {},
                                   sorted(op_events, key=lambda e: e[1]),
                                   [], t_start, t_stop)
        self.window = records.Window(
            start=t_start, seconds=t_stop - t_start,
            reqs=[records.Req(rid=i, due=0.0, prompt_len=1,
                              max_new_tokens=1, first=f)
                  for i, f in enumerate(firsts)],
            chunks=list(chunks), spans=[], late=[])
        self.sizes = {"layers": layers}


def test_kernel_names_come_from_the_instruction_name():
    assert programs.kernel(_op("paged_decode_sweep")) == "paged_decode_sweep"
    assert programs.kernel(_op("closed_call")) == "closed_call"
    assert programs.kernel(FUSION) is None


def test_busy_between_reads_the_union():
    merged = programs.busy_intervals([("a", 0.0, 2.0), ("b", 1.0, 3.0),
                                      ("c", 5.0, 6.0), ("d", 5.2, 5.4)])
    assert merged == [(0.0, 3.0), (5.0, 6.0)]
    assert programs.busy_between(merged, 2.5, 5.5) == pytest.approx(1.0)
    assert programs.busy_between(merged, -1.0, 10.0) == pytest.approx(4.0)
    assert programs.busy_between(merged, 3.0, 5.0) == 0.0


def _sweep(t, layers=2, op=0.010, kernel="paged_verify_sweep"):
    """One suffix sweep from ``t``: per layer a fusion then the kernel."""
    evs = []
    for _ in range(layers):
        evs += [(FUSION, t, t + op), (_op(kernel, "bf16[16,9,16,64]"),
                                      t + op, t + 2 * op)]
        t += 2 * op
    evs.append((FUSION, t, t + op))            # the head, after the layers
    return evs, t + op


def test_suffix_sweep_reader_times_one_sweep_between_kernel_calls():
    read = spec.metric_reader("suffix_sweep_ms.chat")
    evs, t = [(FUSION, 0.0, 0.020)], 0.020     # the join's page copy
    for _ in range(3):                         # join 1: three sweeps
        sw, t = _sweep(t + 0.001)              # 1 ms of host between
        evs += sw
    first_1 = t + 0.002
    t = first_1 + 0.050
    evs.append((FUSION, t, t + 0.030))         # join 2's page copy
    t += 0.030
    for _ in range(2):
        sw, t = _sweep(t + 0.001, kernel="paged_verify_partials")
        evs += sw
    run = Run(evs, -0.1, t + 0.1, firsts=[first_1, t + 0.01])
    # a sweep is 5 ops of 10 ms; 2 + 1 periods inside joins, none across
    assert read(run) == pytest.approx(50.0)
    run.sizes["layers"] = 3                    # calls are not whole sweeps
    assert read(run) is None


def test_decode_outside_reader_leaves_out_the_kernels_and_the_joins():
    read = spec.metric_reader("decode_outside_attn_ms.batch")
    evs = [(FUSION, 0.0, 0.5)]                 # a join's prefill
    first = 0.51
    evs += [("%while.1 = (s32[]) while(...)", 0.60, 1.40),
            (FUSION, 0.60, 0.70), (_op("paged_decode_sweep"), 0.70, 1.30),
            (FUSION, 1.30, 1.40)]
    evs += [(FUSION, 1.60, 1.70), (_op("paged_decode_partials"), 1.70, 2.0),
            (_op("split_kv_merge"), 2.0, 2.1), (FUSION, 2.1, 2.2)]
    chunks = [records.Chunk(end=1.45, wall_s=0.9, n_active=8,
                            tokens_kept=64, steps=8),
              records.Chunk(end=2.25, wall_s=0.7, n_active=8,
                            tokens_kept=64, steps=8)]
    run = Run(evs, 0.0, 3.0, chunks, firsts=[first])
    # 0.2 s outside the kernels in each chunk, over 16 steps
    assert read(run) == pytest.approx(1000 * 0.4 / 16)
    run.trace.t_start = 0.58                   # the first dispatch is out
    assert read(run) == pytest.approx(1000 * 0.2 / 8)


def test_readers_find_nothing_on_a_trace_without_kernel_names():
    events = trace.load_events(str(OLD))
    marks = [m * 1e-9 for m in events["marks"]]
    s = trace.reduce(events, marks, 0.060, 0.285, [])
    assert any(programs.kernel(n) == "closed_call" for n, _, _ in
               s.op_events)
    run = Run([], 0.0, 1.0, firsts=[0.1], layers=30)
    run.trace = s
    run.window.chunks = [records.Chunk(end=0.284, wall_s=0.02, n_active=12,
                                       tokens_kept=96, steps=8)]
    for name in ("suffix_sweep_ms.chat", "decode_outside_attn_ms.batch"):
        assert spec.metric_reader(name)(run) is None, name


def _req(rid, due, admitted):
    return records.Req(rid=rid, due=due, prompt_len=1, max_new_tokens=1,
                       admitted=admitted)


def test_join_and_wait_readings_of_program_spans():
    spans = [Span("poll", 0.0, 0.1), Span("join", 0.1, 1.1, rid=1),
             Span("suffix_prefill", 0.2, 1.0, parent=1),
             Span("join", 1.1, 1.6, rid=2), Span("chunk", 1.6, 3.0),
             Span("join", 3.0, 3.4, rid=3)]
    reqs = [_req(1, 0.0, 0.05), _req(2, 0.5, 1.05), _req(3, 1.0, 3.0)]
    assert engine_spans.join_p50_s(spans, reqs) == pytest.approx(0.5)
    # request 2 waits 0.55 s, all inside join 1; request 3 waits 2 s, of
    # which join 1 covers 0.1 and join 2 covers 0.5
    share = engine_spans.wait_in_joins_share(spans, reqs)
    assert share == pytest.approx(100 * (0.55 + 0.6) / (0.05 + 0.55 + 2.0))
    assert engine_spans.wait_in_joins_share(spans, [_req(4, 1.0, 1.0)]) \
        is None


def test_idle_gaps_are_named_by_the_innermost_program_span():
    spans = [Span("poll", 0.0, 0.1), Span("join", 0.1, 1.0, rid=1),
             Span("suffix_prefill", 0.1, 0.8, parent=1),
             Span("chunk", 1.0, 2.0), Span("harvest", 1.8, 2.0, parent=3)]
    waits = [records.Span("admission wait", 2.0, 2.5)]
    evs = [(FUSION, a, b) for a, b in [(0.15, 0.7), (0.78, 0.82),
                                         (0.85, 0.95), (1.05, 1.8),
                                         (1.85, 1.9), (2.6, 2.7)]]
    got = dict(engine_spans.gaps(evs, 0.0, 3.0, spans, waits))
    assert got == pytest.approx({
        "poll": 0.15, "join/suffix_prefill": 0.08, "join": 0.03,
        "chunk": 0.1, "chunk/harvest": 0.05, "admission wait": 0.7,
        "engine host": 0.3})
    old = trace.reduce({"ops": [[n, a * 1e9, (b - a) * 1e9]
                                for n, a, b in evs], "marks": [0.0]},
                       [0.0], 0.0, 3.0, waits)
    assert sum(got.values()) == pytest.approx(sum(s for _, s in old.gaps))


def test_collect_assigns_each_op_its_program(tmp_path):
    """A TPU v5e trace of one jitted ``suffix_step`` that calls a Pallas
    kernel named ``probe_sweep`` and a matmul, under an annotation
    ``join``: op events carry no program stat there, the module line does."""
    import gzip
    path = tmp_path / "probe.xplane.pb"
    with gzip.open(spec.BENCH / "tests" / "data" /
                   "named_kernel_v5e.xplane.pb.gz", "rb") as f:
        path.write_bytes(f.read())
    events = programs.collect(str(path))
    assert [programs.kernel(n) for n, _, _ in events["ops"]] == \
        ["probe_sweep", None]
    assert events["programs"] == ["jit_suffix_step"] * 2
    assert "join" in {n for n, _, _ in events["annotations"]}
    assert events["marks"] == []


def _recorded(cell, key):
    """A piece of a traced window recorded on a TPU v5e by
    ``bench/tools/engine_spans.py --spans 1 --trace 1 --record``: its
    events, the harness's records, the engine's spans, and a reader's
    ``Run`` on it."""
    import gzip
    import json
    with gzip.open(spec.BENCH / "tests" / "data" /
                   f"spans_{cell}_v5e.json.gz", "rt") as f:
        d = json.load(f)[key]
    run = Run([], d["t_start"], d["t_stop"],
              [records.Chunk(*c) for c in d["chunks"]], d["firsts"],
              d["sizes"]["layers"])
    run.trace = trace.reduce(d["events"], d["host_marks"], d["t_start"],
                             d["t_stop"], [])
    spans = [Span(n, a, b, p, rid, counts)
             for n, a, b, p, rid, counts in d["spans"]]
    off = programs.clock_offset(d["events"], d["host_marks"])
    return d, run, spans, off


def _program_ops(d, off, program, span):
    return [(n, s * 1e-9 + off, (s + dur) * 1e-9 + off)
            for (n, s, dur), p in zip(d["events"]["ops"],
                                      d["events"]["programs"])
            if p == program and span.start <= s * 1e-9 + off < span.end]


def test_suffix_sweep_reader_matches_the_program_on_a_recorded_join():
    d, run, spans, off = _recorded("chat", "join")
    join, = [s for s in spans if s.name == "join"
             and d["t_start"] <= s.start and s.end <= d["t_stop"]]
    assert join.counts["sweeps"] >= 3
    own = programs.busy_intervals(_program_ops(d, off, "jit_suffix_step",
                                               join))
    per_sweep = 1000 * sum(b - a for a, b in own) / join.counts["sweeps"]
    got = spec.metric_reader("suffix_sweep_ms.chat")(run)
    assert got == pytest.approx(per_sweep, rel=0.01)
    assert 100 < got < 250


@pytest.mark.parametrize("cell", ["chat", "batch"])
def test_decode_outside_reader_matches_the_program_on_a_recorded_chunk(cell):
    d, run, spans, off = _recorded(cell, "chunk")
    chunk, = [s for s in spans if s.name == "chunk"
              and d["t_start"] <= s.start and s.end <= d["t_stop"]]
    ops = _program_ops(d, off, "jit_decode_loop", chunk)
    kern = sum(b - a for n, a, b in ops if programs.kernel(n) in
               ("paged_decode_sweep", "paged_decode_partials",
                "split_kv_merge"))
    assert kern > 0
    busy = sum(b - a for a, b in programs.busy_intervals(ops))
    steps = run.window.chunks[0].steps
    got = spec.metric_reader("decode_outside_attn_ms.batch")(run)
    assert got == pytest.approx(1000 * (busy - kern) / steps, rel=0.01)


@pytest.mark.parametrize("cell,key", [("chat", "join"), ("chat", "chunk"),
                                      ("batch", "chunk")])
def test_recorded_spans_line_up_with_their_annotations(cell, key):
    d, run, spans, off = _recorded(cell, key)
    res = programs.alignment(d["events"], off, spans, d["t_start"],
                             d["t_stop"])
    assert len(res) >= 3 and max(map(abs, res)) < 1e-3
    got = engine_spans.gaps(run.trace.op_events, d["t_start"], d["t_stop"],
                            spans, [])
    assert sum(s for _, s in got) == pytest.approx(
        sum(s for _, s in run.trace.gaps))
    assert {n.split("/")[0] for n, _ in got} <= {"join", "chunk", "poll",
                                                "grow_pages", "engine host"}
