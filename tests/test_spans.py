"""The engine's span recorder: the recorder alone, then the spans a small
engine records — what they nest under, what they count, and that recording
them changes no token."""
import dataclasses
import glob

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import transformer as tfm
from repro.serving import (EngineConfig, ServeEngine, SpanRecorder,
                           batch_trace, poisson_trace)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def test_recorder_nesting_self_time_and_counts():
    rec = SpanRecorder(clock=FakeClock())
    with rec.span("join", rid=7) as join:          # t 1
        with rec.span("suffix_prefill") as s:      # t 2
            s.counts["sweeps"] = 3
        rec.open("sample_first")                   # t 4
        rec.close(synced=1)                        # t 5
    assert join.end == 6.0
    rec.open("chunk", start=10.0)
    rec.open("dispatch", start=10.0)
    rec.switch("device_wait", 12.5)
    rec.close(end=14.0)
    rec.close(end=15.0, n_active=2)
    names = [s.name for s in rec.spans]
    assert names == ["join", "suffix_prefill", "sample_first", "chunk",
                     "dispatch", "device_wait"]
    assert [s.parent for s in rec.spans] == [-1, 0, 0, -1, 3, 3]
    assert rec.spans[0].rid == 7 and rec.spans[1].rid is None
    assert rec.spans[1].counts == {"sweeps": 3}
    assert rec.spans[2].counts == {"synced": 1}
    assert rec.spans[3].counts == {"n_active": 2}
    # join 1..6 holds children 2..3 and 4..5
    assert rec.self_seconds(0) == pytest.approx(5.0 - 1.0 - 1.0)
    assert rec.spans[4].seconds + rec.spans[5].seconds == \
        rec.spans[3].seconds - 1.0
    assert rec.self_seconds(3) == pytest.approx(1.0)


def test_recorder_closes_what_a_raising_block_left_open():
    rec = SpanRecorder(clock=FakeClock())
    with pytest.raises(ValueError):
        with rec.span("chunk"):
            rec.open("dispatch")
            raise ValueError("boom")
    assert all(s.end > s.start for s in rec.spans)
    with rec.span("poll"):
        pass
    assert rec.spans[-1].parent == -1
    rec.open("left open")
    rec.clear()
    assert rec.spans == []


def test_spans_annotate_a_profiler_trace(tmp_path):
    rec = SpanRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("join"):
            jax.block_until_ready(jax.numpy.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    names = {e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host") for line in p.lines
             for e in line.events}
    assert "join" in names


@pytest.fixture(scope="module")
def smollm():
    cfg = get_arch("smollm-135m").smoke
    params, _ = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


ECFG = EngineConfig(n_slots=2, page_size=4, max_len=64, decode_chunk=4,
                    prefill_chunk=4)


def _shared_prefix_trace(cfg):
    # an 11-token shared head over pages of 4 ends mid-page: restored joins
    # run the copy-on-write copy and chunked suffix sweeps; the first of
    # each pool prefills cold
    return poisson_trace(6, rate_per_step=0.3, seed=7,
                         vocab_size=cfg.vocab_size, prompt_len=(3, 9),
                         max_new_tokens=(4, 10), shared_prefix_len=11,
                         prompt_pools=2)


def _children(rec, parent):
    return [s.name for s in rec.spans if s.parent == parent]


def test_engine_spans_on_and_off_serve_identical_tokens(smollm, monkeypatch):
    cfg, params = smollm
    reqs = _shared_prefix_trace(cfg)
    made = []
    real = jax.profiler.TraceAnnotation
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: made.append(name) or real(name))
    off = ServeEngine(cfg, ECFG, params).run(reqs)
    assert made == []                     # off: nothing annotated
    rec = SpanRecorder()
    on = ServeEngine(cfg, ECFG, params, spans=rec).run(reqs)
    assert made and len(made) == len(rec.spans)
    for a, b in zip(on.results, off.results):
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      np.asarray(b.tokens),
                                      err_msg=f"rid {a.rid}")


def test_engine_join_spans_count_their_sweeps(smollm):
    cfg, params = smollm
    reqs = _shared_prefix_trace(cfg)
    rec = SpanRecorder()
    rep = ServeEngine(cfg, ECFG, params, spans=rec).run(reqs)
    joins = [(i, s) for i, s in enumerate(rec.spans) if s.name == "join"]
    assert sorted(s.rid for _, s in joins) == sorted(r.rid for r in reqs)
    assert all(s.parent == -1 for _, s in joins)
    restored = 0
    for i, s in joins:
        L, m = s.counts["prompt_tokens"], s.counts["restored"]
        kids = _children(rec, i)
        if m:
            restored += 1
            sweeps = -(-(L - m) // ECFG.prefill_chunk)
            assert s.counts["sweeps"] == sweeps
            sub, = [c for c in rec.spans
                    if c.parent == i and c.name == "suffix_prefill"]
            assert sub.counts == {"sweeps": sweeps}
            assert "prefill" not in kids and "inject" not in kids
        else:
            assert s.counts["sweeps"] == 0
            assert kids[:2] == ["prefill", "inject"]
        assert kids[-2:] == ["sample_first", "register_prefix"]
    assert restored and "page_copy" in {c.name for c in rec.spans}
    assert sum(s.counts["restored"] for _, s in joins) == \
        rep.prefill_tokens_saved
    polls = [s for s in rec.spans if s.name == "poll"]
    assert sum(s.counts["admitted"] for s in polls) == len(reqs)
    assert polls[-1].counts["queue_depth"] == 0


def test_engine_chunk_spans_match_the_report(smollm):
    cfg, params = smollm
    reqs = _shared_prefix_trace(cfg)
    walls = []
    rec = SpanRecorder()
    rep = ServeEngine(cfg, ECFG, params, spans=rec,
                      on_chunk=lambda st: walls.append(st.wall_s)).run(reqs)
    chunks = [(i, s) for i, s in enumerate(rec.spans) if s.name == "chunk"]
    assert len(chunks) == rep.n_chunks == len(walls)
    assert sum(s.counts["tokens_kept"] for _, s in chunks) == rep.tokens_kept
    assert sum(s.counts["finished"] for _, s in chunks) == len(reqs)
    for (i, s), wall in zip(chunks, walls):
        assert s.parent == -1
        assert _children(rec, i) == ["upload", "dispatch", "device_wait",
                                     "harvest", "on_chunk"]
        d, w = [c for c in rec.spans if c.parent == i][1:3]
        assert d.end == w.start
        assert wall == pytest.approx(d.seconds + w.seconds, abs=1e-12)
    top = {s.name for s in rec.spans if s.parent == -1}
    assert top == {"poll", "join", "grow_pages", "chunk"}


def test_engine_grow_pages_counts_preemptions(smollm, tmp_path):
    cfg, params = smollm
    reqs = batch_trace(3, seed=5, vocab_size=cfg.vocab_size, prompt_len=6,
                       max_new_tokens=14)
    # 2 scratch + 6 usable pages; each context needs ceil((6+14)/4) = 5
    tight = dataclasses.replace(ECFG, n_pages=2 + 6, max_len=48)
    rec = SpanRecorder()
    rep = ServeEngine(cfg, tight, params, spans=rec,
                      snapshot_dir=str(tmp_path), snapshot_every=2).run(reqs)
    grows = [s for s in rec.spans if s.name == "grow_pages"]
    assert rep.n_preemptions > 0
    assert sum(s.counts["preempted"] for s in grows) == rep.n_preemptions
    assert sum(s.counts["grown"] for s in grows) > 0
    snaps = [s for s in rec.spans if s.name == "snapshot"]
    assert len(snaps) == 1 + rep.n_chunks // 2
    assert all(s.parent == -1 for s in snaps)
