"""The wall-clock admission hook and the window accounting."""
import types

import pytest

from harness import records


class FakeClock:
    def __init__(self):
        self.t = 100.0
        self.slept = []

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.slept.append(dt)
        self.t += dt


def _req(rid):
    return types.SimpleNamespace(rid=rid)


def test_hook_admits_due_requests_and_refuses_early_ones_when_busy():
    clk = FakeClock()
    hook = records.WallClockAdmission(sleep=clk.sleep, clock=clk)
    hook.due = {0: 100.0, 1: 100.5, 2: 103.0}
    assert hook(_req(0), 3) is True          # due: admitted without a wait
    assert hook(_req(1), 3) is False         # early and the engine is busy
    assert clk.slept == []
    clk.t = 100.7
    assert hook(_req(1), 2) is True
    assert hook.admitted == {0: 100.0, 1: 100.7}


def test_hook_sleeps_only_on_an_empty_engine():
    clk = FakeClock()
    hook = records.WallClockAdmission(sleep=clk.sleep, clock=clk)
    hook.due = {0: 102.0}
    assert hook(_req(0), 1) is True           # empty engine: wait, admit
    assert clk.slept == [2.0]
    assert hook.admitted[0] == 102.0
    assert [(s.name, s.start, s.end) for s in hook.spans] == \
        [("admission wait", 100.0, 102.0)]
    assert hook.late == [0.0]


def test_hook_admits_everything_during_setup():
    hook = records.WallClockAdmission()
    assert hook(_req(5), 7) is True and hook.admitted == {}


def _window():
    reqs = [records.Req(rid=i, due=10.0 + i, prompt_len=100,
                        max_new_tokens=5, admitted=10.5 + i,
                        first=11.0 + i, finish=11.0 + i + 4 * (i + 1),
                        n_tokens=5) for i in range(4)]
    chunks = [records.Chunk(end=10.0 + k, wall_s=0.5, n_active=2,
                            tokens_kept=3, steps=4) for k in range(12)]
    return records.Window(start=10.0, seconds=4.0, reqs=reqs, chunks=chunks,
                          spans=[], late=[])


def test_tails_are_taken_over_every_request():
    w = _window()
    assert w.ttft() == [1.0, 1.0, 1.0, 1.0]
    assert w.tpot() == [1.0, 2.0, 3.0, 4.0]
    assert records.pct(w.tpot(), 50) == 2.5
    assert records.pct([], 90) is None


def test_window_counts_tokens_on_the_host_inside_it():
    w = _window()
    # first tokens at 11, 12, 13 (14 is at the close); chunks ending at
    # 10, 11, 12, 13 inside [10, 14)
    assert w.tokens_in_window() == 3 + 4 * 3
    assert len(w.chunks_in_window()) == 4


def test_covered_is_the_union_clipped_to_the_window():
    w = _window()
    assert w.covered([(9.0, 10.5), (10.2, 11.0), (12.0, 12.5),
                      (13.5, 20.0)]) == pytest.approx(1.0 + 0.5 + 0.5)


def test_engine_clock_zero_pairs_joins_in_time_order():
    assert records.engine_clock_zero([5.001, 5.3, 5.2], [0.2, 0.0, 0.1]) \
        == pytest.approx(5.001)
    with pytest.raises(RuntimeError):
        records.engine_clock_zero([1.0], [0.0, 0.1])
