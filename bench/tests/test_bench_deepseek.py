"""The DeepSeek-V2-Lite cell's own files: its reference refuses a program
that is not the file's model, and its long-batch mix deals the backlog
the cell states."""
import dataclasses
import json

import pytest

from harness import spec, traffic
from repro.configs import get_arch
from repro.models.common import YaRN

ROOT = spec.BENCH.parent
CONF = json.loads((ROOT / "bench" / "configs" / "deepseek-v2-lite.json")
                  .read_text())
REF = spec.reference(CONF["bench"]["reference"])


def _program(**change):
    b = CONF["bench"]["program"]
    cfg = dataclasses.replace(get_arch(b["arch"]).config, **b["overrides"])
    return dataclasses.replace(cfg, **change)


def test_reference_accepts_the_files_program():
    s = REF.sizes(CONF)
    REF.check_program(s, _program())
    assert (s["experts"], s["held"], s["layers"]) == (64, 8, 7)


@pytest.mark.parametrize("change", [
    {"held_experts": 16},                 # another chip share
    {"held_experts": 0},                  # every expert held
    {"rope_scaling": None},               # no YaRN
    {"rope_scaling": YaRN(factor=40.0, original_max_position=8192,
                          beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                          mscale_all_dim=0.707)},
    {"n_layers": 6},                      # another depth
    {"norm_topk_prob": True},
], ids=["held16", "held_all", "no_yarn", "yarn_8192", "depth6", "norm_topk"])
def test_reference_refuses_another_model(change):
    with pytest.raises(ValueError, match="differs from the file"):
        REF.check_program(REF.sizes(CONF), _program(**change))


def test_long_batch_mix_deals_its_backlog():
    mix = json.loads((spec.BENCH / "traffic" / "long_batch.json")
                     .read_text())
    seconds, seed = 51.0, 2**33 + 5
    planned, warm = traffic.make_trace(mix, seed=seed, seconds=seconds,
                                       vocab=int(CONF["vocab_size"]))
    backlog = [p for p in planned if p.arrival_s == 0.0]
    assert len(backlog) == mix["backlog"] == 64
    n_arrivals = round(traffic.rate_per_s(mix) * seconds)
    assert len(planned) == 64 + n_arrivals
    assert all(0.0 < p.arrival_s < seconds for p in planned[64:])
    lens = [len(p.prompt) for p in planned]
    assert 256 <= min(lens) and max(lens) <= 3072
    assert all(len(p.prompt) + p.max_new_tokens <= 4096 for p in planned)
    assert all(128 <= p.max_new_tokens <= 768 for p in planned)
    assert len(warm) == mix["warmup"]["requests"]
