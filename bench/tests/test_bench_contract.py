"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
resolves to a file of its own."""
import json
import re

import pytest

from harness import spec

ROOT = spec.BENCH.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = re.compile(r"(hidden_size|intermediate_size|latent|state_size|proj"
                    r"|head_dim|_dim$|_rank$|expand|num_experts_per_tok)")


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= len(BENCH["paths"]) <= 16
    assert len(BENCH["command"]) <= 32
    assert all(_text(w) for w in BENCH["command"])
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_configs():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _text(c["source"]) and _text(c["why"])
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["bench"]["reduced"] == c["reduced"]
        assert conf["bench"]["source"] == c["source"]
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTHS.search(k), k
        assert conf["bench"]["correctness"]["max_logit_gap"] is not None
        assert conf["bench"]["memory_analysis"] is not None


def test_workloads():
    names = set()
    four = 0
    configs = {c["name"] for c in BENCH["configs"]}
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["name"] not in names and w["config"] in configs
        assert w["chips"] in (1, 4) and _text(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        assert (spec.BENCH / "traffic" / f"{w['traffic']}.json").exists()
        mix = json.loads((spec.BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        assert mix["rate"]["knee_req_per_s"] is not None
        names.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metrics(kind):
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    seen = set()
    for m in BENCH[kind]:
        keys = {"name", "unit", "better", "source"}
        keys |= {"bound"} if kind == "end_to_end" else {"layer", "moves"}
        assert set(m) - {"workloads"} == keys, m
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["name"] not in seen
        seen.add(m["name"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
        assert (spec.BENCH / "metrics" / f"{m['name']}.py").exists()
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert m["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
            assert m["moves"] in e2e and _text(m["layer"])


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names


def test_check_fits_the_budget():
    r, n = BENCH["run_seconds"], 24
    assert (2 + 14 * n) * (r + 60) + n * 2 * 90 + 1200 <= 43200


def test_a_new_metric_is_found_by_name(tmp_path):
    (tmp_path / "answer.metric.py").write_text(
        "def read(run):\n    return 42.0 if run else None\n")
    assert spec.metric_reader("answer.metric", metrics_dir=tmp_path)(1) \
        == 42.0
    assert spec.metric_reader("answer.metric", metrics_dir=tmp_path)(0) \
        is None
