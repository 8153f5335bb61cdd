"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

  configuration  ``configs[].file``          (JSON: published keys + "bench")
  reference      ``bench/references/<bench.reference>.py``
  traffic mix    ``bench/traffic/<traffic>.json``
  metric         ``bench/metrics/<metric name>.py`` (a ``read(run)`` function)
  peaks          ``bench/harness/peaks.json``, keyed by ``device_kind``

Adding a cell, a configuration, a mix or a metric is adding files and
entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict             # the configuration file, parsed
    traffic_name: str
    traffic: dict            # the mix file, parsed
    end_to_end: list[dict]   # BENCHMARK.json entries this cell reports
    per_layer: list[dict]


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(entry: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry["moves"] in e2e_names


def load_cell(root: pathlib.Path, name: str, *, benchmark_file=None,
              traffic_dir=None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` (or ``benchmark_file``),
    its files read relative to ``root``."""
    path = pathlib.Path(benchmark_file or root / "BENCHMARK.json")
    bench = json.loads(path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg["file"]).read_text())
    tdir = pathlib.Path(traffic_dir) if traffic_dir else BENCH / "traffic"
    traffic = json.loads((tdir / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, e2e, per_layer)


def metric_reader(name: str, metrics_dir=None):
    d = pathlib.Path(metrics_dir) if metrics_dir else BENCH / "metrics"
    return load_module(d / f"{name}.py").read


def reference(name: str):
    return load_module(BENCH / "references" / f"{name}.py")


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "harness" / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
