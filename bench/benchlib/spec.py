"""What a run is made of, looked up by name in ``BENCHMARK.json``.

A workload names a configuration (``bench/configs/<file>.json``: the published
sizes, the engine settings, the reference module and the correctness limit) and
a traffic mix (``bench/traffic/<traffic>.json``).  Every metric is read by
``bench/metrics/<name>.py``, whose ``read(run)`` returns a number or ``None``.
Nothing here names a cell, a configuration, a mix or a metric.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: object          # callable(run) -> float | None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    end_to_end: list      # Metric, those this cell reports
    per_layer: list


def load_module(path: pathlib.Path):
    """Import a file by path (names may hold dots, as metric names do)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _metrics(entries: list, workload: str) -> list:
    out = []
    for m in entries:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py").read
        out.append(Metric(m["name"], m["unit"], reader))
    return out


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=_metrics(bench["end_to_end"], workload),
        per_layer=_metrics(bench["per_layer"], workload))
