"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix. Its
files, relative to the checkout root:

* ``configs[].file`` — the model configuration as it is run;
* ``chipbench/traffic/<traffic>.json`` — the mix's lengths, arrivals and
  denoising schedule, read by the one generator in ``traffic.py``;
* ``chipbench/cells/<workload>.json`` — the cell's rate or request count,
  serve settings and correctness limits;
* ``chipbench/metrics/<metric>.py`` — one reader per metric.

Adding a cell, configuration, traffic mix or metric adds files and entries;
no file here changes.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

ROOT = Path(__file__).resolve().parent.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    root: Path
    bench: dict
    workload: dict          # the BENCHMARK.json workloads entry
    config: dict            # the model configuration file
    traffic: dict           # the traffic mix file
    cell: dict              # the cell file

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def metrics(self, kind: str) -> List[dict]:
        """The ``end_to_end`` (``kind="end_to_end"``) or ``per_layer``
        entries this cell reports, in ``BENCHMARK.json`` order. An entry
        with a ``workloads`` key lists its cells; a per-layer entry without
        one goes wherever its ``moves`` metric is reported."""
        e2e = [m for m in self.bench["end_to_end"] if _covers(m, self.name)]
        if kind == "end_to_end":
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in names)]


def _covers(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Optional[Path] = None) -> Cell:
    root = Path(root or ROOT)
    bench = load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(work)}")
    w = work[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / cfgs[w["config"]]["file"])
    traffic = load_json(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    cell = load_json(root / "chipbench" / "cells" / f"{name}.json")
    return Cell(root, bench, w, config, traffic, cell)


def metric_path(root: Path, name: str) -> Path:
    return Path(root) / "chipbench" / "metrics" / f"{name}.py"
