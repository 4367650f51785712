"""What one cell of ``BENCHMARK.json`` names, gathered from the files the
names point to: the cell, its configuration, its traffic mix, and the
metrics it reports."""

from __future__ import annotations

import dataclasses
import importlib
import json
import os

from chipbench import model


@dataclasses.dataclass
class Cell:
    root: str
    name: str
    chips: int
    conf: dict                    # chipbench/configs/<config>.json
    traffic: dict                 # chipbench/traffic/<traffic>.json
    end_to_end: list              # BENCHMARK.json entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"there are {sorted(cells)}")
    w = cells[workload]
    with open(os.path.join(root, "chipbench", "traffic",
                           f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    return Cell(root=root, name=workload, chips=w["chips"],
                conf=model.load(root, w["config"]),
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, workload)])


def metric_module(name: str):
    """``chipbench/metrics/<name>.py``: its ``value(run)`` reads the metric
    from a finished run, or returns None where there is nothing to read."""
    return importlib.import_module(f"chipbench.metrics.{name}")
