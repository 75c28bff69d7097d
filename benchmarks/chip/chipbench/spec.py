"""Find a cell's configuration, traffic mix and metrics by name.

Everything a cell needs is data: ``BENCHMARK.json`` at the checkout
root names the cell's configuration (a file under ``configs/``) and its
traffic mix (``traffic/<name>.json``); each per-layer metric is a reader
module ``metrics/<name>.py`` with ``read(run) -> float | None``.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict              # the configuration file's contents
    traffic_name: str
    traffic: dict             # the traffic file's contents
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list
    bench_dir: str = BENCH_DIR


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files read.

    Raises ``LookupError`` for an unknown cell and ``FileNotFoundError``
    where a named file is missing."""
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise LookupError(f"no workload {name!r} in BENCHMARK.json "
                          f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    with open(os.path.join(root, centry["file"])) as fh:
        config = json.load(fh)
    with open(traffic_path(w["traffic"], bench_dir)) as fh:
        traffic = json.load(fh)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def traffic_path(name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "traffic", f"{name}.json")


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    mod_name = "chipbench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: Cell, run) -> dict:
    """Every per-layer metric of the cell that its reader finds; a reader
    that returns None is left out of the result."""
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"], cell.bench_dir)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
