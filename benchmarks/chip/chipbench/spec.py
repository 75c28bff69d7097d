"""Find a cell's configuration, traffic mix and metrics by name.

Everything a cell needs is data: ``BENCHMARK.json`` at the checkout
root names the cell's configuration (a file under ``configs/``) and its
traffic mix (``traffic/<name>.json``); each per-layer metric is a reader
module ``metrics/<name>.py`` with ``read(run) -> float | None``.  The
configuration names its model family (``model.family``, as the
program's ``ArchConfig`` has it) and its plain model reference
(``reference``: a module's path from the checkout root), so a new
family is new files only.
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
    reference: object         # the configuration's model reference module
    bench_dir: str = BENCH_DIR


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


# what every model reference module provides, and what a cell of a kind
# calls besides
REFERENCE_NEEDS = ("loss", "train_flops_per_token")
REFERENCE_NEEDS_BY_KIND = {"serve": ("logits",)}


def load_cell(root: str, name: str, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files read
    and its configuration's model reference loaded.

    Raises ``LookupError`` for an unknown cell, a configuration without
    ``model.family`` or ``reference`` or a reference without what the
    cell calls, ``ValueError`` for a family the program does not know,
    and ``FileNotFoundError`` where a named file is missing."""
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
    reference = load_reference(
        root, centry["file"], config,
        REFERENCE_NEEDS + REFERENCE_NEEDS_BY_KIND.get(traffic["kind"], ()))
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, reference=reference,
                bench_dir=bench_dir)


def known_families() -> set:
    """The model families of the program's own configurations."""
    from repro.configs import get_config, list_archs
    return {get_config(a).family for a in list_archs()}


def load_reference(root: str, file: str, config: dict, needs=()):
    """The model reference module of the configuration in ``file``, once
    the configuration names a family the program knows and a reference
    that provides ``needs``."""
    family = config.get("model", {}).get("family")
    if family is None:
        raise LookupError(f"{file}: the model block names no \"family\"")
    if family not in known_families():
        raise ValueError(f"{file}: the program knows no model family "
                         f"{family!r} (it has {sorted(known_families())})")
    if "reference" not in config:
        raise LookupError(f"{file}: no \"reference\" names the model's "
                          "plain reference")
    path = os.path.join(root, config["reference"])
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{file}: its reference {path} is missing")
    mod = _load_module(path,
                       "chipbench_ref_" + _mod_name(config["reference"]))
    missing = [f for f in needs if not callable(getattr(mod, f, None))]
    if missing:
        raise LookupError(f"{file}: its reference {config['reference']} "
                          f"has no {', '.join(missing)}")
    return mod


def traffic_path(name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "traffic", f"{name}.json")


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    return _load_module(path, "chipbench_metric_" + _mod_name(name)).read


def _mod_name(name: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in name)


def _load_module(path: str, mod_name: str):
    """The module of the Python file ``path``, loaded under ``mod_name``
    and kept out of ``sys.modules``."""
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_per_layer(cell: Cell, run) -> dict:
    """Every per-layer metric of the cell that its reader finds; a reader
    that returns None is left out of the result."""
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"], cell.bench_dir)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
