"""Find a cell's pieces by the names in ``BENCHMARK.json``.

A configuration is ``benchmark/configs/<config>.json`` (the file named
in the manifest), a traffic mix ``benchmark/traffic/<traffic>.json``,
and a per-layer metric ``benchmark/layers/<metric>.py`` with a
``read(readings)`` function.  Nothing here knows any cell by name, so a
later PR adds one with files and manifest entries alone.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, List

# the checkout's root (tests point it at a copy)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file
    mix: dict             # the traffic file
    end_to_end: List[dict]  # the cell's end-to-end metric entries
    per_layer: List[dict]   # the cell's per-layer metric entries


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    from .traffic import load_mix

    root = ROOT
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    mix = load_mix(os.path.join(root, "benchmark", "traffic",
                                w["traffic"] + ".json"))
    runs = config.get("worker", {}).get("HashModel", "md5")
    if runs != config["hash_model"]:
        raise ValueError(
            f"{name}: configuration {w['config']!r} is judged as hash model "
            f"{config['hash_model']!r}, but its worker runs HashModel "
            f"{runs!r}")
    if "pool" in mix and mix["pool_hash"] != config["hash_model"]:
        raise ValueError(
            f"{name}: configuration {w['config']!r} runs hash model "
            f"{config['hash_model']!r}, but mix {w['traffic']!r} has its "
            f"pool solved under pool_hash {mix['pool_hash']!r}")
    e2e = [m for m in manifest["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if m["moves"] in moved and _reports(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=e2e, per_layer=layer)


def reader(metric: str) -> Callable:
    """``read`` of ``benchmark/layers/<metric>.py``."""
    path = os.path.join(ROOT, "benchmark", "layers", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
