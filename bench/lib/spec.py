"""Finds a cell's pieces by name: BENCHMARK.json at the checkout's root
names the cell, its configuration file and its traffic mix; the rest are
files under the benchmark's directory, one per piece:

  configs/<config>.json   sizes, cuts, weights' init, engine, check sizes;
                          its `reference` key names the two modules below
  reference/<key>.py      the architecture's plain float32 reference:
                          shapes(hf), hparams_of(hf), logits_at(...)
  arch/<key>.py           how the program takes that architecture:
                          model_config(hf), program_params(w, hf, vocab),
                          work(hf) (bench/lib/work.py)
  traffic/<mix>.json      the mix's parameters (bench/lib/traffic.py)
  cells/<cell>.json       the cell's fixed offered rate (its knee sweep)
                          and the limits of its correctness check
  metrics/<metric>.py     a per-layer metric's reader: read(ctx) -> number
                          or None when it finds nothing to read

A later cell, mix, configuration, architecture or metric is new files and
new entries in BENCHMARK.json; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict  # the configuration file
    mix_name: str
    mix: dict  # the traffic file
    settings: dict  # the cell file: rate_rps, limits
    end_to_end: List[dict]
    per_layer: List[dict]
    reference: ModuleType  # bench/reference/<config's reference key>.py
    arch: ModuleType  # bench/arch/<config's reference key>.py


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, *, root: Path = ROOT,
              bench: Optional[dict] = None) -> Cell:
    """The cell called `name`, with every file it names loaded. `root` is
    the checkout holding BENCHMARK.json (tests pass their own)."""
    bench = bench if bench is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / conf["file"])
    ref = module("reference", config["reference"], root=root)
    arch = module("arch", config["reference"], root=root)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, mix_name=w["traffic"],
                mix=load_json(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                settings=load_json(root / "bench" / "cells" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer,
                reference=ref, arch=arch)


def module(kind: str, key: str, *, root: Path = ROOT) -> ModuleType:
    """bench/<kind>/<key>.py under root, loaded by path: an architecture's
    reference or arch module, or a per-layer metric's reader. A missing
    file is an error that names it."""
    path = root / "bench" / kind / f"{key}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {path.relative_to(root)} under {root} "
                                f"for {kind} {key!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{key.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, *, root: Path = ROOT) -> Callable:
    """A per-layer metric's reader, loaded from bench/metrics/<name>.py."""
    return module("metrics", metric, root=root).read


def peaks(device_kind: str, *, root: Path = ROOT) -> Dict[str, float]:
    """The chip's published peaks; an unknown chip is an error."""
    table = load_json(root / "bench" / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]
