"""Finds a cell, its configuration and its metrics by name.

Layout under the benchmark directory (later cells add files, never edit):
  configs/<config>.json   model, data and federated settings, pinned t0
  cells/<workload>.json   the traffic: strategy, scenario, alpha, sampler
                          steps, warm lists, limits of the comparison
  metrics/<metric>.py     one reader per per-layer metric
  archs/<arch>.py         one file per architecture a config names
                          (`model.arch`, `generator.arch`): seeded weights,
                          the program's object, the plain reference, and
                          parameter, FLOP and byte counts
and BENCHMARK.json at the root of the checkout lists which metrics each
cell reports.
"""
from __future__ import annotations

import importlib.util
import json
import re
from functools import lru_cache
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = HERE.parents[1]                              # the checkout


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    c = load_json(HERE / "cells" / f"{name}.json")
    c["name"] = name
    return c


def config(name: str) -> dict:
    """The config file; an architecture it names that has no file under
    archs/ fails here, before any device work."""
    c = load_json(HERE / "configs" / f"{name}.json")
    c["name"] = name
    arch(c["model"]["arch"])
    arch(c["generator"]["arch"])
    return c


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports in a run of this kind."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def _module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no file {path} for {kind[:-1]} {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"{kind[:-1]}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@lru_cache(maxsize=None)
def arch(name: str):
    """archs/<name>.py, loaded once per process."""
    return _module("archs", name)


def reader(metric: str):
    """metrics/<metric>.py's `read(ctx)`."""
    return _module("metrics", metric).read
