"""BENCHMARK.json and the benchmark's data files keep to the contract the
harness and the driver read them by."""
import json
import math
import re

import pytest

from chipbench import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = cells.benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = sorted(p.stem for p in (cells.HERE / "configs").glob("*.json"))
CELLS = sorted(p.stem for p in (cells.HERE / "cells").glob("*.json"))
# numbers check.compare can give, and the layers a cell's limits cover
NUMBERS = {"plan_alpha", "plan_gap", "plan_bgen", "sample_gap", "sample_rms",
           "aug_loss", "aug_norm", "aug_norm_med", "fleet_loss",
           "fleet_norm", "fleet_norm_med", "eval_gap"}
LAYERS = {"genfv": ("plan_", "sample_", "aug_", "fleet_"),
          "fedavg": ("plan_", "fleet_")}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_run_seconds_fit_a_full_check():
    """2 + 14 runs per cell, each run_seconds + 60, 2 x 90 s of compile
    per cell and 1200 s spare must fit 43200 s with 24 cells."""
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51 and rs == int(rs)
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_and_units(group):
    entries = BENCH[group]
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k]


def test_entry_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("name", CONFIGS)
def test_config_files_load(name):
    assert NAME.match(name)
    c = cells.config(name)
    m = c["model"]
    assert m["stage_widths"] == [64, 128, 256, 512] and m["width_mult"] == 1.0
    assert c["generator"]["t_image"] > 0 and NAME.match(c["run"]["dataset"])


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_entries_match_files(name):
    c = cells.config(name)
    entry = next(e for e in BENCH["configs"] if e["name"] == name)
    assert entry["file"] == f"benchmarks/chip/configs/{name}.json"
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    assert entry["source"] == c["source"]
    assert any(w["config"] == name for w in BENCH["workloads"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load(name):
    assert NAME.match(name)
    c = cells.cell(name)
    assert c["config"] in CONFIGS
    from repro.core.planner import bucket_size
    w = c["warm"]
    for kind in ("fleet", "planner", "sampler"):
        assert all(b >= 4 and b & (b - 1) == 0
                   for b in w[f"{kind}_buckets"]), kind
    for kind in ("fleet", "sampler"):
        assert {bucket_size(n) for n in w[f"{kind}_sizes"]} == \
            set(w[f"{kind}_buckets"]), kind
    assert isinstance(c["traffic_seed"], int)
    limits = c["check"]["limits"]
    assert set(limits) <= NUMBERS
    for prefix in LAYERS[c["strategy"]]:
        assert any(k.startswith(prefix) for k in limits), prefix
    assert all(isinstance(v, (int, float)) and math.isfinite(v)
               for v in limits.values())


@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_entries_match_files(name):
    w = next(e for e in BENCH["workloads"] if e["name"] == name)
    assert name in CELLS
    assert cells.cell(name)["config"] == w["config"]


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_reader_and_moves(metric):
    m = next(e for e in BENCH["per_layer"] if e["name"] == metric)
    assert callable(cells.reader(metric))
    e2e = {e["name"]: e for e in BENCH["end_to_end"]}
    assert m["moves"] in e2e
    target = e2e[m["moves"]]
    for w in m.get("workloads", WORKLOADS):
        assert w in WORKLOADS
        assert "workloads" not in target or w in target["workloads"], \
            f"{w} reports {metric} but not {m['moves']}"


def test_every_cell_reports_setup_another_e2e_and_a_layer():
    for w in WORKLOADS:
        e2e = [m["name"] for m in cells.cell_metrics(BENCH, w, trace=False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cells.cell_metrics(BENCH, w, trace=True)


def test_layers_named_alike():
    """A layer's metrics give one name, and that name is in PERF.md."""
    perf = (cells.ROOT / "PERF.md").read_text()
    for layer in {m["layer"] for m in BENCH["per_layer"]}:
        assert f"| {layer} |" in perf, layer


def test_paths_hold_only_allowed_names():
    for p in cells.HERE.rglob("*"):
        if "__pycache__" in p.parts or p.is_dir():
            continue
        rel = p.relative_to(cells.ROOT).as_posix()
        assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel
