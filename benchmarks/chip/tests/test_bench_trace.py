"""The trace reduction on a small hand-made trace (fixtures/), whose
numbers are worked out below."""
import json
from pathlib import Path

import pytest

from chipbench import cells, flops, tracing


# two rounds, [0, 1000) and [1000, 2500) ns: host spans, two device
# programs and four device operations (two of them overlapping)
HAND = json.loads((Path(__file__).parent / "fixtures" /
                   "trace_hand.json").read_text())


def test_hand_trace():
    red = tracing.reduce(HAND)
    assert red["window_s"] == pytest.approx(2500e-9)
    # busy: [120,220) + [400,600) + [1200,1900) = 1000 ns
    assert red["busy_s"] == pytest.approx(1000e-9)
    assert red["rounds"] == [
        {"round/plan": pytest.approx(200e-9),
         "round/generate": pytest.approx(400e-9),
         "round/generate/sample": pytest.approx(300e-9)},
        {"round/aggregate": pytest.approx(900e-9)}]
    assert tracing.module_time(red, "fleet_step") == pytest.approx(700e-9)
    assert tracing.module_time(red, "nothing") is None
    # gaps [0,120) none open, [220,400) generate and plan open (generate
    # is innermost), [600,1200) none, [1900,2500) none
    gaps = dict((k, v) for k, v in tracing.breakdown(red)["idle_gaps"])
    assert gaps["round/generate"] == pytest.approx(180e-9)
    assert gaps["between spans"] == pytest.approx((120 + 600 + 600) * 1e-9)


def _ctx(red, cell):
    celld = cells.cell(cell)
    return {"red": red, "config": cells.config(celld["config"]),
            "cell": celld, "flops": flops, "module_time": tracing.module_time,
            "peak": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
            "rsu_steps_factor": 4,
            "rounds": [{"k": 8, "b_gen": 900}, {"k": 8, "b_gen": 900}]}


def test_hand_trace_metrics():
    ctx = _ctx(tracing.reduce(HAND), "c10.genfv.highway")
    read = {m: cells.reader(m)(ctx) for m in (
        "planner.plan_s", "gen.sample_s", "gen.augment_s",
        "fleet.aggregate_s", "device.idle_share", "loop.host_s")}
    assert read["planner.plan_s"] == pytest.approx(100e-9)
    assert read["gen.sample_s"] == pytest.approx(150e-9)
    assert read["gen.augment_s"] == pytest.approx(50e-9)
    assert read["fleet.aggregate_s"] == pytest.approx(450e-9)
    assert read["device.idle_share"] == pytest.approx(60.0)
    assert read["loop.host_s"] == 0.0
    train = flops.resnet_train_flops(1.0, 10)
    want = 100 * 2 * 8 * 4 * 64 * train / 197e12 / 700e-9
    assert cells.reader("kernel.fleet_step_roofline")(ctx) == \
        pytest.approx(want)


def test_reader_finds_nothing_returns_none():
    empty = tracing.reduce({"host": [], "modules": [], "ops": []})
    ctx = _ctx(empty, "c10.genfv.highway")
    for m in cells.benchmark()["per_layer"]:
        assert cells.reader(m["name"])(ctx) is None, m["name"]
