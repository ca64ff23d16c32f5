"""The readers of the child spans inside the fleet dispatch, the RSU step
and eval, on a small hand-made trace whose numbers are worked out below."""
import pytest

from chipbench import cells, tracing

# two rounds, [0, 2000) and [2000, 3000) ns; the second generates nothing
HOST = [
    ["bench/round", 0, 2000],
    ["round/generate", 100, 600],
    ["round/generate/sample", 110, 290],
    ["round/generate/pool", 400, 50],
    ["round/generate/train", 450, 200],
    ["round/aggregate", 700, 800],
    ["round/aggregate/stack", 710, 50],
    ["round/aggregate/put", 760, 240],
    ["round/aggregate/step", 1000, 480],
    ["round/eval", 1500, 400],
    ["round/eval/put", 1510, 40],
    ["bench/round", 2000, 1000],
    ["round/aggregate", 2000, 600],
    ["round/aggregate/stack", 2010, 30],
    ["round/aggregate/put", 2040, 100],
    ["round/aggregate/step", 2140, 450],
    ["round/eval", 2600, 300],
    ["round/eval/put", 2610, 40],
]
DEVICE = [
    ["jit__sample_strided_1", 150, 230],
    ["jit_local_sgd_steps_2", 470, 170],
    ["jit__fleet_step_impl_3", 1010, 460],
    ["jit__accuracy_4", 1560, 40],
    ["jit__fleet_step_impl_3", 2150, 430],
    ["jit__accuracy_4", 2660, 40],
]
TRACE = {"host": HOST, "modules": DEVICE, "ops": DEVICE}


@pytest.fixture(scope="module")
def red():
    return tracing.reduce(TRACE)


def read(red, metric):
    return cells.reader(metric)({"red": red})


@pytest.mark.parametrize("metric, ns", [
    ("fleet.stack_s", (50 + 30) / 2),
    ("fleet.put_s", (240 + 100) / 2),
    ("fleet.step_s", (480 + 450) / 2),
    ("gen.pool_s", 50 / 2),             # over both rounds, one generated
    ("gen.train_s", 200 / 2),
    ("eval.put_s", (40 + 40) / 2),
])
def test_child_span_readers(red, metric, ns):
    assert read(red, metric) == pytest.approx(ns * 1e-9)


def test_children_fit_inside_their_parents(red):
    fleet = sum(read(red, m) for m in ("fleet.stack_s", "fleet.put_s",
                                       "fleet.step_s"))
    assert fleet == pytest.approx((80 + 340 + 930) * 1e-9 / 2)
    assert fleet <= read(red, "fleet.aggregate_s") == pytest.approx(700e-9)
    gen = read(red, "gen.pool_s") + read(red, "gen.train_s")
    # gen.augment_s: round/generate less its sample child, (600 - 290) / 2
    assert gen <= read(red, "gen.augment_s") == pytest.approx(155e-9)


def test_idle_gaps_name_the_child_spans(red):
    # [0,150) before any span; [380,470) mid 425 in pool; [640,1010) mid
    # 825 in aggregate/put; [1470,1560) mid 1515 and [2580,2660) mid 2620
    # in eval/put; [1600,2150) mid 1875 and [2700,3000) mid 2850 in eval
    gaps = dict(tracing.breakdown(red)["idle_gaps"])
    assert gaps == {"between spans": pytest.approx(150e-9),
                    "round/generate/pool": pytest.approx(90e-9),
                    "round/aggregate/put": pytest.approx(370e-9),
                    "round/eval/put": pytest.approx(170e-9),
                    "round/eval": pytest.approx(850e-9)}
    assert red["busy_s"] == pytest.approx(1370e-9)
    assert tracing.module_time(red, "accuracy") == pytest.approx(80e-9)
