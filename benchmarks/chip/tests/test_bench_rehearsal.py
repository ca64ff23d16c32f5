"""CPU rehearsals at a tiny size: the set-up, a window of a few rounds,
and the comparison with the references, as the harness runs them. The
control (the reference one precision lower in the program's place) and
each fault that the cell can have must read as not correct against the
cell's own limits; the program must read correct.

Cases: `c10.genfv.highway`; the same with the generator at base width 32
in its config (another generator through the same harness files, as the
next generator config will come); `c10.fedavg.highway`, the FedAvg
baseline with no generation.

Tiny size: client width 1/16, 1,200 training images, 32 test images, 6
vehicles (20 for fedavg, which trains 30% of those present; fleet
buckets 4 and 8), one local SGD step of batch 16 for a vehicle and for
the RSU, 2 sampler steps and a t0 of 0.01 s that keeps b_gen under 64.
One step: at this width several steps of SGD drift apart chaotically
from float32 round-off alone (the RSU's 4 steps read aug_norm 0.21)."""
import copy
import functools

import pytest

from chipbench import cells, check, faults
from chipbench.session import Session

SEED = 2147483713
# case: (cell, config overrides as (group, key, value))
CASES = {
    "c10.genfv.highway": ("c10.genfv.highway", ()),
    "c10.genfv.highway.base32": ("c10.genfv.highway",
                                 (("generator", "base_width", 32),)),
    "c10.fedavg.highway": ("c10.fedavg.highway",
                           (("fl", "num_vehicles", 20),)),
}
# faults a case can have: no generation, no altered image
FAULTS = {"c10.genfv.highway": faults.CAUGHT,
          "c10.fedavg.highway": ("unchanged", "half_batch",
                                 "altered_update")}


def tiny(case="c10.genfv.highway"):
    cell, overrides = CASES[case]
    celld = copy.deepcopy(cells.cell(cell))
    cfgd = copy.deepcopy(cells.config(celld["config"]))
    cfgd["model"]["width_mult"] = 0.0625
    cfgd["run"].update(train_size=1200, test_size=32)
    cfgd["fl"].update(num_vehicles=6, local_steps=1, rsu_steps_factor=1,
                      batch_size=16)
    cfgd["generator"]["t_image"] = 0.01
    for group, key, value in overrides:
        cfgd[group][key] = value
    celld["sampler_steps"] = 2
    celld["warm"] = {"fleet_buckets": [4, 8], "planner_buckets": [4, 8],
                     "sampler_buckets": [4, 8, 16, 32, 64],
                     "fleet_sizes": list(range(1, 9)),
                     "sampler_sizes": list(range(1, 65))}
    return cfgd, celld


def drive(case="c10.genfv.highway", seed=SEED):
    cfgd, celld = tiny(case)
    s = Session(cfgd, celld, seed)
    s.setup()
    s.window(0.3)
    return s, celld["check"]["limits"]


# numbers whose limit the control's reading lies above, by case: the
# fedavg cell's float64 planner reads round-off, its float32 control does
# not (the genfv cells' planner stops on tolerances, which the float32
# control reads inside)
CONTROL_OVER = {"c10.fedavg.highway": ("plan_gap", "fleet_norm")}


def verdict(s, readings, limits):
    ok, rows = check.judge(check.worst(n for _, n in readings), limits,
                           s.window_compiles)
    return ok, {k: v for k, v, _ in rows}


@functools.cache
def driven(case):
    return drive(case)


@pytest.fixture(scope="module", params=sorted(CASES))
def session(request):
    return driven(request.param)


def test_program_reads_correct(session):
    s, limits = session
    readings = s.readings()
    assert readings, "no round was kept for the comparison"
    assert s.times and s.comp.requests.get("window", 0) == 0
    ok, numbers = verdict(s, readings, limits)
    assert ok, numbers
    assert set(numbers) == set(limits) | {"window_compiles"}


def test_compile_inside_the_window_reads_not_correct(session):
    s, limits = session
    assert not check.judge(check.worst(n for _, n in s.readings()), limits,
                           window_compiles=1)[0]


def test_control_reads_not_correct(session):
    s, limits = session
    ok, numbers = verdict(s, s.readings(control=True), limits)
    assert not ok, numbers


@pytest.mark.parametrize("case", sorted(CONTROL_OVER))
def test_control_reads_over_the_limits_set_against_it(case):
    s, limits = driven(case)
    _, numbers = verdict(s, s.readings(control=True), limits)
    assert {k for k in CONTROL_OVER[case] if numbers[k] > limits[k]} == \
        set(CONTROL_OVER[case]), numbers


@pytest.mark.parametrize("case,fault", [(c, f) for c in sorted(FAULTS)
                                        for f in FAULTS[c]])
def test_fault_reads_not_correct(case, fault):
    with faults.planted(fault):
        s, limits = drive(case, SEED + 1)
    ok, numbers = verdict(s, s.readings(), limits)
    assert not ok, (fault, numbers)
