"""A CPU rehearsal of c10.genfv.highway at a tiny size: the set-up, a
window of a few rounds, and the comparison with the references, as the
harness runs them. The control (the reference one precision lower in
the program's place) and each fault that the cell can have must read as
not correct against the cell's own limits; the program must read correct.

Tiny size: client width 1/16, 1,200 training images, 32 test images, 6
vehicles (fleet
buckets 4 and 8), one local SGD step of batch 16 for a vehicle and for
the RSU, 2 sampler steps and a t0 of 0.01 s that keeps b_gen under 64.
One step: at this width several steps of SGD drift apart chaotically
from float32 round-off alone (the RSU's 4 steps read aug_norm 0.21)."""
import copy

import pytest

from chipbench import cells, check, faults
from chipbench.session import Session

CELL = "c10.genfv.highway"
SEED = 2147483713


def tiny():
    celld = copy.deepcopy(cells.cell(CELL))
    cfgd = copy.deepcopy(cells.config(celld["config"]))
    cfgd["model"]["width_mult"] = 0.0625
    cfgd["run"].update(train_size=1200, test_size=32)
    cfgd["fl"].update(num_vehicles=6, local_steps=1, rsu_steps_factor=1,
                      batch_size=16)
    cfgd["generator"]["t_image"] = 0.01
    celld["sampler_steps"] = 2
    celld["warm"] = {"fleet_buckets": [4, 8], "planner_buckets": [4, 8],
                     "sampler_buckets": [4, 8, 16, 32, 64],
                     "fleet_sizes": list(range(1, 9)),
                     "sampler_sizes": list(range(1, 65))}
    return cfgd, celld


def drive(seed=SEED):
    cfgd, celld = tiny()
    s = Session(cfgd, celld, seed)
    s.setup()
    s.window(0.3)
    return s, celld["check"]["limits"]


def verdict(s, readings, limits):
    ok, rows = check.judge(check.worst(n for _, n in readings), limits,
                           s.window_compiles)
    return ok, {k: v for k, v, _ in rows}


@pytest.fixture(scope="module")
def session():
    return drive()


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


@pytest.mark.parametrize("fault", faults.CAUGHT)
def test_fault_reads_not_correct(fault):
    with faults.planted(fault):
        s, limits = drive(SEED + 1)
    ok, numbers = verdict(s, s.readings(), limits)
    assert not ok, (fault, numbers)
