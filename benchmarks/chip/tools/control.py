"""Readings that the comparison's limits are set from, for one cell at
its own size, many seeds in one process (the set-up is paid once for
the programs; each seed builds its own runner, data and weights).

    python3 benchmarks/chip/tools/control.py c10.genfv.highway \
        --seeds 101 102 103 --seconds 8 [--control] [--faults NAME ...]

Per seed it runs the cell's set-up and a short window, then prints one
JSON line per kept round: the program against the reference, and with
--control also the reference computed one precision lower (bfloat16;
float32 for the float64 planner) in the program's place. Each of
--faults plants one of `chipbench.faults.FAULTS` under the timed path and
runs every seed again. After each seed and kind, a `verdict` line holds
the worst numbers over the kept rounds passed through `check.judge` with
the cell's limits. Needs the chip.
"""
import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("cell")
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--control", action="store_true")
    p.add_argument("--faults", nargs="*", default=[])
    a = p.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("control: needs a TPU")
    import run_cell
    from chipbench import cells, check, faults
    from chipbench.session import Session
    run_cell.use_cache(jax)
    celld = cells.cell(a.cell)
    cfgd = cells.config(celld["config"])
    limits = celld["check"]["limits"]
    for fault in [None] + list(a.faults):
        for seed in a.seeds:
            t0 = time.perf_counter()
            # a fault goes in before the set-up: the recorder wraps the
            # runner's methods as they are when it is built
            ctx = faults.planted(fault) if fault else \
                contextlib.nullcontext()
            with ctx:
                s = Session(cfgd, celld, seed)
                s.setup()
                s.window(a.seconds)
            kinds = [(fault or "program", False)]
            if a.control and fault is None:
                kinds.append(("control", True))
            for kind, ctl in kinds:
                readings = s.readings(control=ctl)
                for rec, numbers in readings:
                    print(json.dumps({
                        "cell": a.cell, "seed": seed, "kind": kind,
                        "round": rec["round"],
                        "K": len(rec["plan"].selected),
                        "b_gen": rec["plan"].b_gen, "numbers": numbers}),
                        flush=True)
                worst = check.worst(n for _, n in readings)
                ok, _ = check.judge(worst, limits, s.window_compiles)
                print(json.dumps({"verdict": kind, "seed": seed,
                                  "correct": ok, "worst": worst}),
                      flush=True)
            print(json.dumps({"seed": seed, "fault": fault,
                              "rounds": len(s.times),
                              "window_compiles": s.window_compiles,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
            del s


if __name__ == "__main__":
    main()
