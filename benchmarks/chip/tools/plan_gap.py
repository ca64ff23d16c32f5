"""The planner's comparison on every round a window of the cell can reach.

    python3 benchmarks/chip/tools/plan_gap.py c10.genfv.highway \
        [--rounds 402] [--out chiprun_out/plan_gap.jsonl]

The cell's traffic seed fixes every round's fleet, so the planner's
inputs in round t are the same in every run, whatever `--seed` the run
has; a run compares the few rounds it keeps. This replays the round loop
with the device work taken out (`buckets.sim_runner`) and, for every
round, compares with the float64 numpy reference (`reference/planner.py`):
  prog     the program's jitted planner on the default device (the chip),
           as the window runs it
  cpu      the same planner on the host's CPU backend (a second witness)
  numpy    the program's own numpy planner path
  control  the reference in float32
  one_pass the reference stopped after one BCD pass (a planner fault)
  unsolved the reference's starting point, no BCD pass (a planner fault)
One JSON line per round (the gap `plan_gap` of `check.compare`, its parts,
the b* gap and the BCD iterations of each), then one summary line per kind
over the rounds a window can keep (the warm rounds left out).
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE / "tools"), str(HERE.parents[1] / "src")]


def _gaps(got: dict, ref: dict) -> dict:
    if not len(ref["l"]):
        return {"gap": 0.0, "l": 0.0, "phi": 0.0, "t_bar": 0.0,
                "b_gen": float(abs(got["b_gen"] - ref["b_gen"]))}
    parts = {"l": float(np.max(np.abs(got["l"] - ref["l"]))),
             "phi": float(np.max(np.abs(got["phi"] - ref["phi"]))),
             "t_bar": float(abs(got["t_bar"] - ref["t_bar"]))}
    return {"gap": max(parts.values()), **parts,
            "b_gen": float(abs(got["b_gen"] - ref["b_gen"]))}


def _as_dict(plan) -> dict:
    return dict(l=np.asarray(plan.l, np.float64),
                phi=np.asarray(plan.phi, np.float64),
                b_gen=int(plan.b_gen), t_bar=float(plan.t_bar),
                iters=int(plan.bcd_iters))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("cell")
    p.add_argument("--rounds", type=int, default=None)
    p.add_argument("--out", default=None)
    a = p.parse_args()
    import jax

    import buckets
    from chipbench import build, cells, check, session
    from reference import planner as ref_plan
    from repro.core import plan_round

    rounds = a.rounds or buckets.default_rounds()
    celld = cells.cell(a.cell)
    runner = buckets.sim_runner(a.cell, int(celld["traffic_seed"]), rounds)
    cfg = build.cfg_dict(runner)
    h = runner.cfg.local_steps
    cpu = jax.devices("cpu")[0]
    out = open(a.out, "w") if a.out else None
    rows = []
    for t in range(rounds):
        pending = runner.begin_round(t)
        b_prev = runner.b_prev
        plan = runner.plan(pending)
        with jax.default_device(cpu):
            on_cpu = runner.plan(pending)
        kw = dict(b_prev=b_prev, svc=runner.svc, alpha_override=pending.alpha)
        on_numpy = plan_round(runner.cfg, pending.fleet, runner.model_bits,
                              h, planner="numpy", **kw)
        fleet = check._vehicles(pending.fleet)
        args = (cfg, fleet, pending.alpha, runner.model_bits, h, b_prev,
                runner.svc.t_per_image)
        ref = ref_plan.plan(*args, np.float64)
        ctl = ref_plan.plan(*args, np.float32)
        one_pass = ref_plan.plan(dict(cfg, bcd_max_iter=1), *args[1:])
        unsolved = ref_plan.plan(dict(cfg, bcd_max_iter=0), *args[1:])
        row = {"round": t, "K": len(plan.selected), "b_gen": plan.b_gen,
               "iters": {"prog": plan.bcd_iters, "cpu": on_cpu.bcd_iters,
                         "numpy": on_numpy.bcd_iters}}
        for kind, got in (("prog", _as_dict(plan)), ("cpu", _as_dict(on_cpu)),
                          ("numpy", _as_dict(on_numpy)), ("control", ctl),
                          ("one_pass", one_pass), ("unsolved", unsolved)):
            row[kind] = _gaps(got, ref)
        row["prog_vs_cpu"] = _gaps(_as_dict(plan), _as_dict(on_cpu))["gap"]
        rows.append(row)
        line = json.dumps(row)
        if out:
            out.write(line + "\n")
        print(line, flush=True)
        runner.finish_round(pending, plan)
    if out:
        out.close()
    kept = [r for r in rows[session.WARM_ROUNDS:] if r["K"]]
    for kind in ("prog", "cpu", "numpy", "control", "one_pass", "unsolved"):
        g = np.array([r[kind]["gap"] for r in kept])
        order = np.argsort(g)
        print(json.dumps({
            "summary": kind, "rounds": len(g), "max": float(g.max()),
            "min": float(g.min()), "median": float(np.median(g)),
            "over_1e-10": int(np.sum(g > 1e-10)),
            "b_gen_max": max(r[kind]["b_gen"] for r in kept),
            "largest_rounds": [[kept[i]["round"], float(g[i])]
                               for i in order[::-1][:8]],
            "smallest_rounds": [[kept[i]["round"], float(g[i])]
                                for i in order[:8]]}), flush=True)


if __name__ == "__main__":
    main()
