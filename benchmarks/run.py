"""Benchmark driver: one module per paper table/figure + the roofline
reader. Prints ``name,us_per_call,derived`` CSV lines.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig5,...]
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks.common import stopwatch
from benchmarks import (bench_faults, bench_gen, bench_planner,
                        bench_rounds, bench_stream, bench_sweep,
                        bench_world, fig5_emd, fig6_selection, fig7_power,
                        fig8_subproblems, fig9_generation, fig10_noniid,
                        roofline, theorem1)
from repro.compile_cache import use_compile_cache

MODULES = {
    "fig5": fig5_emd.run,
    "fig6": fig6_selection.run,
    "fig7": fig7_power.run,
    "fig8": fig8_subproblems.run,
    "fig9": fig9_generation.run,
    "fig10": fig10_noniid.run,
    "theorem1": theorem1.run,
    "roofline": roofline.run,
    "rounds": bench_rounds.run,          # quick sweep; full: -m benchmarks.bench_rounds
    "world": bench_world.run,            # sim world; full: -m benchmarks.bench_world
    "planner": bench_planner.run,        # two-scale planner; full: -m benchmarks.bench_planner
    "sweep": bench_sweep.run,            # repro.exp grid; full: -m benchmarks.bench_sweep
    "faults": bench_faults.run,          # fault schedules; full: -m benchmarks.bench_faults
    "stream": bench_stream.run,          # quorum streaming; full: -m benchmarks.bench_stream
    "gen": bench_gen.run,                # AIGC dataplane; full: -m benchmarks.bench_gen
}

# FL-training-heavy modules skipped under --quick (the `sweep` smoke still
# exercises the grid/batched-planning path end-to-end there)
HEAVY = ("fig6", "fig10", "theorem1")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module keys")
    ap.add_argument("--quick", action="store_true",
                    help=f"skip the FL-training figures {HEAVY}")
    args = ap.parse_args()
    use_compile_cache()

    keys = list(MODULES)
    if args.only:
        keys = [k for k in args.only.split(",") if k in MODULES]
    if args.quick:
        keys = [k for k in keys if k not in HEAVY]

    print("name,us_per_call,derived")
    failures = 0
    for k in keys:
        with stopwatch() as sw:
            try:
                MODULES[k]()
            except Exception as e:
                failures += 1
                print(f"{k}/FAILED,0.00,{type(e).__name__}: {e}")
                traceback.print_exc(file=sys.stderr)
        print(f"{k}/module_total,{sw.elapsed_s * 1e6:.0f},")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
