"""Sets of runs of one cell, one process after another, and the spread of
each end-to-end metric, as the bounds are set from.

    python3 benchmarks/chip/tools/sets.py c10.genfv.highway \
        --seeds 11 12 13 14 15 16 --sets 2 [--first 10] \
        [--trace-seeds 21 22] [--out chiprun_out/c10]

`--first` runs one seed before the sets (in a fresh checkout it
compiles; its set-up is recorded apart). Each set runs every seed once
with `--trace 0`; `--trace-seeds` adds traced runs after them. Every
run's stdout and stderr go to `<out>/<n>.<seed>.{out,err}`. At the end
it prints, per set and metric, the median and the spread (the distance
between the first and third quartile of `statistics.quantiles(n=4)`, as
a share of the median), `correct` of every run, and five times the
widest spread. This process never touches JAX: each run owns the chip.
"""
import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).resolve().parents[1] / "run_cell.py"


def one(out: Path, tag: str, workload: str, seed: int, seconds: int,
        trace: int) -> dict:
    t0 = time.perf_counter()
    p = subprocess.run([sys.executable, str(RUN), "--workload", workload,
                        "--seed", str(seed), "--seconds", str(seconds),
                        "--trace", str(trace)], capture_output=True,
                       text=True)
    (out / f"{tag}.{seed}.out").write_text(p.stdout)
    (out / f"{tag}.{seed}.err").write_text(p.stderr)
    lines = p.stdout.strip().splitlines()
    res = json.loads(lines[-1]) if p.returncode == 0 and lines else {}
    row = {"tag": tag, "seed": seed, "trace": trace, "rc": p.returncode,
           "wall_s": time.perf_counter() - t0,
           "correct": res.get("correct"),
           "metrics": {k: v["value"] for k, v in
                       res.get("metrics", {}).items()},
           "checks": res.get("checks"),
           "memory_peak_bytes": res.get("device", {}).get(
               "memory_peak_bytes")}
    print(json.dumps(row), flush=True)
    return row


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("workload")
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--first", type=int, default=None)
    p.add_argument("--trace-seeds", type=int, nargs="*", default=[])
    p.add_argument("--seconds", type=int, default=None)
    p.add_argument("--out", default="chiprun_out/sets")
    a = p.parse_args()
    root = RUN.parents[2]
    seconds = a.seconds or json.loads(
        (root / "BENCHMARK.json").read_text())["run_seconds"]
    out = Path(a.out)
    out.mkdir(parents=True, exist_ok=True)
    if a.first is not None:
        one(out, "first", a.workload, a.first, seconds, 0)
    sets = [[one(out, f"set{i}", a.workload, s, seconds, 0)
             for s in a.seeds] for i in range(a.sets)]
    for s in a.trace_seeds:
        one(out, "trace", a.workload, s, seconds, 1)
    widest = {}
    for i, rows in enumerate(sets):
        ok = [r for r in rows if r["metrics"]]
        for m in sorted({k for r in ok for k in r["metrics"]}):
            vals = [r["metrics"][m] for r in ok if m in r["metrics"]]
            if len(vals) < 2:
                continue
            sp = spread(vals)
            widest[m] = max(widest.get(m, 0.0), sp)
            print(json.dumps({"set": i, "metric": m, "n": len(vals),
                              "median": statistics.median(vals),
                              "spread": sp, "values": vals}))
    print(json.dumps({"widest_spread": widest,
                      "five_times": {m: 5 * v for m, v in widest.items()},
                      "correct": [r["correct"] for rows in sets
                                  for r in rows]}))


if __name__ == "__main__":
    main()
