"""One run of one cell of the GenFV round-loop benchmark on one TPU.

    python3 benchmarks/chip/run_cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Builds the round loop (`repro.fl.GenFVRunner`) from the cell's files with
inputs and weights made from the seed, warms every program the cell's
window drives, then runs rounds in a closed loop (`begin_round`, `plan`,
`finish_round`, fenced on the global params) until `--seconds` have
passed. After the window it compares a sample of the window's rounds with
the plain references and prints one JSON line as the last line of stdout:
`correct`, `attempted` (rounds), `failed`, `metrics`, `device`, with
`--trace 1` also `breakdown`, and last `checks` (each compared number with
its limit). With `--trace 0` the metrics are the cell's end-to-end ones;
with `--trace 1` its per-layer ones, read from a profiler trace of a few
steady rounds. Exits nonzero, with no result line, where JAX finds no TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _dir_bytes(path: Path) -> int:
    return sum(f.stat().st_size for f in path.rglob("*") if f.is_file()) \
        if path.exists() else 0


def use_cache(jax) -> dict:
    """The persistent compile cache where JAX_COMPILATION_CACHE_DIR says,
    else at the fixed <checkout>/.jax_cache, with no size cap in this
    process, so that every program of a cell stays in it (the largest, the
    RSU's 16-step local SGD, is about 200 MB), and no minimum compile time,
    so that a warm run loads every program, the small per-size slices
    included."""
    found = jax.config.jax_compilation_cache_max_size
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return {"dir": path, "cap_found": found, "cap_set": -1,
            "bytes_before": _dir_bytes(Path(path))}


def main(argv=None) -> int:
    args = parse(argv)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"run_cell: needs a TPU; JAX found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind})")
        return 2
    from chipbench import cells, peaks
    bench = cells.benchmark()
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        log(f"run_cell: no workload {args.workload!r} in BENCHMARK.json")
        return 2
    if len(devs) < entry["chips"]:
        log(f"run_cell: the cell needs {entry['chips']} chips, JAX found "
            f"{len(devs)}")
        return 2
    celld = cells.cell(args.workload)
    cfgd = cells.config(celld["config"])
    result = run(args, bench, celld, cfgd, peaks.peak(devs[0].device_kind))
    print(json.dumps(result), flush=True)
    return 0


def run(args, bench, celld, cfgd, peak) -> dict:
    """Set-up, window, metrics and comparison of one run; returns the
    result object. Checks no device (`main` does)."""
    import jax
    from chipbench import cells, check, flops, tracing
    from chipbench.session import Session

    devs = jax.devices()
    cache = use_cache(jax)
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    s = Session(cfgd, celld, args.seed, trace=bool(args.trace))
    s.setup()
    setup_s = time.perf_counter() - T_START
    log(f"setup: {setup_s:.3f}s {s.phases} ({s.slices} slice programs "
        f"warmed); "
        f"compile requests {s.comp.requests.get('setup', 0)}, of which the "
        f"persistent cache served {s.comp.hits.get('setup', 0)}")
    s.window(args.seconds, TRACE_DIR if args.trace else None)
    mem = devs[0].memory_stats() or {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    log(s.summary())
    cache["bytes_written"] = _dir_bytes(Path(cache["dir"])) \
        - cache["bytes_before"]
    log(f"compile cache: {cache}")

    metrics, breakdown = {}, None
    if args.trace:
        red = tracing.reduce(tracing.events(str(TRACE_DIR)))
        ctx = {"red": red, "config": cfgd, "cell": celld, "peak": peak,
               "flops": flops, "module_time": tracing.module_time,
               "rsu_steps_factor": s.runner.cfg.rsu_steps_factor,
               "rounds": [{"k": lg.selected, "b_gen": lg.b_gen}
                          for lg in s.traced]}
        for m in cells.cell_metrics(bench, args.workload, trace=True):
            v = cells.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = tracing.breakdown(red)
    else:
        times = s.times
        values = {"round_s": s.window_s / len(times),
                  "round_p90_s": (statistics.quantiles(times, n=10)[-1]
                                  if len(times) > 1 else times[0]),
                  "setup_s": setup_s}
        for m in cells.cell_metrics(bench, args.workload, trace=False):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    t_ref = time.perf_counter()
    readings = s.readings()
    for rec, numbers in readings:
        log(f"check round {rec['round']} (K={len(rec['plan'].selected)}, "
            f"b_gen={rec['plan'].b_gen}): {numbers}")
    ok, rows = check.judge(check.worst(n for _, n in readings),
                           celld["check"]["limits"], s.window_compiles)
    ok = ok and bool(readings) and s.failed == 0
    log(f"reference: {time.perf_counter() - t_ref:.3f}s over "
        f"{len(readings)} rounds")
    for name, v, lim in rows:
        log(f"{name} {v!r} limit {lim!r}")
    result = {"correct": bool(ok), "attempted": len(s.times),
              "failed": s.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in rows}
    return result


if __name__ == "__main__":
    sys.exit(main())
