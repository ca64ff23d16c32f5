"""What tracing costs a round of a benchmark cell on one TPU, split into
the program's own spans and the profiler.

    python3 trace_cost.py --workload c10.genfv.highway --seed 7 \
        [spans] [profiled] [host0] [device_off] ...

Each run named builds the cell afresh (the harness's `Session`, so every
run does the same rounds from round 0), in the order given, in this one
process (default: spans, profiled three times, host0, device_off):

* spans: the program's tracer attached and no profiler, for the
  benchmark's `run_seconds`: the time of each round, each span's seconds
  in each round, the bytes each round copied to the device
  (`xfer/h2d_bytes` by site) and the RSU pool's size after it
  (`gen/pool_bytes`);
* profiled: `run_cell.py --trace 1` with a `PROFILED_SECONDS` window (its
  result line, and the time of each round);
* host0, device_off: the first five window rounds under the profiler
  with the host tracer at level 0, or with the TPU's device tracer off
  (host tracer level 1, as the harness has it).

The profiler's cost depends on how many profiler sessions the process
has run before (the first costs most), so the order is part of the
measurement. The untraced times are `run_cell.py --trace 0`'s own (its
stderr). The result goes to `chiprun_out/trace_cost/<workload>.<seed>.json`
and a summary to stderr. Exits nonzero where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CHIP = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(CHIP), str(ROOT / "src")]
OUT = ROOT / "chiprun_out" / "trace_cost"
TRACE_DIR = ROOT / ".bench_trace"
KEPT = 5            # window rounds the profiler traces (rounds 2-6)
#: the per-layer metrics read only the first KEPT window rounds
PROFILED_SECONDS = 15.0
DEFAULT_RUNS = ("spans", "profiled", "profiled", "profiled", "host0",
                "device_off")

#: profiler options besides the harness's (python tracer 0, host tracer 1)
OPTIONS = {"host0": {"host_tracer_level": 0},
           "device_off": {"host_tracer_level": 1,
                          "advanced_configuration":
                              {"tpu_trace_mode": "TRACE_ONLY_HOST"}}}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def median(xs):
    return statistics.median(xs) if xs else None


def span_table(rounds, names):
    """Per span: median seconds a round over the first KEPT rounds and
    over all rounds (0 where a round lacks the span)."""
    return {n: {"kept": median([r["spans"].get(n, 0.0)
                                for r in rounds[:KEPT]]),
                "window": median([r["spans"].get(n, 0.0) for r in rounds])}
            for n in names}


def spans(cfgd, celld, seed: int, seconds: float) -> dict:
    """The window with the program's tracer on and no profiler."""
    from chipbench.session import Session
    s = Session(cfgd, celld, seed, trace=True)
    s.setup()
    obs, rounds, inner = s.runner.obs, [], s.round
    m = obs.metrics

    def h2d():
        return {site: m.counter_value("xfer/h2d_bytes", site=site)
                for site in ("fleet", "eval")}

    def traced_round(recorder=None):
        first, before = len(obs.events), h2d()
        lg, dt = inner(recorder)
        per = {}
        for ev in obs.events[first:]:
            if ev["ph"] == "X":
                per[ev["name"]] = per.get(ev["name"], 0.0) + ev["dur"]
        after = h2d()
        rounds.append({"round": lg.round, "k": lg.selected,
                       "b_gen": lg.b_gen, "s": dt, "spans": per,
                       "h2d_bytes": {k: after[k] - before[k] for k in after},
                       "pool_bytes": m.gauge_value("gen/pool_bytes")})
        return lg, dt

    s.round = traced_round
    s.window(seconds)
    names = sorted({n for r in rounds for n in r["spans"]})
    out = {"rounds": rounds, "window_s": s.window_s,
           "round_s": s.window_s / len(rounds),
           "window_compiles": s.window_compiles,
           "spans": span_table(rounds, names)}
    del s
    gc.collect()
    return out


@contextlib.contextmanager
def grab_sessions(into: list):
    """Keeps each Session whose window runs, for its round times."""
    from chipbench.session import Session
    window = Session.window

    def grabbing(self, *a, **kw):
        into.append(self)
        return window(self, *a, **kw)
    Session.window = grabbing
    try:
        yield
    finally:
        Session.window = window


def profiled(bench, cfgd, celld, seed: int, seconds: float, peak) -> dict:
    """One `run_cell.py --trace 1` run in this process."""
    import run_cell
    args = argparse.Namespace(workload=celld["name"], seed=seed,
                              seconds=seconds, trace=1)
    grabbed = []
    with grab_sessions(grabbed):
        result = run_cell.run(args, bench, celld, cfgd, peak)
    s = grabbed[-1]
    out = {"result": result, "times": s.times,
           "ks": [lg.selected for lg in s.logs]}
    grabbed.clear()
    del s
    gc.collect()
    return out


def option_run(cfgd, celld, seed: int, fields: dict) -> dict:
    """The first KEPT window rounds under the profiler with `fields` set
    on its options, each in a `bench/round` annotation as the harness
    does; their times, and what the trace holds."""
    import shutil

    import jax
    from chipbench import tracing
    from chipbench.session import Session
    s = Session(cfgd, celld, seed, trace=True)
    s.setup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    for k, v in fields.items():
        setattr(opts, k, v)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    times = []
    for _ in range(KEPT):
        with jax.profiler.TraceAnnotation(tracing.ROUND):
            times.append(s.round()[1])
    jax.profiler.stop_trace()
    ev = tracing.events(str(TRACE_DIR))
    red = tracing.reduce(ev)
    out = {"times": times, "host_events": len(ev["host"]),
           "device_ops": len(ev["ops"]), "busy_s": red["busy_s"],
           "window_s": red["window_s"],
           "breakdown": tracing.breakdown(red) if red["rounds"] else None}
    del s
    gc.collect()
    return out


def summary(runs) -> None:
    for i, (name, r) in enumerate(runs):
        if name == "spans":
            log(f"{i} spans on, profiler off: round_s {r['round_s']:.4f}; "
                f"rounds 2-6 {[round(x['s'], 4) for x in r['rounds'][:KEPT]]}"
                f"; {r['window_compiles']} compilations in the window")
            for n, v in r["spans"].items():
                log(f"  {n}: median {v['kept']:.5f}s (rounds 2-6), "
                    f"{v['window']:.5f}s (window)")
            pool = [x["pool_bytes"] for x in r["rounds"]]
            log(f"  gen/pool_bytes first {pool[0]} last {pool[-1]} "
                f"({len(pool)} rounds)")
        elif name == "profiled":
            res = r["result"]
            metrics = {k: round(v["value"], 6)
                       for k, v in res["metrics"].items()}
            log(f"{i} profiled: correct {res['correct']}; rounds 2-6 "
                f"{[round(t, 4) for t in r['times'][:KEPT]]}; {metrics}; "
                f"idle gaps {res['breakdown']['idle_gaps'][:5]}")
        else:
            log(f"{i} {name}: rounds 2-6 {[round(t, 4) for t in r['times']]}"
                f" mean {statistics.mean(r['times']):.4f}; host events "
                f"{r['host_events']}, device ops {r['device_ops']}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("runs", nargs="*", default=list(DEFAULT_RUNS),
                   choices=("spans", "profiled") + tuple(OPTIONS))
    args = p.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"trace_cost: needs a TPU; JAX found platform {dev.platform!r}")
        return 2
    import run_cell
    from chipbench import cells, peaks
    run_cell.use_cache(jax)
    bench = cells.benchmark()
    celld = cells.cell(args.workload)
    cfgd = cells.config(celld["config"])
    t0 = time.perf_counter()
    doc = {"workload": args.workload, "seed": args.seed,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())}}
    peak = peaks.peak(dev.device_kind)
    runs = []
    for i, name in enumerate(args.runs):
        seed = args.seed + i
        if name == "spans":
            r = spans(cfgd, celld, seed, bench["run_seconds"])
        elif name == "profiled":
            r = profiled(bench, cfgd, celld, seed, PROFILED_SECONDS, peak)
        else:
            r = option_run(cfgd, celld, seed, OPTIONS[name])
        runs.append((name, r))
    doc["runs"] = [{"run": name, **r} for name, r in runs]
    OUT.mkdir(parents=True, exist_ok=True)
    path = OUT / f"{args.workload}.{args.seed}.json"
    path.write_text(json.dumps(doc))
    summary(runs)
    log(f"trace_cost: {time.perf_counter() - t0:.1f}s; wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
