"""Flattens the newest profiler trace under a directory to the event
lists `chipbench.tracing.reduce` reads, as JSON (a fixture for the tests,
or a record to read by hand).

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tools/dump_trace.py \
        .bench_trace out.json [--rounds N] [--merge-ops]

--rounds keeps the first N traced rounds and the events inside them;
--merge-ops stores the union of the device operations' intervals, which
is all the reduction reads of them, in place of every operation.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import tracing  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("trace_dir")
    p.add_argument("out")
    p.add_argument("--rounds", type=int, default=0)
    p.add_argument("--merge-ops", action="store_true",
                   help="store the union of the device operations' intervals"
                        " (what the reduction reads of them) in place of"
                        " every operation")
    a = p.parse_args()
    ev = tracing.events(a.trace_dir)
    if a.rounds:
        rounds = sorted((s, s + d) for n, s, d in ev["host"]
                        if n == tracing.ROUND)[:a.rounds]
        lo, hi = rounds[0][0], rounds[-1][1]
        ev = {k: [e for e in v if e[1] >= lo and e[1] + e[2] <= hi]
              for k, v in ev.items()}
    if a.merge_ops:
        merged = []
        for _, s, d in sorted(ev["ops"], key=lambda e: e[1]):
            if merged and s <= merged[-1][1] + merged[-1][2]:
                end = max(merged[-1][1] + merged[-1][2], s + d)
                merged[-1][2] = end - merged[-1][1]
            else:
                merged.append(["busy", s, d])
        ev["ops"] = merged
    with open(a.out, "w") as f:
        json.dump(ev, f)
    print({k: len(v) for k, v in ev.items()})


if __name__ == "__main__":
    main()
