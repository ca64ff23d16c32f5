"""The profiler trace of a few steady rounds, and its reduction.

`TracedObs` is the program's `repro.obs.Obs` with every span also opened
as a `jax.profiler.TraceAnnotation` of the same name, so the round loop's
phases land on the profiler's clock beside the device's operations. The
harness adds a `bench/round` annotation around each traced round.

`events(xplane_path)` flattens the trace to
  {"host": [[name, start_ns, dur_ns], ...],          host annotations
   "modules": [[name, start_ns, dur_ns], ...],       device programs
   "ops": [[name, start_ns, dur_ns], ...]}           device operations
(the first TPU device plane's "XLA Modules" and "XLA Ops" lines), and
`reduce(ev)` turns that into per-round span seconds, device busy time and
program times. Tests hold `reduce` to a recorded fixture.
"""
from __future__ import annotations

import glob
import os

ROUND = "bench/round"


def traced_obs():
    import jax
    from repro.obs import Obs
    from repro.obs.trace import Span

    class _Span(Span):
        __slots__ = ("_ann",)

        def __enter__(self):
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
            return super().__enter__()

        def __exit__(self, *exc):
            out = super().__exit__(*exc)      # fences `sync` first
            self._ann.__exit__(*exc)
            return out

    class TracedObs(Obs):
        def span(self, name, key=None, **tags):
            return _Span(self, name, key, tags)

    return TracedObs()


def events(log_dir: str) -> dict:
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    out = {"host": [], "modules": [], "ops": []}
    device_done = False
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("round/", "bench/")):
                        out["host"].append([e.name, e.start_ns,
                                            e.duration_ns])
        elif name.startswith("/device:") and not device_done \
                and "CPU" not in name:
            lines = {ln.name: ln for ln in plane.lines}
            for key, line_name in (("modules", "XLA Modules"),
                                   ("ops", "XLA Ops")):
                if line_name in lines:
                    out[key] = [[e.name, e.start_ns, e.duration_ns]
                                for e in lines[line_name].events]
            device_done = bool(out["modules"] or out["ops"])
    return out


def _union(intervals):
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def reduce(ev: dict) -> dict:
    """Per-round spans, device busy time and program times of the traced
    rounds. Times in seconds."""
    rounds = sorted((s, s + d) for n, s, d in ev["host"] if n == ROUND)
    if not rounds:
        return {"rounds": [], "window_s": 0.0, "busy_s": 0.0,
                "modules": {}, "gaps": []}
    w0, w1 = rounds[0][0], rounds[-1][1]
    per_round = []
    for s, e in rounds:
        spans = {}
        for n, t, d in ev["host"]:
            if n != ROUND and s <= t and t + d <= e:
                spans[n] = spans.get(n, 0.0) + d * 1e-9
        per_round.append(spans)
    dev = ev["ops"] or ev["modules"]
    iv = [(max(t, w0), min(t + d, w1)) for _, t, d in dev
          if t + d > w0 and t < w1]
    busy = _union(iv) * 1e-9
    modules = {}
    for n, t, d in ev["modules"]:
        if t + d > w0 and t < w1:
            modules[n] = modules.get(n, 0.0) + d * 1e-9
    # idle gaps between device work, named by the innermost host span open
    # at the gap's middle
    gaps, end = [], w0
    for s, e in sorted(iv) + [(w1, w1)]:
        if s > end:
            mid = (s + end) / 2
            open_ = [(t, n) for n, t, d in ev["host"]
                     if n != ROUND and t <= mid <= t + d]
            label = max(open_)[1] if open_ else "between spans"
            gaps.append([label, (s - end) * 1e-9])
        end = max(end, e)
    return {"rounds": per_round, "window_s": (w1 - w0) * 1e-9,
            "busy_s": busy, "modules": modules, "gaps": gaps}


def module_time(red: dict, fragment: str):
    """Seconds of device programs whose name contains `fragment`, or None
    where none ran."""
    hits = [v for k, v in red["modules"].items() if fragment in k]
    return sum(hits) if hits else None


def breakdown(red: dict, top: int = 10) -> dict:
    ops = sorted(red["modules"].items(), key=lambda kv: -kv[1])[:top]
    by_label = {}
    for label, s in red["gaps"]:
        by_label[label] = by_label.get(label, 0.0) + s
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
