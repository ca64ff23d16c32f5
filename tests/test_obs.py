"""Tier-1 tests for `repro.obs` — the span/event tracer, metrics registry
and sinks, plus the two hard invariants of the observability layer:

* **bitwise no-perturbation** — attaching an `Obs` tracer to a runner or
  sweep never changes a single RoundLog field relative to the NULL_OBS
  run, on both planner backends, with and without fault schedules;
* **trace validity** — every exported trace.json is Chrome/Perfetto
  loadable: spans closed, non-negative timestamps/durations, compile vs
  execute stages tagged.

Also hosts the library print-lint (structured obs logging replaced the
bare prints; `launch/` CLIs are exempt) and the null-path overhead smoke.
"""
from __future__ import annotations

import functools
import io
import json
import os
import re
import time

import jax
import numpy as np
import pytest

from repro.configs.base import GenFVConfig
from repro.exp import ExperimentSpec, Sweep
from repro.fl.rounds import GenFVRunner, RunConfig, run_payload
from repro.obs import (METRICS_SCHEMA, MetricsRegistry, NULL_OBS, NullObs,
                       Obs, ProgressLogger, Stopwatch, list_metrics_artifacts,
                       load_metrics_artifact, log_line, save_metrics_artifact,
                       stopwatch)
from repro.obs.trace import _NULL_SPAN

FAST = dict(rounds=3, train_size=300, test_size=32, width_mult=0.0625)
FAST_CFG = GenFVConfig(batch_size=8, local_steps=2, num_vehicles=6)

SRC_ROOT = os.path.join(os.path.dirname(__file__), "..", "src", "repro")


class FakeClock:
    """Deterministic monotone clock: every read advances by `step`."""

    def __init__(self, step: float = 1.0):
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


# ---------------------------------------------------------------------------
# Metrics registry.
# ---------------------------------------------------------------------------
def test_registry_counters_gauges_dists():
    m = MetricsRegistry()
    m.count("a")
    m.count("a", 2)
    m.count("a", 1, phase="x")                  # different tags: own key
    m.gauge("g", 5.0)
    m.gauge("g", 7.0)                           # last write wins
    for v in (3.0, 1.0, 2.0):
        m.observe("d", v)
    assert m.counter_value("a") == 3
    assert m.counter_value("a", phase="x") == 1
    assert m.counter_value("missing") == 0
    assert m.gauge_value("g") == 7.0
    assert m.gauge_value("missing", default=-1) == -1
    p = m.payload()
    (d,) = p["dists"]
    assert d == {"name": "d", "tags": {}, "n": 3, "sum": 6.0,
                 "min": 1.0, "max": 3.0}


def test_registry_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.count("c", 1)
    b.count("c", 2)
    a.gauge("g", 1.0)
    b.gauge("g", 9.0)
    a.observe("d", 1.0)
    b.observe("d", 5.0)
    a.merge(b)
    assert a.counter_value("c") == 3
    assert a.gauge_value("g") == 9.0            # other's gauges overwrite
    (d,) = a.payload()["dists"]
    assert (d["n"], d["sum"], d["min"], d["max"]) == (2, 6.0, 1.0, 5.0)


def test_registry_payload_json_ready():
    m = MetricsRegistry()
    m.count("z", tag="t")
    m.count("a")
    m.observe("d", 1.5, stage="compile")
    p = json.loads(json.dumps(m.payload()))     # scalar leaves only
    assert [r["name"] for r in p["counters"]] == ["a", "z"]   # sorted


# ---------------------------------------------------------------------------
# Stopwatch / progress logging.
# ---------------------------------------------------------------------------
def test_stopwatch_live_and_frozen():
    clk = FakeClock(step=1.0)
    with stopwatch(clock=clk) as sw:
        live = sw.elapsed_s                     # one clock read: 1.0
    frozen = sw.elapsed_s
    assert live == 1.0
    assert frozen == 2.0                        # exit read froze it
    assert sw.elapsed_s == frozen               # no more clock reads
    assert isinstance(sw, Stopwatch)


def test_progress_logger_rate_limit_and_force():
    out = io.StringIO()
    clk = FakeClock(step=0.01)                  # 10ms between reads
    pl = ProgressLogger(min_interval_s=0.1, clock=clk, out=out)
    wrote = [pl.emit("k", f"line{i}") for i in range(5)]
    assert wrote[0] and not any(wrote[1:])      # throttled after the first
    assert pl.emit("other", "x")                # per-key, not global
    assert pl.emit("k", "final", force=True)    # force bypasses the limit
    assert out.getvalue().splitlines() == ["line0", "x", "final"]


def test_log_line_records_event_and_renders(capsys):
    obs = Obs(clock=FakeClock(), meta={})
    log_line(obs, "train/x", "round 0 acc=0.1", force=True,
             round=0, accuracy=0.1)
    (ev,) = obs.events
    assert ev["name"] == "log" and ev["tags"]["accuracy"] == 0.1
    log_line(NULL_OBS, "train/x", "null path ok", force=True)
    out = capsys.readouterr().out
    assert "round 0 acc=0.1" in out and "null path ok" in out


# ---------------------------------------------------------------------------
# Span mechanics.
# ---------------------------------------------------------------------------
def test_span_compile_execute_tagging():
    obs = Obs(clock=FakeClock())
    for _ in range(2):
        with obs.span("phase", key=4):
            pass
    with obs.span("phase", key=8):              # new jit key: compiles again
        pass
    with obs.span("untracked"):                 # key=None: never "compile"
        pass
    stages = [e["stage"] for e in obs.events]
    assert stages == ["compile", "execute", "compile", "execute"]
    assert obs.metrics.payload()["dists"] == [
        {"name": "span/phase", "tags": {"stage": "compile"}, "n": 2,
         "sum": pytest.approx(2.0), "min": 1.0, "max": 1.0},
        {"name": "span/phase", "tags": {"stage": "execute"}, "n": 1,
         "sum": 1.0, "min": 1.0, "max": 1.0},
        {"name": "span/untracked", "tags": {"stage": "execute"}, "n": 1,
         "sum": 1.0, "min": 1.0, "max": 1.0}]


def test_span_nesting_and_open_count():
    obs = Obs(clock=FakeClock())
    with obs.span("outer"):
        assert obs.open_spans == 1
        with obs.span("inner"):
            assert obs.open_spans == 2
    assert obs.open_spans == 0
    # inner closes first, so it is appended first
    assert [e["name"] for e in obs.events] == ["inner", "outer"]


def test_tagged_view_merges_tags():
    obs = Obs(clock=FakeClock())
    cell = obs.tagged(cell=3)
    with cell.span("round/plan", round=1):
        pass
    cell.count("planner/rounds")
    cell.event("log", text="x")
    assert obs.events[0]["tags"] == {"cell": 3, "round": 1}
    assert obs.metrics.counter_value("planner/rounds", cell=3) == 1
    nested = cell.tagged(round=9)
    nested.gauge("g", 1.0)
    assert obs.metrics.gauge_value("g", cell=3, round=9) == 1.0


def test_null_obs_surface():
    assert isinstance(NULL_OBS, NullObs) and not NULL_OBS.enabled
    sp = NULL_OBS.span("anything", key=1, tag="x")
    assert sp is _NULL_SPAN                     # one shared no-op span
    with sp as s:
        s.sync = object()                       # swallowed, never read
    NULL_OBS.count("c", 5)
    NULL_OBS.gauge("g", 1.0)
    NULL_OBS.observe("d", 2.0)
    NULL_OBS.event("e", k=1)
    assert NULL_OBS.tagged(cell=1) is NULL_OBS  # no per-cell allocation


def test_null_obs_overhead_smoke():
    """The disabled path must stay in no-op territory: 50k span + metric
    call groups well under a second (generous bound for slow CI hosts)."""
    t0 = time.perf_counter()
    for _ in range(50_000):
        with NULL_OBS.span("round/plan", key=4, round=1):
            pass
        NULL_OBS.count("planner/rounds")
        NULL_OBS.observe("round/t_round", 0.5)
    assert time.perf_counter() - t0 < 2.0


# ---------------------------------------------------------------------------
# Sinks: metrics artifact, Chrome/Perfetto trace.
# ---------------------------------------------------------------------------
def _sample_obs() -> Obs:
    obs = Obs(clock=FakeClock(), meta={"spec": "unit"})
    with obs.span("round/plan", key=4, round=0):
        with obs.span("round/select", round=0):
            pass
    obs.event("log", text="hello")
    with obs.span("round/plan", key=4, round=1, cell=2):
        pass
    obs.count("planner/rounds", 2)
    obs.gauge("fleet/bucket", 4)
    return obs


def test_metrics_artifact_roundtrip(tmp_path):
    obs = _sample_obs()
    path = obs.save_metrics("unit", directory=str(tmp_path))
    assert list_metrics_artifacts(str(tmp_path)) == [path]
    doc = load_metrics_artifact(path)
    assert doc["schema"] == METRICS_SCHEMA
    assert doc["meta"] == {"spec": "unit"}
    assert doc["open_spans"] == 0 and doc["events"] == 4
    assert {"backend", "device_kind", "jax", "platform"} <= set(doc["host"])
    assert doc["host"]["device_kind"] == jax.devices()[0].device_kind
    names = {c["name"] for c in doc["counters"]}
    assert "planner/rounds" in names
    assert any(d["name"] == "span/round/plan" for d in doc["dists"])


def test_metrics_artifact_schema_guard(tmp_path):
    bad = tmp_path / "x.metrics.json"
    bad.write_text(json.dumps({"schema": "something/else"}))
    with pytest.raises(ValueError, match="not a"):
        load_metrics_artifact(str(bad))
    with pytest.raises(ValueError, match="schema"):
        save_metrics_artifact({"schema": "wrong"}, "x",
                              directory=str(tmp_path))


def test_trace_schema(tmp_path):
    obs = _sample_obs()
    path = obs.write_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(path))                 # Perfetto-loadable JSON
    assert doc["otherData"]["schema"] == "repro.obs/trace/v1"
    evs = doc["traceEvents"]
    assert evs and all(e["ts"] >= 0 for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and all(e["dur"] >= 0 for e in xs)
    # spans are appended at close: their end timestamps are monotone
    ends = [e["ts"] + e["dur"] for e in xs]
    assert ends == sorted(ends)
    assert all(e["s"] == "t" for e in evs if e["ph"] == "i")
    # the sweep-cell tag routes to its own track; untagged events share 0
    assert {e["tid"] for e in xs} == {0, 3}
    assert {e["args"]["stage"] for e in xs} == {"compile", "execute"}


def test_trace_refuses_open_spans(tmp_path):
    obs = Obs(clock=FakeClock())
    span = obs.span("dangling")
    span.__enter__()
    with pytest.raises(ValueError, match="open"):
        obs.write_trace(str(tmp_path / "trace.json"))


# ---------------------------------------------------------------------------
# RunConfig plumbing.
# ---------------------------------------------------------------------------
def test_runconfig_obs_field_is_execution_machinery():
    plain = RunConfig(**FAST)
    traced = RunConfig(obs=Obs(clock=FakeClock()), **FAST)
    assert plain == traced                      # compare=False: same cell
    payload = run_payload(traced)
    assert "obs" not in payload
    json.dumps(payload)                         # checkpoint/spec-safe


# ---------------------------------------------------------------------------
# Runner integration: the bitwise no-perturbation invariant + metrics
# content. The traced runs are cached so the parity, ledger and trace
# tests share one training per (planner, faults) combination.
# ---------------------------------------------------------------------------
def _run_cfg(planner: str, faults: str | None) -> RunConfig:
    return RunConfig(strategy="genfv", scenario="rush_hour", seed=0,
                     planner=planner, faults=faults, **FAST)


@functools.lru_cache(maxsize=None)
def _traced(planner: str, faults: str | None):
    obs = Obs(meta={"test": "obs", "planner": planner})
    res = GenFVRunner(_run_cfg(planner, faults), fl_cfg=FAST_CFG,
                      obs=obs).train()
    return obs, res


@pytest.mark.parametrize("planner", ["jax", "numpy"])
@pytest.mark.parametrize("faults", [None, "mixed_stress"])
def test_runner_obs_bitwise_no_perturbation(planner, faults):
    """The hard invariant: an attached tracer only *reads* host values, so
    every RoundLog field — including float curves — is bitwise identical
    to the NULL_OBS run, on both planner backends, faulted or not."""
    obs, traced = _traced(planner, faults)
    plain = GenFVRunner(_run_cfg(planner, faults), fl_cfg=FAST_CFG).train()
    assert len(plain.logs) == FAST["rounds"]
    for a, b in zip(plain.logs, traced.logs):
        assert a == b                           # every field, bitwise
    # the traced run went through a vectorized genfv round that holds
    # every child span of the dispatch, the RSU step and eval
    names = {e["name"] for e in obs.events if e["ph"] == "X"}
    assert set(CHILD_SPANS) <= names, set(CHILD_SPANS) - names


def test_runner_obs_bitwise_ddpm_generate_path(monkeypatch, tmp_path):
    """Same invariant on the AIGC dataplane: the tracer's span around the
    batched sampling dispatch (`round/generate/sample`) and its gen
    counters are read-only, so a ddpm run is bitwise identical with and
    without an attached Obs — and the span actually fires."""
    import repro.gen.service as gen_service
    from repro.gen.calib import CALIB_BUCKET, _calib_key, save_calibration
    from repro.diffusion.ddpm import DDPM

    for k, v in (("RUNNER_TIMESTEPS", 8), ("RUNNER_BASE_WIDTH", 8),
                 ("PRETRAIN_STEPS", 2), ("PRETRAIN_REF", 64)):
        monkeypatch.setattr(gen_service, k, v)
    monkeypatch.setenv("REPRO_ARTIFACTS", str(tmp_path / "artifacts"))
    ddpm = DDPM(timesteps=8, num_classes=10, base_width=8)
    save_calibration({_calib_key(ddpm, 2, CALIB_BUCKET):
                      {"t_image": 0.05, "bucket": CALIB_BUCKET,
                       "sampler_steps": 2}})

    run = RunConfig(strategy="genfv", seed=0, generator="ddpm",
                    sampler_steps=2, **FAST)
    obs = Obs(meta={"test": "obs-gen"})
    traced = GenFVRunner(run, fl_cfg=FAST_CFG, obs=obs).train()
    plain = GenFVRunner(run, fl_cfg=FAST_CFG).train()
    assert len(plain.logs) == FAST["rounds"]
    for a, b in zip(plain.logs, traced.logs):
        assert a == b                           # every field, bitwise
    gen_rounds = sum(1 for l in traced.logs if l.b_gen > 0)
    assert gen_rounds > 0
    spans = [d for d in obs.metrics.payload()["dists"]
             if d["name"] == "span/round/generate/sample"]
    assert spans and sum(d["n"] for d in spans) == gen_rounds
    assert obs.metrics.counter_value("gen/images") == \
        sum(int(l.b_gen) for l in traced.logs)


#: child span -> its parent: the host stacking, the copy and the fused
#: step inside the aggregation; the pool append and the RSU's training
#: inside generation; the test set's copy inside eval
CHILD_SPANS = {"round/aggregate/stack": "round/aggregate",
               "round/aggregate/put": "round/aggregate",
               "round/aggregate/step": "round/aggregate",
               "round/generate/pool": "round/generate",
               "round/generate/train": "round/generate",
               "round/eval/put": "round/eval"}


def test_child_spans_nest_inside_their_parents():
    obs, res = _traced("jax", None)
    spans = [e for e in obs.events if e["ph"] == "X"]
    parents = {p: [e for e in spans if e["name"] == p]
               for p in set(CHILD_SPANS.values())}
    inside = {id(e): [] for ps in parents.values() for e in ps}
    for e in spans:
        parent = CHILD_SPANS.get(e["name"])
        if parent is None:
            continue
        host = [p for p in parents[parent] if p["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= p["ts"] + p["dur"]]
        assert len(host) == 1, e
        inside[id(host[0])].append(e)
    for ps in parents.values():
        for p in ps:
            assert sum(c["dur"] for c in inside[id(p)]) <= p["dur"]
    # one of each child per round that dispatched, generated or evaluated
    count = {n: sum(1 for e in spans if e["name"] == n) for n in CHILD_SPANS}
    fleet_rounds = sum(1 for l in res.logs if l.selected)
    gen_rounds = sum(1 for l in res.logs if l.b_gen)
    assert count == {"round/aggregate/stack": fleet_rounds,
                     "round/aggregate/put": fleet_rounds,
                     "round/aggregate/step": fleet_rounds,
                     "round/generate/pool": gen_rounds,
                     "round/generate/train": FAST["rounds"],
                     "round/eval/put": FAST["rounds"]}


def test_runner_counts_bytes_copied_to_the_device():
    """xfer/h2d_bytes: the padded fleet batch (float32 32x32x3 images and
    int32 labels per slot and step, a float32 weight per slot) and the
    test set; gen/pool_bytes: the RSU's pool after the last append."""
    from repro.fl.fleet import bucket_size
    obs, res = _traced("jax", None)
    h, b = FAST_CFG.local_steps, FAST_CFG.batch_size
    image = 32 * 32 * 3 * 4
    fleet = sum(bucket_size(l.selected) * (h * b * (image + 4) + 4)
                for l in res.logs if l.selected)
    m = obs.metrics
    assert fleet > 0
    assert m.counter_value("xfer/h2d_bytes", site="fleet") == fleet
    assert m.counter_value("xfer/h2d_bytes", site="eval") == \
        FAST["rounds"] * FAST["test_size"] * (image + 4)
    assert m.gauge_value("gen/pool_bytes") == \
        sum(int(l.b_gen) for l in res.logs) * (image + 4)


def test_roundlog_carries_planner_convergence():
    _, res = _traced("jax", None)
    for log in res.logs:
        assert log.bcd_iters >= 1
        assert log.planner_converged in (0, 1)


def test_checkpoint_roundtrips_planner_fields(tmp_path):
    run = _run_cfg("jax", None)
    r = GenFVRunner(run, fl_cfg=FAST_CFG)
    r.run_round(0)
    path = str(tmp_path / "runner.npz")
    r.save_checkpoint(path)
    fresh = GenFVRunner(run, fl_cfg=FAST_CFG)
    assert fresh.load_checkpoint(path) == 1
    assert fresh.logs == r.logs                 # bcd_iters etc. included


def test_runner_metrics_planner_counters():
    obs, res = _traced("jax", None)
    m = obs.metrics
    assert m.counter_value("planner/rounds", planner="jax") == FAST["rounds"]
    converged = m.counter_value("planner/converged", planner="jax")
    assert converged == sum(l.planner_converged for l in res.logs)
    payload = m.payload()
    dists = {(d["name"], d["tags"].get("stage")) for d in payload["dists"]}
    for phase in ("round/fleet", "round/select", "round/plan",
                  "round/local_sgd", "round/generate", "round/aggregate",
                  "round/eval"):
        assert any(n == f"span/{phase}" for n, _ in dists), phase
    # the first jitted plan is traced+compiled; every round is accounted
    assert ("span/round/plan", "compile") in dists
    assert sum(d["n"] for d in payload["dists"]
               if d["name"] == "span/round/plan") == FAST["rounds"]
    # world gauges (scenario fleets come from the persistent world)
    assert m.gauge_value("world/population") is not None


def test_runner_metrics_fault_ledger():
    obs, res = _traced("jax", "mixed_stress")
    m = obs.metrics
    for key in ("late", "rejected", "stale_merged", "dropped"):
        assert m.counter_value(f"faults/{key}") == res.curve(key).sum()
    d = next(d for d in m.payload()["dists"]
             if d["name"] == "round/t_round")
    assert d["n"] == FAST["rounds"]


def test_runner_trace_emission(tmp_path):
    obs, _ = _traced("jax", None)
    assert obs.open_spans == 0
    path = obs.write_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    stages = {e["args"].get("stage") for e in doc["traceEvents"]
              if e["ph"] == "X"}
    assert {"compile", "execute"} <= stages


# ---------------------------------------------------------------------------
# Sweep integration: the ISSUE acceptance grid — 8 cells with obs enabled
# emit a loadable trace + metrics artifact while staying bitwise identical
# to the untraced sweep.
# ---------------------------------------------------------------------------
SWEEP_FAST = dict(rounds=2, train_size=200, test_size=32, width_mult=0.0625)


def _sweep_spec() -> ExperimentSpec:
    return ExperimentSpec(name="obs-accept",
                          strategies=("genfv", "fl_only"),
                          scenarios=("rush_hour", "highway_free_flow"),
                          seeds=(0, 1),
                          base=RunConfig(**SWEEP_FAST))


def test_sweep_obs_emission_and_parity(tmp_path):
    spec = _sweep_spec()
    assert spec.n_cells == 8
    obs = Obs(meta={"spec": spec.name})
    traced = Sweep(spec, fl_cfg=FAST_CFG, obs=obs).run()
    plain = Sweep(spec, fl_cfg=FAST_CFG).run()

    # bitwise parity across the whole grid, incl. the new planner metrics
    assert {"bcd_iters", "planner_converged"} <= set(plain.metrics)
    for k in plain.metrics:
        np.testing.assert_array_equal(traced.metrics[k], plain.metrics[k],
                                      err_msg=k)

    # emission: Perfetto-loadable trace with per-cell tracks + stages
    trace = json.load(open(obs.write_trace(str(tmp_path / "trace.json"))))
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["args"]["stage"] for e in xs} == {"compile", "execute"}
    cell_tracks = {e["tid"] for e in xs if e["tid"] > 0}
    assert cell_tracks == set(range(1, 9))      # all 8 cells traced

    # metrics artifact with planner convergence counters + sweep gauges
    doc = load_metrics_artifact(
        obs.save_metrics(spec.name, directory=str(tmp_path)))
    m = obs.metrics
    assert m.gauge_value("sweep/cells") == 8
    assert m.gauge_value("sweep/planner_dispatches") is not None
    per_cell = sum(m.counter_value("planner/rounds", cell=c, planner="jax")
                   for c in range(8))
    assert per_cell == 8 * SWEEP_FAST["rounds"]
    assert any(c["name"] == "planner/converged" for c in doc["counters"])
    assert any(d["name"].startswith("span/sweep/plan_batched")
               for d in doc["dists"])


# ---------------------------------------------------------------------------
# Library print-lint: structured obs logging only (launch/ CLIs exempt).
# ---------------------------------------------------------------------------
_PRINT_RE = re.compile(r"(?<![\w.])print\(")


def test_no_bare_print_in_library():
    offenders = []
    for dirpath, dirnames, files in os.walk(SRC_ROOT):
        dirnames[:] = [d for d in dirnames
                       if d not in ("launch", "__pycache__")]
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                for i, line in enumerate(f, 1):
                    code = line.split("#", 1)[0]
                    if _PRINT_RE.search(code):
                        offenders.append(
                            f"{os.path.relpath(path, SRC_ROOT)}:{i}")
    assert not offenders, (
        "bare print( in library code — route it through "
        f"repro.obs.log_line / ProgressLogger instead: {offenders}")


# ---------------------------------------------------------------------------
# Clock-discipline lint: the FL round loop and the serving engine must run
# on injectable clocks only (VirtualClock / the Obs clock parameter) so the
# streaming determinism contract (fl/stream.py) can't silently regress.
# ---------------------------------------------------------------------------
_WALLCLOCK_RE = re.compile(r"(?<![\w.])time\.(time|monotonic)\(")


def test_no_wall_clock_in_streaming_paths():
    offenders = []
    for sub in ("fl", "serve"):
        for dirpath, dirnames, files in os.walk(os.path.join(SRC_ROOT, sub)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                with open(path) as f:
                    for i, line in enumerate(f, 1):
                        code = line.split("#", 1)[0]
                        if _WALLCLOCK_RE.search(code):
                            offenders.append(
                                f"{os.path.relpath(path, SRC_ROOT)}:{i}")
    assert not offenders, (
        "time.time()/time.monotonic() in a deterministic streaming path — "
        f"inject a VirtualClock (repro.obs) instead: {offenders}")
