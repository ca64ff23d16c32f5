"""One cell on one seed: set-up, the measured window, and the readings
of the comparison, in the order a run makes them. `run_cell.py`, the
control tool and the tests drive this same object."""
from __future__ import annotations

import shutil
import time

import jax
import numpy as np

from chipbench import build, capture, check, tracing, warm

WARM_ROUNDS = 2     # rounds run at the end of the set-up
TRACE_ROUNDS = 5    # steady rounds the profiler traces with --trace 1


class CompileLog:
    """Counts compile requests by phase (`hits`: those the persistent
    cache served) and names the programs compiled inside the window."""

    def __init__(self):
        self.phase = "setup"
        self.requests, self.hits, self.names = {}, {}, {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, dur, **kw):
        # fired for every compile request, served from the cache or not
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests[self.phase] = self.requests.get(self.phase, 0) + 1
            if self.phase == "window":
                fn = kw.get("fun_name", "?")
                self.names[fn] = self.names.get(fn, 0) + 1

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits[self.phase] = self.hits.get(self.phase, 0) + 1


class Session:
    def __init__(self, cfgd: dict, celld: dict, seed: int,
                 trace: bool = False):
        self.cfgd, self.celld, self.seed = cfgd, celld, int(seed)
        self.trace = trace
        self.gen = celld["strategy"] in check.GENERATING
        self.comp = CompileLog()

    # ------------------------------------------------------------------
    def setup(self) -> None:
        """Runner, warm-up of the cell's programs, warm rounds."""
        celld, w = self.celld, self.celld["warm"]
        obs = tracing.traced_obs() if self.trace else None
        clock = [time.perf_counter()]
        self.phases = {}

        def lap(name):
            now = time.perf_counter()
            self.phases[name] = now - clock[0]
            clock[0] = now
        self.runner, self.unet_params = build.build_runner(
            self.cfgd, celld, self.seed, obs=obs)
        runner = self.runner
        lap("build")
        n = warm.warm_fleet(runner, w["fleet_buckets"], w["fleet_sizes"],
                            aug=self.gen)
        lap("fleet")
        if self.gen:
            n += warm.warm_sampler(runner, w["sampler_buckets"],
                                   w["sampler_sizes"])
            lap("sampler")
        warm.warm_planner(runner, w["planner_buckets"])
        capture._snapshot(runner.server.params)
        lap("planner")
        self.t = 0
        for _ in range(WARM_ROUNDS):
            self.round()
        lap("warm_rounds")
        self.recorder = capture.Recorder(runner, self.seed)
        self.slices = n

    def round(self, recorder=None):
        runner, t = self.runner, self.t
        t0 = time.perf_counter()
        pending = runner.begin_round(t)
        b_prev = runner.b_prev
        plan = runner.plan(pending)
        if recorder is not None:
            recorder.before(t, pending, plan, b_prev)
        lg = runner.finish_round(pending, plan)
        jax.block_until_ready(runner.server.params)
        dt = time.perf_counter() - t0
        if recorder is not None:
            recorder.after(lg)
        self.t += 1
        return lg, dt

    def window(self, seconds: float, trace_dir=None) -> None:
        """Closed loop of rounds (one at least) until `seconds` have
        passed; with a `trace_dir`, the first `TRACE_ROUNDS` are
        profiled. The profiler records the device and the annotations
        only (no Python tracer, host tracer level 1), so that a traced
        round costs about what an untraced one does."""
        self.comp.phase = "window"
        self.logs, self.times, self.traced = [], [], []
        profiling = trace_dir is not None
        if profiling:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        t_win = time.perf_counter()
        while True:
            if profiling:
                with jax.profiler.TraceAnnotation(tracing.ROUND):
                    lg, dt = self.round(self.recorder)
                self.traced.append(lg)
                if len(self.traced) >= TRACE_ROUNDS:
                    jax.profiler.stop_trace()
                    profiling = False
            else:
                lg, dt = self.round(self.recorder)
            self.logs.append(lg)
            self.times.append(dt)
            if time.perf_counter() - t_win >= seconds:
                break
        self.window_s = time.perf_counter() - t_win
        if profiling:
            jax.profiler.stop_trace()
        self.comp.phase = "after"

    @property
    def window_compiles(self) -> int:
        return self.comp.requests.get("window", 0)

    @property
    def failed(self) -> int:
        return sum(1 for lg in self.logs if not (
            np.isfinite(lg.loss) and 0.0 <= lg.accuracy <= 1.0))

    def summary(self) -> str:
        ks = [lg.selected for lg in self.logs]
        hist = {k: ks.count(k) for k in sorted(set(ks))}
        out = (f"window: {len(self.logs)} rounds in {self.window_s:.3f}s; "
               f"compilations inside the window {self.window_compiles} "
               f"{self.comp.names or ''}; K histogram {hist}; mean b_gen "
               f"{float(np.mean([lg.b_gen for lg in self.logs])):.1f}; "
               f"round_p90_s over {len(self.times)} rounds")
        n = len(self.traced)
        if n:
            # every run does the same rounds, so the traced rounds' cost
            # is read against the first `n` round times of an untraced run
            traced = float(np.mean(self.times[:n]))
            out += f"; the {n} traced rounds {traced:.4f}s a round"
        return out + f"; round times {[round(t, 4) for t in self.times]}"

    # ------------------------------------------------------------------
    def check_ctx(self) -> dict:
        r = self.runner
        return {"cfg": build.cfg_dict(r), "strategy": self.celld["strategy"],
                "model_bits": r.model_bits, "t_image": r.svc.t_per_image,
                "unet_params": self.unet_params, "seed": self.seed,
                "timesteps": self.cfgd["generator"]["timesteps"],
                "sampler_steps": self.celld["sampler_steps"],
                "test": (r.test_imgs, r.test_labels)}

    def readings(self, control: bool = False):
        """Per kept round, the compared numbers of the program against
        the reference, or with `control` of the reference computed one
        precision lower (bfloat16; float32 for the planner) in the
        program's place."""
        import jax.numpy as jnp
        ctx = self.check_ctx()
        out = []
        for rec in self.recorder.records():
            ref = check.reference_outputs(rec, ctx)
            got = (check.reference_outputs(rec, ctx, jnp.bfloat16)
                   if control else check.program_outputs(rec))
            out.append((rec, check.compare(rec, got, ref, ctx)))
        return out
