"""GenFV round orchestration (paper Fig. 2 workflow + Algorithm 3), plus the
baseline schemes of Sec. VI-B: FedAvg, No-EMD, OCEAN-a, MADCA-FL, FL-only,
AIGC-only.

Each round:
  1. label sharing: vehicles report label histograms -> EMD_n
  2. SUBP1 selection (strategy-dependent)
  3. SUBP2-4 resource allocation (two-scale BCD) -> RoundPlan + delay ledger
  4. selected vehicles run h local SGD steps
  5. RSU generates b images (SUBP4 schedule) and trains the augmented model
  6. EMD-weighted aggregation (eq. 4)
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import read_manifest, restore_tree, save_tree
from repro.configs.base import GenFVConfig, StreamConfig
from repro.configs.genfv_cifar import CNNConfig, cnn_config
from repro.core import mobility, plan_round
from repro.core.emd import add_weighted, tree_finite
from repro.core.generation import label_schedule
from repro.core.planner import RoundPlan
from repro.core.selection import (dropout_mask, select, select_madca,
                                  select_no_emd, select_ocean, select_random)
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import DATASET_CLASSES, make_image_dataset
from repro.fl.client import client_update, local_sgd
from repro.fl.faults import (FaultInjector, FaultSpec, StaleBuffer,
                             StaleEntry, fault_names, get_fault,
                             realized_times)
from repro.fl.fleet import FleetEngine, bucket_size
from repro.fl.generator import OracleGenerator
from repro.fl.server import GenFVServer
from repro.models.cnn import cnn_forward, init_cnn
from repro.obs import NULL_OBS, Obs, log_line
from repro.sim import LEGACY, VehicularWorld, WorldState, get_scenario, \
    scenario_names

STRATEGIES = ("genfv", "fedavg", "no_emd", "madca", "ocean",
              "fl_only", "aigc_only", "fedprox")

#: SUBP2-4 backends understood by core/two_scale.py::plan_round.
PLANNERS = ("jax", "numpy")

#: AIGC services the round loop can serve SUBP4 schedules with: "oracle"
#: is the procedural quality-gap sampler (pinned fast reference, bitwise
#: frozen), "ddpm" the real batched diffusion dataplane (repro.gen) with
#: measured per-image cost fed into the eq. 12-13 delay terms.
GENERATORS = ("oracle", "ddpm")

# moderate client lr: high-lr few-class local models drift into incompatible
# basins and weight-average destructively
CLIENT_LR = 5e-2


def validate_run_fields(strategy: str, scenario: str, planner: str,
                        dataset: str, faults: str | None = None) -> None:
    """Registry validation shared by `RunConfig` and `repro.exp`'s
    `ExperimentSpec`: unknown names used to fail deep inside the round loop
    (or silently fall through string compares in `_alpha`); now they raise
    at construction with the valid names spelled out."""
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}; valid: "
                         f"{', '.join(STRATEGIES)}")
    if scenario != LEGACY and scenario not in scenario_names():
        raise ValueError(
            f"unknown scenario {scenario!r}; registered: "
            f"{', '.join(scenario_names())} (or {LEGACY!r} for the "
            f"memoryless seed sampler)")
    if planner not in PLANNERS:
        raise ValueError(f"unknown planner {planner!r}; valid: "
                         f"{', '.join(PLANNERS)}")
    if dataset not in DATASET_CLASSES:
        raise ValueError(f"unknown dataset {dataset!r}; valid: "
                         f"{', '.join(DATASET_CLASSES)}")
    if faults is not None and faults not in fault_names():
        raise ValueError(f"unknown fault schedule {faults!r}; registered: "
                         f"{', '.join(fault_names())} (or None for a "
                         "fault-free run)")


def eval_stream_seed(seed: int) -> int:
    """RNG seed of the held-out eval set for run seed `seed`.

    The seed's `seed + 999` scheme collided under seed sweeps: cell 0's
    eval set drew from the same stream as cell 999's train set. Spawning a
    child `SeedSequence` instead gives every run seed an eval stream that
    no integer root seed (and no other run's spawn) can reproduce."""
    child = np.random.SeedSequence(seed).spawn(1)[0]
    return int(child.generate_state(1, np.uint64)[0])


@dataclass(frozen=True)
class RunConfig:
    """One experiment cell: frozen so `repro.exp` grids can expand, hash and
    serialize cells; validated at construction (`validate_run_fields`)."""
    dataset: str = "cifar10"
    alpha: float = 0.1
    rounds: int = 20
    strategy: str = "genfv"
    train_size: int = 4000
    test_size: int = 512
    width_mult: float = 0.25
    seed: int = 0
    model_bits: float | None = None      # default: 32 bits/param of the CNN
    vectorized: bool = True              # fused fleet engine vs sequential
                                         # per-vehicle reference path
    # Fleet source: a repro.sim scenario name (persistent world, default) or
    # "legacy" for the seed's memoryless per-round i.i.d. sampler.
    scenario: str = "highway_free_flow"
    # SUBP2-4 backend: "jax" (jitted/batched XLA kernel, default) or
    # "numpy" (host reference solver; pins the paper math bit-for-bit)
    planner: str = "jax"
    # Named fault schedule from fl/faults.py's registry, or None for the
    # fault-free loop (which then executes byte-identically to the seed:
    # tests/test_faults.py pins the no-injection equivalence).
    faults: str | None = None
    # Streaming round policy (configs/base.py::StreamConfig) consumed by
    # `repro.fl.stream.StreamEngine`; ignored by the synchronous `train()`
    # loop. None means "no streaming policy configured" (StreamEngine then
    # uses StreamConfig() defaults, which reproduce sync semantics). A plain
    # dict is coerced so checkpoint/spec payloads round-trip through JSON.
    stream: StreamConfig | None = None
    # AIGC service (GENERATORS): "oracle" or "ddpm" (repro.gen dataplane).
    generator: str = "oracle"
    # DDIM-style stride of the DDPM's full noise schedule — the SUBP4
    # quality/cost dial, swept as an ExperimentSpec axis. Ignored by the
    # oracle (which has no denoising loop).
    sampler_steps: int = 50
    # Observability handle (repro.obs): an `Obs` tracer/metrics registry,
    # or None for the zero-overhead null path. Excluded from equality,
    # hashing and serialization (`run_payload`) — two runs differing only
    # in obs are the same experiment, and attaching a tracer must never
    # change what the run computes (tests/test_obs.py pins bitwise parity).
    obs: Obs | None = field(default=None, compare=False, repr=False)

    def __post_init__(self):
        validate_run_fields(self.strategy, self.scenario, self.planner,
                            self.dataset, self.faults)
        if self.generator not in GENERATORS:
            raise ValueError(f"unknown generator {self.generator!r}; "
                             f"valid: {', '.join(GENERATORS)}")
        if self.sampler_steps < 1:
            raise ValueError(
                f"sampler_steps must be >= 1, got {self.sampler_steps}")
        if isinstance(self.stream, dict):
            # frozen dataclass: rehydrate a JSON payload in place
            object.__setattr__(self, "stream",
                               StreamConfig.from_payload(self.stream))


def run_payload(run: "RunConfig") -> dict:
    """JSON-ready dict of the fields that identify the experiment — every
    RunConfig field except the `obs` handle (execution machinery, not
    configuration). Checkpoint fingerprints and sweep/spec artifacts all
    serialize through here so an attached tracer never leaks into (or
    invalidates) persisted state. The nested StreamConfig flattens to a
    plain dict (RunConfig.__post_init__ coerces it back)."""
    return {f.name: (getattr(run, f.name).to_payload()
                     if f.name == "stream" and run.stream is not None
                     else getattr(run, f.name))
            for f in dataclasses.fields(run) if f.name != "obs"}


@dataclass
class RoundLog:
    round: int
    selected: int
    t_bar: float
    b_gen: int
    kappa2: float
    emd_bar: float
    loss: float
    accuracy: float
    dropped: int = 0     # selected vehicles that left coverage mid-round
    # -- fault-tolerance ledger (fl/faults.py; all zero on fault-free runs) --
    late: int = 0          # missed the round deadline (straggler/outage)
    rejected: int = 0      # non-finite (poisoned) updates the guard refused
    stale_merged: int = 0  # buffered late updates merged this round
    stale_dropped: int = 0  # buffered updates aged past max_staleness
    t_round: float = 0.0   # realized wall-clock (= t_bar without faults)
    # -- planner diagnostics (core/planner.py; previously dropped) ---------
    bcd_iters: int = 0         # SUBP2-4 BCD outer iterations this round
    planner_converged: int = 1  # 0 iff the BCD hit its iteration cap


@dataclass
class RunResult:
    logs: List[RoundLog] = field(default_factory=list)

    def curve(self, key: str) -> np.ndarray:
        return np.array([getattr(l, key) for l in self.logs])


@dataclass
class PendingRound:
    """A round between `begin_round` (fleet + SUBP1 done) and
    `finish_round` (waiting on its SUBP2-4 `RoundPlan`)."""
    t: int
    fleet: List
    parts: np.ndarray
    alpha: np.ndarray


class GenFVRunner:
    #: manifest schema of `save_checkpoint` (bump on layout changes; v2
    #: added the RoundLog planner diagnostics bcd_iters/planner_converged,
    #: v3 the stale_dropped ledger column and the streaming-state block
    #: `repro.fl.stream.StreamEngine` appends, v4 the "gen" block recording
    #: the measured AIGC service so a resumed ddpm run replans against the
    #: RECORDED t0 instead of re-measuring — re-measurement would jitter
    #: eq. 48's b* and break bitwise resume)
    CKPT_SCHEMA = "repro.fl/runner-ckpt/v4"

    def __init__(self, run: RunConfig, fl_cfg: GenFVConfig | None = None,
                 generator=None, engine: FleetEngine | None = None,
                 dataset_fn: Callable | None = None,
                 faults: FaultSpec | None = None, obs=None, svc=None):
        self.run = run
        # explicit obs overrides the RunConfig handle (Sweep injects a
        # cell-tagged view of its shared tracer); default is the null path
        self.obs = obs if obs is not None else (
            run.obs if run.obs is not None else NULL_OBS)
        self.cfg = fl_cfg or GenFVConfig(dirichlet_alpha=run.alpha)
        self.scenario = None if run.scenario == LEGACY \
            else get_scenario(run.scenario)
        if self.scenario is not None:
            # overlay the scenario's physical-layer overrides (speed law,
            # geometry, arrival rate, shadowing) onto the FL config
            self.cfg = self.scenario.apply(self.cfg)
        self.rng = np.random.default_rng(run.seed)
        self.cnn_cfg: CNNConfig = cnn_config(run.dataset, run.width_mult)
        classes = DATASET_CLASSES[run.dataset]

        # dataset_fn lets repro.exp's Sweep share one dataset build across
        # grid cells (identical (name, n, seed) calls -> identical arrays,
        # so the cache is exact, not approximate)
        dataset_fn = dataset_fn or make_image_dataset
        imgs, labels = dataset_fn(run.dataset, run.train_size, seed=run.seed)
        self.test_imgs, self.test_labels = dataset_fn(
            run.dataset, run.test_size, seed=eval_stream_seed(run.seed))
        parts = dirichlet_partition(labels, self.cfg.num_vehicles, run.alpha,
                                    self.rng)
        self.client_data = [(imgs[ix], labels[ix]) for ix in parts]
        self.hists = [np.bincount(labels[ix], minlength=classes) /
                      max(len(ix), 1) for ix in parts]
        self.sizes = [len(ix) for ix in parts]
        # persistent world: one data partition per vehicle residency
        self.world = None if self.scenario is None else VehicularWorld(
            self.cfg, self.scenario, n_partitions=len(self.client_data),
            rng=self.rng)

        key = jax.random.PRNGKey(run.seed)
        params = init_cnn(key, self.cnn_cfg)
        n_params = sum(x.size for x in jax.tree.leaves(params))
        # explicit None check: model_bits=0.0 is a legal override (free comms)
        self.model_bits = (run.model_bits if run.model_bits is not None
                           else n_params * 32.0)
        # AIGC service selection. `generator`/`svc` injections override the
        # RunConfig (Sweep factories, tests); otherwise run.generator picks
        # the dataplane. The oracle path keeps svc=None so plan_round
        # constructs the assumed DiffusionService exactly as the seed did
        # (bitwise-frozen reference); the ddpm path prices eq. 48 against
        # the measured per-image wall-clock of the real sampler. Lazy
        # imports: repro.gen reaches repro.exp.artifacts, which would cycle
        # at module import time.
        self.svc = svc
        gen = generator
        if gen is None:
            if run.generator == "ddpm":
                from repro.gen.calib import calibrated_service
                from repro.gen.service import make_ddpm_generator
                gen = make_ddpm_generator(run.dataset, classes, run.seed,
                                          run.sampler_steps, obs=self.obs)
                if self.svc is None:
                    self.svc = calibrated_service(gen.params, gen.ddpm,
                                                  run.sampler_steps)
            else:
                gen = OracleGenerator(run.dataset)
        self.server = GenFVServer(self.cnn_cfg, params, gen, self.rng,
                                  obs=self.obs)
        # max_bucket at the hard ceiling: fleet size is Poisson(num_vehicles),
        # so K can exceed the engine's conservative default cap; buckets
        # compile lazily, an unused headroom costs nothing. An injected
        # engine (Sweep shares one per model shape) must match this runner's
        # dispatch signature exactly.
        if engine is not None:
            if (engine.cfg != self.cnn_cfg or engine.h != self.cfg.local_steps
                    or engine.batch_size != self.cfg.batch_size
                    or engine.lr != CLIENT_LR):
                raise ValueError(
                    "injected FleetEngine does not match this run's model "
                    f"shape: engine=({engine.cfg.name}, h={engine.h}, "
                    f"B={engine.batch_size}, lr={engine.lr}) vs run="
                    f"({self.cnn_cfg.name}, h={self.cfg.local_steps}, "
                    f"B={self.cfg.batch_size}, lr={CLIENT_LR})")
            self.engine = engine
        else:
            self.engine = FleetEngine(self.cnn_cfg, self.cfg.local_steps,
                                      self.cfg.batch_size, lr=CLIENT_LR,
                                      max_bucket=4096, obs=self.obs)
        self.classes = classes
        self.b_prev = 0
        # -- fault tolerance (tentpole; all dormant when spec is None) -----
        # explicit FaultSpec overrides the RunConfig's registry name (ad-hoc
        # schedules in tests/benchmarks without registering them)
        spec = faults if faults is not None else (
            get_fault(run.faults) if run.faults is not None else None)
        self.faults = FaultInjector(spec) if spec is not None else None
        self.stale = StaleBuffer()
        # -- resumable execution: completed-round log + cursor -------------
        self.logs: List[RoundLog] = []
        self.next_round = 0
        cfg_cnn = self.cnn_cfg

        def _accuracy(p, x, y):
            return jnp.mean((jnp.argmax(cnn_forward(p, cfg_cnn, x), -1) == y)
                            .astype(jnp.float32))
        self._eval = jax.jit(_accuracy)

    # ------------------------------------------------------------------
    def _alpha(self, fleet, round_idx: int) -> np.ndarray:
        s = self.run.strategy
        batches = self.cfg.local_steps
        if s in ("genfv", "aigc_only", "fl_only"):
            return select(self.cfg, fleet, self.model_bits, batches).alpha
        if s == "fedprox":
            return select_random(self.rng, fleet, k=max(
                1, int(0.3 * len(fleet))))
        if s == "fedavg":
            return select_random(self.rng, fleet, k=max(
                1, int(0.3 * len(fleet))))
        if s == "no_emd":
            return select_no_emd(self.cfg, fleet, self.model_bits, batches)
        if s == "madca":
            return select_madca(self.cfg, fleet, self.model_bits, batches)
        if s == "ocean":
            return select_ocean(self.cfg, fleet, self.model_bits, batches,
                                round_idx, self.run.rounds)
        raise ValueError(s)

    # ------------------------------------------------------------------
    # Round lifecycle. `run_round` = begin -> plan -> finish; repro.exp's
    # Sweep drives the same three phases but routes many cells' `plan`
    # calls through ONE `plan_rounds_batched` dispatch between begin and
    # finish. The split is RNG-neutral: `begin_round` consumes self.rng in
    # exactly the order the old monolithic body did, and planning draws no
    # randomness at all.
    # ------------------------------------------------------------------
    def begin_round(self, t: int) -> PendingRound:
        """Phase 1: materialize the round's fleet and run SUBP1 selection."""
        cfg = self.cfg
        # fleet of the round: vehicles map onto data partitions
        with self.obs.span("round/fleet", round=t):
            if self.world is None:
                # legacy memoryless sampler: a fresh i.i.d. fleet every
                # round, mapped onto a fresh permutation of the partitions
                order = self.rng.permutation(len(self.client_data))
                hists = [self.hists[i] for i in order]
                sizes = [self.sizes[i] for i in order]
                fleet = mobility.sample_fleet(self.rng, cfg, hists, sizes)
                parts = order                   # parts[j]: fleet[j]'s data
            else:
                fleet, parts = self.world.fleet(self.hists, self.sizes)

        with self.obs.span("round/select", round=t, fleet=len(fleet)):
            alpha = self._alpha(fleet, t) if fleet else np.zeros(0, np.int32)
        return PendingRound(t, fleet, parts, alpha)

    def plan(self, pending: PendingRound) -> RoundPlan:
        """Phase 2: SUBP2-4 resource allocation for one pending round."""
        # span key mirrors the jax planner's jit cache key (the selected set
        # padded to its bucket) so the first dispatch per bucket tags as
        # "compile"
        k = int(np.sum(pending.alpha))
        bucket = bucket_size(k) if k else 0
        key = (self.run.planner, bucket) if self.run.planner == "jax" else None
        # no sync needed: plan_round unpacks to host scalars (self-fencing)
        with self.obs.span("round/plan", key=key, round=pending.t,
                           planner=self.run.planner, bucket=bucket):
            plan = plan_round(self.cfg, pending.fleet, self.model_bits,
                              self.cfg.local_steps, b_prev=self.b_prev,
                              svc=self.svc,
                              alpha_override=pending.alpha,
                              planner=self.run.planner)
        return plan

    def finish_round(self, pending: PendingRound, plan: RoundPlan) -> RoundLog:
        """Phase 3 (synchronous semantics): realize faults, enforce the
        deadline t_bar*(1+slack), then execute the round.

        With a `FaultSpec` attached the round buffers late-but-finite
        updates for a staleness-discounted merge in a later round and
        rejects poisoned ones via the in-kernel finiteness guard
        (fl/faults.py). Without one every branch reduces bitwise to the
        seed semantics (tests/test_faults.py pins the equivalence).

        The execution body lives in `_execute_round`, parameterized by the
        late/skip partition and the stale-merge set — `repro.fl.stream`'s
        event-driven engine computes those from its quorum/deadline event
        simulation instead and drives the same body (the async merge path),
        so both loops share one aggregation/ledger/eval implementation."""
        cfg = self.cfg
        t = pending.t
        fleet = pending.fleet

        # ---- fault realization + round deadline ---------------------------
        spec = self.faults.spec if self.faults is not None else None
        rf = None
        late_mask = None
        t_round = plan.t_bar
        if spec is not None and plan.selected:
            rf = self.faults.draw(t, len(plan.selected))
            t_real = realized_times(cfg, fleet, plan, self.model_bits, rf,
                                    spec.outage_fade_db)
            deadline = plan.t_bar * (1.0 + spec.deadline_slack)
            late_mask = (t_real > deadline) & ~rf.departed
            # the RSU holds the round open until the last on-time upload —
            # or until the deadline, once anyone misses it / departs
            if late_mask.any() or rf.departed.any():
                t_round = float(deadline)
            else:
                t_round = float(max(plan.t_bar, float(t_real.max())))

        # Mid-round dropout (persistent world only): SUBP1 admitted against
        # min(t_hold, t_max), but the realized straggler window plan.t_bar is
        # only known after SUBP2-4 — a selected vehicle whose holding time
        # falls short of it leaves coverage before uploading and contributes
        # nothing. The legacy sampler has no vehicle persistence, so the
        # seed's semantics (everyone selected finishes) are kept there.
        survive = None
        if self.world is not None and plan.selected:
            t_run = min(t_round, cfg.t_max)
            survive = dropout_mask(cfg, fleet, plan.selected, t_run)

        # buffered late updates from EARLIER rounds become mergeable now;
        # weights are staleness-discounted sizes rho_eff ∝ |D_n| * gamma^age
        stale_models, stale_weights, stale_emds = [], [], []
        stale_dropped = 0
        if spec is not None and self.run.strategy != "aigc_only":
            entries, ages, stale_dropped = self.stale.pop_mergeable(
                t, spec.max_staleness)
            stale_models = [e.params for e in entries]
            stale_weights = [e.size * spec.staleness_discount ** a
                             for e, a in zip(entries, ages)]
            stale_emds = [e.emd for e in entries]

        return self._execute_round(
            pending, plan, rf=rf, late_mask=late_mask, t_round=t_round,
            survive=survive, stale_models=stale_models,
            stale_weights=stale_weights, stale_emds=stale_emds,
            stale_dropped=stale_dropped, guard_host=spec is not None)

    def _execute_round(self, pending: PendingRound, plan: RoundPlan, *,
                       rf, late_mask, t_round: float, survive,
                       stale_models: List, stale_weights: List[float],
                       stale_emds: List[float], stale_dropped: int = 0,
                       late_sink: Callable | None = None,
                       skip_mask=None, guard_host: bool = False,
                       dt_floor: float = 0.0) -> RoundLog:
        """Execute one planned round: training, generation, aggregation,
        world step, eval. Both round loops drive this body:

        * synchronous (`finish_round`): late_mask from the fault deadline,
          stale merges drained from `self.stale`, late updates pushed back
          into it (the default `late_sink`);
        * streaming (`repro.fl.stream.StreamEngine`): late/skip partition
          from the quorum-commit event simulation, stale merges folded from
          the in-flight queue at their arrival instants, late updates
          sunk back into that queue with their realized due times, and
          `dt_floor` carrying the streaming cadence into the world step.

        `stale_weights` are the already-discounted size weights (the caller
        owns the gamma^age policy); `guard_host` enables the host-side
        finiteness checks of the sequential reference path; `skip_mask`
        marks selected positions whose upload can never arrive (exhausted
        retry budgets) — they count as dropped without consuming RNG."""
        run = self.run
        cfg = self.cfg
        t = pending.t
        fleet, parts = pending.fleet, pending.parts
        self.b_prev = plan.b_gen
        if late_sink is None:
            late_sink = lambda entry, pos: self.stale.push(entry)  # noqa: E731

        dropped = 0
        use_aigc = run.strategy in ("genfv", "aigc_only")
        use_fl = run.strategy != "aigc_only"
        prox_mu = 0.1 if run.strategy == "fedprox" else 0.0

        # AIGC generation + augmented training run first: omega_a depends only
        # on the round-start global model, and the fused fleet dispatch below
        # consumes it as the kappa2 term of eq. (4).
        aug = None
        loss = 0.0
        if use_aigc:
            with self.obs.span("round/generate", round=t,
                               b_gen=plan.b_gen):
                counts = label_schedule(
                    plan.b_gen if use_fl else cfg.gen_batch * 4,
                    self.classes)
                self.server.generate(counts, round_idx=t)
                with self.obs.span("round/generate/train") as sp:
                    aug, aug_loss = self.server.train_augmented(
                        cfg.local_steps * cfg.rsu_steps_factor,
                        cfg.batch_size, lr=CLIENT_LR)
                    sp.sync = aug
            if not use_fl:
                loss = aug_loss

        n_trained = 0
        late = rejected = 0
        stale_merged = len(stale_models)
        forced_out: List[int] = []        # vids force-departed this round
        msizes, memds = [], []
        if use_fl:
            models = []                # sequential reference path
            fsizes = []                # sizes of the finite (kept) models
            bimgs, blabels = [], []    # vectorized engine path
            n_poison = 0               # poisoned batches inside the dispatch
            with self.obs.span("round/local_sgd", round=t,
                               selected=len(plan.selected),
                               vectorized=int(run.vectorized)):
                for pos, j in enumerate(plan.selected):
                    if survive is not None and not survive[pos]:
                        dropped += 1
                        continue
                    if rf is not None and rf.departed[pos]:
                        dropped += 1   # forced exit: the update never arrives
                        forced_out.append(fleet[j].vid)
                        continue
                    if skip_mask is not None and skip_mask[pos]:
                        # retry budget exhausted (streaming): the upload can
                        # never arrive — dropped without consuming RNG
                        dropped += 1
                        continue
                    v = fleet[j]
                    di, dl = self.client_data[parts[j]]
                    if len(dl) < 2:
                        continue
                    is_late = late_mask is not None and bool(late_mask[pos])
                    is_poisoned = rf is not None and bool(rf.poisoned[pos])
                    if run.vectorized:
                        bi, bl = self.engine.sample_batches(self.rng, di, dl)
                        if is_late:
                            # missed the deadline: train on the
                            # already-sampled batches outside the fused
                            # dispatch and buffer the update for a
                            # staleness-discounted merge next round
                            late += 1
                            if is_poisoned:
                                rejected += 1  # poisoned AND late: dropped
                            else:
                                m, _ = local_sgd(
                                    self.server.params, self.cnn_cfg,
                                    jnp.asarray(bi), jnp.asarray(bl),
                                    cfg.local_steps, CLIENT_LR, prox_mu)
                                late_sink(StaleEntry(
                                    m, v.data_size, v.emd, t, v.vid), pos)
                            continue
                        if is_poisoned:
                            # NaN batches corrupt the update inside the fused
                            # dispatch; the in-kernel finiteness guard
                            # rejects it there (one XLA program either way)
                            bi = np.full_like(bi, np.nan)
                            n_poison += 1
                        bimgs.append(bi)
                        blabels.append(bl)
                    else:
                        m, l = client_update(self.server.params, self.cnn_cfg,
                                             di, dl, self.rng, cfg.local_steps,
                                             cfg.batch_size, lr=CLIENT_LR,
                                             prox_mu=prox_mu)
                        if is_poisoned:
                            m = jax.tree.map(
                                lambda x: jnp.full_like(x, jnp.nan), m)
                        if is_late:
                            late += 1
                            if tree_finite(m):
                                late_sink(StaleEntry(
                                    m, v.data_size, v.emd, t, v.vid), pos)
                            else:
                                rejected += 1
                            continue
                        if guard_host and not tree_finite(m):
                            # host-side guard (reference path): the vehicle
                            # still counts as a participant (it trained and
                            # uploaded; mirrors the in-kernel guard's
                            # accounting) but its weight mass renormalizes
                            # onto the finite survivors
                            rejected += 1
                            msizes.append(v.data_size)
                            memds.append(v.emd)
                            continue
                        models.append(m)
                        fsizes.append(v.data_size)
                        loss += l
                    msizes.append(v.data_size)
                    memds.append(v.emd)
            n_trained = len(msizes)

            # span key mirrors the fused dispatch's jit cache key — the
            # padded fleet bucket and the finiteness-guard flag select the
            # compiled XLA program (fl/fleet.py)
            agg_bucket = bucket_size(len(bimgs)) if bimgs else 0
            agg_guard = bool(n_poison)
            agg_key = ((agg_bucket, agg_guard)
                       if run.vectorized and bimgs else None)
            if self.obs.enabled and run.vectorized and bimgs:
                self.obs.gauge("fleet/bucket", agg_bucket)
                self.obs.observe("fleet/pad_waste",
                                 agg_bucket - len(bimgs))
            with self.obs.span("round/aggregate", key=agg_key, round=t,
                               guard=int(agg_guard),
                               stale=stale_merged) as sp:
                if run.vectorized and bimgs:
                    if n_poison or stale_models:
                        # recovery dispatch: joint fresh+stale weights, and
                        # the guarded kernel IFF a poisoned batch is actually
                        # inside it. The guard is numerically neutral on
                        # finite inputs, but it is a different fused XLA
                        # program (ULP-level drift in the vmapped SGD), so
                        # clean rounds must keep dispatching the seed's
                        # kernel to stay bitwise.
                        all_sizes = np.asarray(
                            list(msizes) + list(stale_weights), np.float64)
                        rho_all = all_sizes / max(all_sizes.sum(), 1.0)
                        emds_all = memds + stale_emds
                        out = self.server.fleet_round(
                            self.engine, bimgs, blabels, msizes, memds,
                            aug if use_aigc else None, prox_mu,
                            guard=bool(n_poison),
                            rhos=(rho_all[:len(msizes)]
                                  if stale_models else None),
                            kappa_emds=emds_all if stale_models else None)
                        if n_poison:
                            _, (k1, k2), losses, finite = out
                            rejected += int((~finite).sum())
                            loss = float(losses[finite].mean()) \
                                if finite.any() else 0.0
                        else:
                            _, (k1, k2), losses = out
                            loss = float(losses.mean())
                        if stale_models:
                            w = (k1 * rho_all[len(msizes):]).tolist()
                            self.server.params = add_weighted(
                                self.server.params, stale_models, w)
                    else:
                        _, (k1, k2), losses = self.server.fleet_round(
                            self.engine, bimgs, blabels, msizes, memds,
                            aug if use_aigc else None, prox_mu)
                        loss = float(losses.mean())
                else:
                    if guard_host and not models and not stale_models \
                            and msizes:
                        # every upload rejected: the federated mass degrades
                        # to the round-start global (no federated progress),
                        # mirroring the guarded kernel's all-poisoned
                        # fallback
                        models, fsizes = [self.server.params], [sum(msizes)]
                    # sizes follow the KEPT models (guard-renormalized
                    # weights); the kappa2 EMD pool spans every participant,
                    # matching the vectorized kernel's accounting
                    _, (k1, k2) = self.server.aggregate(
                        models + stale_models,
                        list(fsizes) + list(stale_weights),
                        memds + stale_emds, aug if use_aigc else None)
                    loss = loss / max(len(models), 1)
                sp.sync = self.server.params

        if run.strategy == "aigc_only":
            self.server.params = aug
            k2 = 1.0
            emd_bar = 0.0
        else:
            emd_bar = float(np.mean(memds)) if memds else 0.0

        # advance the world by the realized round wall-clock: the straggler
        # window — deadline-extended under faults — (or the RSU's generation
        # window if longer — AIGC strategies only), floored so an empty round
        # still consumes its scheduling slot, capped at t_max
        if self.world is not None:
            with self.obs.span("round/world_step", round=t):
                if forced_out:
                    # fault-injected departures leave before the step (no
                    # RNG consumed, so a benign spec leaves the stream
                    # untouched)
                    self.world.remove(forced_out)
                t_rsu = plan.t_rsu if use_aigc else 0.0
                dt = max(t_round, t_rsu, dt_floor) if plan.selected \
                    else max(cfg.t_max, dt_floor)
                self.world.step(self.rng, float(
                    np.clip(dt, 0.25 * cfg.t_max, cfg.t_max)))

        # float() forces the device value: the eval span self-fences
        with self.obs.span("round/eval", round=t):
            with self.obs.span("round/eval/put") as sp:
                test = jax.device_put((self.test_imgs, self.test_labels))
                sp.sync = test
            if self.obs.enabled:
                self.obs.count("xfer/h2d_bytes",
                               test[0].nbytes + test[1].nbytes, site="eval")
            acc = float(self._eval(self.server.params, *test))
        log = RoundLog(t, n_trained, plan.t_bar, plan.b_gen, k2,
                       emd_bar, float(loss), acc, dropped, late, rejected,
                       stale_merged, stale_dropped, float(t_round),
                       bcd_iters=plan.bcd_iters,
                       planner_converged=int(plan.converged))
        self._record_round(log)
        self.logs.append(log)
        self.next_round = t + 1
        return log

    def _record_round(self, log: RoundLog) -> None:
        """Feed the round's already-computed diagnostics — previously
        discarded on the floor — into the obs metrics registry. Pure
        host-side reads; the enabled guard keeps the null path free of even
        the kwargs allocations."""
        obs = self.obs
        if not obs.enabled:
            return
        run = self.run
        obs.observe("planner/bcd_iters", log.bcd_iters, planner=run.planner)
        obs.count("planner/converged", log.planner_converged,
                  planner=run.planner)
        obs.count("planner/rounds", 1, planner=run.planner)
        obs.observe("round/selected", log.selected)
        obs.observe("round/t_bar", log.t_bar)
        obs.observe("round/t_round", log.t_round)
        obs.observe("round/t_overrun", log.t_round - log.t_bar)
        obs.count("faults/late", log.late)
        obs.count("faults/rejected", log.rejected)
        obs.count("faults/stale_merged", log.stale_merged)
        obs.count("faults/stale_dropped", log.stale_dropped)
        obs.count("faults/dropped", log.dropped)
        if self.world is not None:
            self.world.observe(obs)

    def run_round(self, t: int) -> RoundLog:
        pending = self.begin_round(t)
        return self.finish_round(pending, self.plan(pending))

    # ------------------------------------------------------------------
    def train(self, verbose: bool = False, checkpoint_path: str | None = None,
              checkpoint_every: int = 1) -> RunResult:
        """Run (or resume) the remaining rounds. A freshly-constructed
        runner starts at round 0; after `load_checkpoint` the loop continues
        at the first incomplete round and the returned RunResult still spans
        all completed rounds. With `checkpoint_path`, state is saved
        atomically every `checkpoint_every` completed rounds."""
        for t in range(self.next_round, self.run.rounds):
            log = self.run_round(t)
            if verbose:
                # rate-limited structured logging (repro.obs): same human
                # rendering as the old bare print, but fast rounds coalesce
                # and the line doubles as a trace event when obs is enabled.
                # The final round always lands (force=).
                log_line(
                    self.obs, "train/round",
                    f"[{self.run.strategy}] round {t:3d} "
                    f"sel={log.selected:2d} drop={log.dropped} "
                    f"t_bar={log.t_bar:5.2f}s b={log.b_gen:4d} "
                    f"k2={log.kappa2:.3f} loss={log.loss:.3f} "
                    f"acc={log.accuracy:.3f}",
                    force=t == self.run.rounds - 1,
                    round=t, accuracy=log.accuracy)
            if checkpoint_path is not None and \
                    (t + 1) % max(checkpoint_every, 1) == 0:
                with self.obs.span("round/checkpoint", round=t):
                    self.save_checkpoint(checkpoint_path)
        return RunResult(list(self.logs))

    # ------------------------------------------------------------------
    # Resumable execution (ROADMAP direction 5). The runner's complete
    # mutable state is: global params, the single shared numpy Generator
    # (server and world hold it by identity), b_prev, the completed-round
    # logs, the AIGC pool, the world arrays and the staleness buffer.
    # Fault draws are round-keyed (fl/faults.py) and the datasets/partition
    # are a pure function of RunConfig, so nothing else needs persisting —
    # a resumed run replays the remaining rounds bitwise
    # (tests/test_faults.py golden resume, both planner backends).
    # ------------------------------------------------------------------
    _LOG_INT_FIELDS = ("round", "selected", "b_gen", "dropped", "late",
                       "rejected", "stale_merged", "stale_dropped",
                       "bcd_iters", "planner_converged")

    def _logs_state(self) -> dict:
        return {f.name: np.asarray([getattr(l, f.name) for l in self.logs],
                                   np.int64 if f.name in self._LOG_INT_FIELDS
                                   else np.float64)
                for f in dataclasses.fields(RoundLog)}

    def _checkpoint_state(self) -> dict:
        """The runner's complete mutable state as a checkpointable tree.
        `StreamEngine.save_checkpoint` reuses this and appends its own
        event-queue block under a key the sync layout never uses."""
        rng_state = np.frombuffer(
            json.dumps(self.rng.bit_generator.state).encode(), np.uint8)
        return {
            "rng": rng_state.copy(),
            "b_prev": np.int64(self.b_prev),
            "next_round": np.int64(self.next_round),
            "gen": ({} if self.svc is None else
                    {"t_image": np.float64(self.svc.t_per_image),
                     "steps": np.int64(getattr(self.svc, "steps", 0))}),
            "params": self.server.params,
            "logs": self._logs_state(),
            "pool": ({} if self.server.pool_imgs is None else
                     {"imgs": self.server.pool_imgs,
                      "labels": self.server.pool_labels}),
            "world": ({} if self.world is None else {
                "arrays": dataclasses.asdict(self.world.state),
                "free": np.asarray(self.world._free, np.int64),
                "next_vid": np.int64(self.world._next_vid),
                "stats": {k: np.float64(v) for k, v in
                          dataclasses.asdict(self.world.stats).items()},
            }),
            "stale": ({} if not self.stale.entries else {
                "params": [e.params for e in self.stale.entries],
                "size": np.asarray([e.size for e in self.stale.entries],
                                   np.int64),
                "emd": np.asarray([e.emd for e in self.stale.entries],
                                  np.float64),
                "trained_round": np.asarray(
                    [e.trained_round for e in self.stale.entries], np.int64),
                "vid": np.asarray([e.vid for e in self.stale.entries],
                                  np.int64),
            }),
        }

    def save_checkpoint(self, path: str) -> str:
        """Atomic snapshot of all mutable round state (repro.checkpoint)."""
        meta = {"schema": self.CKPT_SCHEMA,
                "run": run_payload(self.run)}
        return save_tree(path, self._checkpoint_state(), metadata=meta)

    def _check_manifest(self, meta: dict) -> None:
        if meta.get("schema") != self.CKPT_SCHEMA:
            raise ValueError(f"checkpoint schema {meta.get('schema')!r} != "
                             f"{self.CKPT_SCHEMA!r}")
        if meta.get("run") != run_payload(self.run):
            raise ValueError(
                "checkpoint was written by a different RunConfig: "
                f"{meta.get('run')} vs {run_payload(self.run)}")

    def load_checkpoint(self, path: str) -> int:
        """Restore a `save_checkpoint` snapshot into this (freshly
        constructed, identically configured) runner. Returns the next round
        to execute; `train()` continues from there."""
        meta = read_manifest(path)["metadata"]
        self._check_manifest(meta)
        if "stream_cfg" in meta:
            raise ValueError(
                "checkpoint was written by a streaming engine (it carries "
                "in-flight upload state); load it with "
                "repro.fl.stream.StreamEngine.load_checkpoint")
        self._restore_state(restore_tree(path))
        return self.next_round

    def _restore_state(self, state: dict) -> None:
        self.rng.bit_generator.state = json.loads(
            bytes(np.asarray(state["rng"], np.uint8)).decode())
        self.b_prev = int(state["b_prev"])
        self.next_round = int(state["next_round"])
        g = state.get("gen", {})
        if g:
            from repro.gen.calib import MeasuredService
            self.svc = MeasuredService(t_image=float(g["t_image"]),
                                       steps=int(g["steps"]))
        self.server.params = jax.tree.map(jnp.asarray, state["params"])
        logs = state["logs"]
        names = [f.name for f in dataclasses.fields(RoundLog)]
        self.logs = [
            RoundLog(**{n: (int(logs[n][i]) if n in self._LOG_INT_FIELDS
                            else float(logs[n][i])) for n in names})
            for i in range(len(logs["round"]))]
        pool = state["pool"]
        self.server.pool_imgs = (np.asarray(pool["imgs"], np.float32)
                                 if pool else None)
        self.server.pool_labels = (np.asarray(pool["labels"], np.int32)
                                   if pool else None)
        if self.world is not None:
            w = state["world"]
            if not w:
                raise ValueError("checkpoint has no world state but this "
                                 "run uses a persistent scenario")
            a = w["arrays"]
            self.world.state = WorldState(
                vid=np.asarray(a["vid"], np.int64),
                x=np.asarray(a["x"], np.float64),
                v=np.asarray(a["v"], np.float64),
                phi_max=np.asarray(a["phi_max"], np.float64),
                f_mem=np.asarray(a["f_mem"], np.float64),
                f_core=np.asarray(a["f_core"], np.float64),
                v_core=np.asarray(a["v_core"], np.float64),
                shadow_db=np.asarray(a["shadow_db"], np.float64),
                partition=np.asarray(a["partition"], np.int64))
            self.world._free = [int(p) for p in np.asarray(w["free"])]
            self.world._next_vid = int(w["next_vid"])
            st = w["stats"]
            self.world.stats.time = float(st["time"])
            self.world.stats.steps = int(st["steps"])
            self.world.stats.arrivals = int(st["arrivals"])
            self.world.stats.departures = int(st["departures"])
            self.world.stats.blocked_arrivals = int(st["blocked_arrivals"])
            self.world._hists_src = None    # invalidate the hist cache
        stale = state["stale"]
        self.stale = StaleBuffer()
        if stale:
            for i in range(len(stale["size"])):
                self.stale.push(StaleEntry(
                    params=jax.tree.map(jnp.asarray, stale["params"][i]),
                    size=int(stale["size"][i]),
                    emd=float(stale["emd"][i]),
                    trained_round=int(stale["trained_round"][i]),
                    vid=int(stale["vid"][i])))
