"""Batched XLA two-scale planner — jitted SUBP2-4 BCD with vmapped
multi-fleet planning.

The numpy reference in `core/{bandwidth,power,generation,two_scale}.py`
walks Algorithm 1 (subgradient bandwidth), Algorithm 2 (SCA power) and the
Algorithm 3 BCD outer loop on the host: up to `bcd_max_iter x (bw_max_iter
+ sca_max_iter)` tiny numpy calls per round, per strategy, per seed. This
module ports the whole small-computation scale to ONE jitted XLA program:

* every loop is a `lax.while_loop` with the SAME iteration structure and
  float-op order as the numpy solvers, run in float64 (`jax.enable_x64`), so
  the results agree to tight tolerances (tests/test_planner.py pins them);
* the selected set is padded into the power-of-two bucket scheme shared
  with `fl/fleet.py` (`bucket_size`, floor 4): padded slots carry zero
  subcarriers / False validity masks and provably cannot perturb the
  result, and jit compiles once per bucket instead of once per distinct K;
* every while-loop carry is **done-guarded** — once a lane converges its
  state freezes — which is what makes `jax.vmap` over independent fleets
  exact: a vmapped `while_loop` keeps stepping all lanes until the slowest
  converges, and the guards make the extra steps no-ops, so
  `plan_rounds_batched` is bitwise-identical to planning each fleet alone.

`two_scale.plan_round(planner="jax")` dispatches here; `planner="numpy"`
keeps the host reference. Design notes: DESIGN.md §"Batched XLA planner".
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import GenFVConfig
from repro.core import channel, gpu_model
from repro.core.generation import DiffusionService
from repro.core.gpu_model import CONSTS, RSU_F_CORE, RSU_SPEEDUP
from repro.core.mobility import Vehicle, rsu_distances
from repro.core.selection import SelectionResult, select

LN2 = float(np.log(2.0))


# ---------------------------------------------------------------------------
# Fleet-size bucketing (shared with fl/fleet.py, which re-exports it).
# ---------------------------------------------------------------------------
def bucket_size(k: int, min_bucket: int = 4, max_bucket: int = 4096) -> int:
    """Smallest power-of-two >= k (clamped to [min_bucket, max_bucket]).

    The floor is 4: XLA:CPU's conv kernels switch strategy at very small
    batch sizes, so a K=2 fleet executed in bucket 2 drifts ~1 ULP from the
    same fleet in bucket 8, while the bucket family {4, 8, 16, ...} is
    bitwise-consistent (tests/test_fleet.py). Padding 1-3 vehicles up to 4
    costs negligible throwaway compute.
    """
    if k > max_bucket:
        raise ValueError(f"fleet of {k} exceeds max bucket {max_bucket}")
    b = max(int(min_bucket), 1)
    while b < k:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# Round plan (moved here from two_scale.py; two_scale re-exports it).
# ---------------------------------------------------------------------------
@dataclass
class RoundPlan:
    alpha: np.ndarray                 # [N] selection indicator
    selected: List[int]               # indices with alpha=1
    l: np.ndarray                     # [K] subcarriers per selected vehicle
    phi: np.ndarray                   # [K] tx power per selected vehicle
    b_gen: int                        # images to generate (SUBP4)
    t_cp: np.ndarray                  # [K] per-vehicle training delay
    t_mu: np.ndarray                  # [K] per-vehicle upload delay
    t_bar: float                      # max_n (t_cp + t_mu) — system delay
    e_total: np.ndarray               # [K] per-vehicle energy
    t_rsu: float                      # RSU generation + augmentation time
    bcd_iters: int = 0
    # BCD stopped before its iteration cap. Host-side definition shared by
    # BOTH backends (`bcd_iters < max_bcd`, conservative when convergence
    # lands exactly on the final allowed iteration) so neither jitted
    # program changes shape; surfaced into RoundLog by fl/rounds.py.
    converged: bool = True
    history: List[float] = field(default_factory=list)   # T_bar per BCD iter
    selection: SelectionResult | None = None


def empty_plan(alpha: np.ndarray,
               sel: SelectionResult | None = None) -> RoundPlan:
    """The no-vehicle-selected plan (shared by both planner backends)."""
    return RoundPlan(alpha, [], np.zeros(0), np.zeros(0), 0,
                     np.zeros(0), np.zeros(0), 0.0, np.zeros(0), 0.0,
                     selection=sel)


# ---------------------------------------------------------------------------
# Per-selected-vehicle constants (shared by the numpy and jax backends).
# ---------------------------------------------------------------------------
class SelectedConsts(NamedTuple):
    t_cp: np.ndarray       # [K] eq. 6 training delay (A in Alg. 1)
    e_cp: np.ndarray       # [K] eq. 8 training energy (C in Alg. 1 / G)
    b_prime: np.ndarray    # [K] shadowed channel gain over noise
    phi_max: np.ndarray    # [K] per-vehicle power cap


def selected_consts(cfg: GenFVConfig, fleet: Sequence[Vehicle],
                    idx: Sequence[int], batches: int) -> SelectedConsts:
    """Constants of the BCD given a selected index set (hoisted out of the
    iteration: they do not change across SUBP2/3/4 passes)."""
    xs = np.array([fleet[i].x for i in idx], np.float64)
    f_mem = np.array([fleet[i].f_mem for i in idx], np.float64)
    f_core = np.array([fleet[i].f_core for i in idx], np.float64)
    v_core = np.array([fleet[i].v_core for i in idx], np.float64)
    gain_db = np.array([fleet[i].gain_db for i in idx], np.float64)
    phi_max = np.array([fleet[i].phi_max for i in idx], np.float64)

    dists = rsu_distances(cfg, xs)
    t_cp = gpu_model.train_times(f_mem, f_core, batches)
    e_cp = gpu_model.runtime_powers(f_mem, f_core, v_core) * t_cp
    n0 = channel.noise_watts(cfg)
    # per-vehicle shadowed channel gain (legacy fleets carry gain_db=0,
    # where the 10^(0/10)=1.0 multiplier reproduces the unshadowed value
    # bitwise)
    shadow = channel.shadow_linear(gain_db)
    b_prime = (cfg.unit_channel_gain * shadow
               * dists ** (-cfg.path_loss_exp) / n0)
    return SelectedConsts(t_cp, e_cp, b_prime, phi_max)


# ---------------------------------------------------------------------------
# Kernel constants: traced scalars, so one compilation per (bucket, max_bcd)
# serves every GenFVConfig.
# ---------------------------------------------------------------------------
class PlannerConsts(NamedTuple):
    model_bits: float
    M: float               # num_subcarriers
    W: float               # subcarrier_bw
    e_bar: float           # e_max
    phi_min: float
    t_max: float
    l_min: float
    bw_step: float
    bw_tol: float
    bw_max_iter: int
    sca_eps: float
    sca_max_iter: int
    bcd_eps: float
    gen_batch: int
    t_per_image: float     # eq. 12 t0
    g_t0: float            # rsu_train_time pieces (eq. 13)
    g_c1: float
    g_theta_mem: float
    g_c2: float
    g_theta_core: float
    rsu_denom: float       # 1.5e9 * speedup


def planner_consts(cfg: GenFVConfig, model_bits: float,
                   svc: DiffusionService, eps: float) -> PlannerConsts:
    g = CONSTS
    return PlannerConsts(
        model_bits=float(model_bits), M=float(cfg.num_subcarriers),
        W=float(cfg.subcarrier_bw), e_bar=float(cfg.e_max),
        phi_min=float(cfg.phi_min), t_max=float(cfg.t_max),
        l_min=float(cfg.bw_l_min), bw_step=float(cfg.bw_step),
        bw_tol=float(cfg.bw_tol), bw_max_iter=int(cfg.bw_max_iter),
        sca_eps=float(cfg.sca_eps), sca_max_iter=int(cfg.sca_max_iter),
        bcd_eps=float(eps), gen_batch=int(cfg.gen_batch),
        t_per_image=float(svc.t_per_image),
        g_t0=float(g.t0), g_c1=float(g.c1), g_theta_mem=float(g.theta_mem),
        g_c2=float(g.c2), g_theta_core=float(g.theta_core),
        rsu_denom=float(RSU_F_CORE * RSU_SPEEDUP))


@lru_cache(maxsize=64)
def _device_consts(c: PlannerConsts) -> PlannerConsts:
    """Device-resident copy of the consts: uploading 21 host scalars per
    dispatch costs ~0.1 ms on CPU, and the runner calls the planner with
    the same config every round."""
    with jax.enable_x64(True):
        return PlannerConsts(*(jnp.asarray(v) for v in c))


# ---------------------------------------------------------------------------
# The kernel: one fleet, padded arrays [Kp], valid mask. All loops mirror
# the numpy solvers' iteration structure and float-op order exactly.
# ---------------------------------------------------------------------------
def _project_budget(l, c: PlannerConsts, valid):
    """bandwidth.project_budget with masked padding (pads hold l=0)."""
    kp = l.shape[0]

    def body(st):
        l, pinned, done, i = st
        free = valid & ~pinned
        s_pin = c.l_min * jnp.sum((valid & pinned).astype(l.dtype))
        s_free = jnp.sum(jnp.where(free, l, 0.0))
        need = s_pin + s_free > c.M
        scale = jnp.maximum(c.M - s_pin, 0.0) / jnp.maximum(s_free, 1e-300)
        l_sc = jnp.where(free, l * scale, jnp.where(valid, c.l_min, 0.0))
        newly = free & (l_sc < c.l_min)
        l_new = jnp.where(newly, c.l_min, l_sc)
        l_out = jnp.where(done | ~need, l, l_new)
        pinned_out = jnp.where(done | ~need, pinned, pinned | newly)
        done_out = done | ~need | ~jnp.any(newly)
        return l_out, pinned_out, done_out, i + 1

    def cond(st):
        return ~st[2] & (st[3] < kp)

    l, _, _, _ = lax.while_loop(cond, body,
                                (l, jnp.zeros(kp, bool), False, 0))
    return l


def _solve_bandwidth(c: PlannerConsts, B, D, t_cp, e_cp, valid, n_val):
    """Algorithm 1 (eq. 33-38): projected subgradient ascent on the
    multipliers, done-guarded for vmap-exactness."""
    l0 = jnp.where(valid, c.M / n_val, 0.0)

    def body(st):
        lam1, lam2, lam3, l, prev, it, done = st
        l_n = jnp.sqrt((lam1 * B + lam2 * D) / jnp.maximum(lam3, 1e-9))
        l_n = jnp.where(valid, jnp.clip(l_n, c.l_min, c.M), 0.0)
        l_n = _project_budget(l_n, c, valid)
        l_safe = jnp.where(valid, l_n, 1.0)
        delay = jnp.where(valid, t_cp + B / l_safe, -jnp.inf)
        t_bar = jnp.max(delay)
        g1 = jnp.where(valid, delay - t_bar, 0.0)
        g2 = jnp.sum(jnp.where(valid, e_cp + D / l_safe, 0.0)) \
            - c.e_bar * n_val
        g3 = jnp.sum(l_n) - c.M
        lam1_n = jnp.maximum(lam1 + c.bw_step * g1, 0.0) + 1e-12
        lam2_n = jnp.maximum(lam2 + c.bw_step * g2, 0.0) + 1e-12
        lam3_n = jnp.maximum(lam3 + c.bw_step * g3, 1e-6)
        conv = jnp.max(jnp.abs(l_n - prev)) < c.bw_tol
        it_n = it + 1
        keep = lambda old, new: jnp.where(done, old, new)   # noqa: E731
        return (keep(lam1, lam1_n), keep(lam2, lam2_n), keep(lam3, lam3_n),
                keep(l, l_n), keep(prev, l_n), keep(it, it_n),
                done | conv | (it_n >= c.bw_max_iter))

    st = (jnp.ones_like(l0), 1.0, 1.0, l0, l0, 0, False)
    st = lax.while_loop(lambda s: ~s[6], body, st)
    return st[3]


def _solve_power(c: PlannerConsts, l_w, b_prime, e_cp, phi_max, valid):
    """Algorithm 2 (eq. 39-46): SCA fixed point, done-guarded."""
    lw_s = jnp.where(valid, l_w, 1.0)
    bp_s = jnp.where(valid, b_prime, 1.0)
    a = c.model_bits / lw_s

    def body(st):
        phi, it, done = st
        u = bp_s * phi
        log2u = jnp.log2(1.0 + u)
        e_i = phi * (c.model_bits / (lw_s * log2u))
        de = a / log2u - a * bp_s * phi / (LN2 * (1.0 + u) * log2u ** 2)
        slack = c.e_bar - e_cp - e_i
        phi_b = jnp.where(de > 1e-12, phi + slack / de, phi_max)
        phi_n = jnp.clip(jnp.minimum(phi_b, phi_max), c.phi_min, phi_max)
        conv = jnp.max(jnp.where(valid, jnp.abs(phi_n - phi), 0.0)) \
            < c.sca_eps
        it_n = it + 1
        return (jnp.where(done, phi, phi_n), jnp.where(done, it, it_n),
                done | conv | (it_n >= c.sca_max_iter))

    st = (jnp.full_like(l_w, c.phi_min), 0, False)
    st = lax.while_loop(lambda s: ~s[2], body, st)
    return st[0]


def _rsu_train_time(c: PlannerConsts, bt):
    """Eq. 13 (gpu_model.rsu_train_time) for bt augmented batches."""
    return c.g_t0 + (c.g_c1 * bt * c.g_theta_mem
                     + c.g_c2 * bt * c.g_theta_core) / c.rsu_denom


def _optimal_generation(c: PlannerConsts, t_bar, b_prev):
    """Eq. 48 closed form (generation.optimal_generation)."""
    bt = jnp.maximum(b_prev // c.gen_batch, 1).astype(t_bar.dtype)
    budget = jnp.minimum(t_bar, c.t_max) - _rsu_train_time(c, bt)
    return jnp.where(budget > 0.0,
                     jnp.floor(budget / c.t_per_image),
                     0.0).astype(b_prev.dtype)


def _bcd_kernel(c: PlannerConsts, t_cp, e_cp, b_prime, phi_max, valid,
                b_prev, max_bcd: int):
    """Algorithm 3 small-computation scale for one (padded) fleet."""
    n_val = jnp.sum(valid.astype(t_cp.dtype))
    bp_s = jnp.where(valid, b_prime, 1.0)

    def t_mu_of(l, phi):
        lw_s = jnp.where(valid, l * c.W, 1.0)
        return c.model_bits / (lw_s * jnp.log2(1.0 + bp_s * phi))

    def body(st):
        l, phi, b, it, done, hist = st
        # SUBP2: bandwidth given phi, b
        rate1 = c.W * jnp.log2(1.0 + bp_s * phi)
        B = jnp.where(valid, c.model_bits / rate1, 0.0)
        D = jnp.where(valid, phi * B, 0.0)
        l_n = _solve_bandwidth(c, B, D, t_cp, e_cp, valid, n_val)
        # SUBP3: power given l, b
        phi_n = _solve_power(c, l_n * c.W, b_prime, e_cp, phi_max, valid)
        # SUBP4: generation given l, phi (closed form, eq. 48)
        t_mu = t_mu_of(l_n, phi_n)
        t_bar = jnp.max(jnp.where(valid, t_cp + t_mu, -jnp.inf))
        b_n = _optimal_generation(c, t_bar, b)
        hist_n = lax.dynamic_update_index_in_dim(hist, t_bar, it, 0)
        conv = ((jnp.max(jnp.where(valid, jnp.abs(l_n - l), 0.0)) < c.bcd_eps)
                & (jnp.max(jnp.where(valid, jnp.abs(phi_n - phi), 0.0))
                   < c.bcd_eps)
                & (jnp.abs(b_n - b) < 1))
        it_n = it + 1
        keep = lambda old, new: jnp.where(done, old, new)   # noqa: E731
        return (keep(l, l_n), keep(phi, phi_n), keep(b, b_n),
                keep(it, it_n), done | conv | (it_n >= max_bcd),
                keep(hist, hist_n))

    l0 = jnp.where(valid, c.M / n_val, 0.0)
    phi0 = jnp.where(valid, phi_max, 0.0)
    b0 = jnp.asarray(b_prev, jnp.int64 if jax.config.jax_enable_x64
                     else jnp.int32)
    st = (l0, phi0, b0, 0, max_bcd <= 0,
          jnp.zeros(max_bcd if max_bcd > 0 else 1, t_cp.dtype))
    l, phi, b, it, _, hist = lax.while_loop(lambda s: ~s[4], body, st)

    # final ledger (mirrors the tail of the numpy plan_round)
    t_mu = jnp.where(valid, t_mu_of(l, phi), 0.0)
    e_mu = phi * t_mu
    t_bar = jnp.max(jnp.where(valid, t_cp + t_mu, -jnp.inf))
    bt = jnp.maximum(b // c.gen_batch, 1).astype(t_cp.dtype)
    t_rsu = (b.astype(t_cp.dtype) * c.t_per_image
             + _rsu_train_time(c, bt))
    return l, phi, b, t_mu, e_mu, t_bar, t_rsu, it, hist


_plan_one = partial(jax.jit, static_argnums=(7,))(_bcd_kernel)


@partial(jax.jit, static_argnums=(7,))
def _plan_many(c, t_cp, e_cp, b_prime, phi_max, valid, b_prev, max_bcd):
    """vmap over a leading fleet axis; consts broadcast."""
    return jax.vmap(
        lambda a, e, bp, pm, v, b: _bcd_kernel(c, a, e, bp, pm, v, b,
                                               max_bcd)
    )(t_cp, e_cp, b_prime, phi_max, valid, b_prev)


# ---------------------------------------------------------------------------
# Host-side wrappers: pad to bucket, dispatch under x64, unpack.
# ---------------------------------------------------------------------------
def _pad(x: np.ndarray, kp: int, fill: float = 0.0) -> np.ndarray:
    x = np.asarray(x, np.float64)
    if len(x) == kp:
        return x
    return np.concatenate([x, np.full(kp - len(x), fill)])


def plan_selected_jax(cfg: GenFVConfig, model_bits: float,
                      consts: SelectedConsts, b_prev: int,
                      svc: DiffusionService, eps: float,
                      max_bcd: int, bucket: int | None = None) -> dict:
    """Run the jitted BCD for one already-selected fleet. Returns the raw
    ledger arrays (trimmed to K) for RoundPlan assembly. `bucket` overrides
    the power-of-two padding (tests use it to prove pad-invariance)."""
    k = len(consts.t_cp)
    kp = bucket_size(k) if bucket is None else int(bucket)
    if kp < k:
        raise ValueError(f"bucket {kp} smaller than fleet {k}")
    valid = np.zeros(kp, bool)
    valid[:k] = True
    c = _device_consts(planner_consts(cfg, model_bits, svc, eps))
    with jax.enable_x64(True):
        out = _plan_one(c, _pad(consts.t_cp, kp), _pad(consts.e_cp, kp),
                        _pad(consts.b_prime, kp),
                        _pad(consts.phi_max, kp, cfg.phi_min),
                        jnp.asarray(valid), int(b_prev), int(max_bcd))
        out = [np.asarray(o) for o in out]
    return _unpack(out, k, int(max_bcd))


def _unpack(out, k: int, max_bcd: int) -> dict:
    l, phi, b, t_mu, e_mu, t_bar, t_rsu, it, hist = out
    iters = int(it)
    return dict(l=l[:k], phi=phi[:k], b_gen=int(b), t_mu=t_mu[:k],
                e_mu=e_mu[:k], t_bar=float(t_bar), t_rsu=float(t_rsu),
                bcd_iters=iters, converged=iters < max_bcd,
                history=[float(h) for h in hist[:iters]])


def plan_rounds_batched(cfg: GenFVConfig, fleets: Sequence[Sequence[Vehicle]],
                        model_bits: float, batches: int,
                        b_prevs: Sequence[int] | None = None,
                        alpha_overrides: Sequence[np.ndarray | None] | None
                        = None,
                        svc: DiffusionService | None = None,
                        eps: float | None = None,
                        max_bcd: int | None = None) -> List[RoundPlan]:
    """Plan many independent fleets in ONE vmapped dispatch.

    Fleets may differ in size and selected-set size; all selected sets are
    padded to a common power-of-two bucket. Per-fleet results are
    bitwise-identical to calling `plan_round(..., planner="jax")` fleet by
    fleet (the done-guarded loops freeze converged lanes). Intended for
    baseline sweeps: strategies x seeds x scenarios with a shared config.
    """
    svc = svc or DiffusionService(steps=cfg.diffusion_steps)
    eps = cfg.bcd_eps if eps is None else eps
    max_bcd = cfg.bcd_max_iter if max_bcd is None else max_bcd
    n_fleet = len(fleets)
    b_prevs = [0] * n_fleet if b_prevs is None else list(b_prevs)
    overrides = ([None] * n_fleet if alpha_overrides is None
                 else list(alpha_overrides))

    sels, alphas, idxs, consts = [], [], [], []
    for fleet, ov in zip(fleets, overrides):
        if ov is None:
            sel = select(cfg, fleet, model_bits, batches)
            alpha = sel.alpha
        else:
            sel = None
            alpha = np.asarray(ov)
        idx = [i for i in range(len(fleet)) if alpha[i] == 1]
        sels.append(sel)
        alphas.append(alpha)
        idxs.append(idx)
        consts.append(selected_consts(cfg, fleet, idx, batches))

    live = [f for f in range(n_fleet) if idxs[f]]
    plans: List[RoundPlan | None] = [None] * n_fleet
    for f in range(n_fleet):
        if f not in live:
            plans[f] = empty_plan(alphas[f], sels[f])
    if not live:
        return plans

    kp = bucket_size(max(len(idxs[f]) for f in live))
    c = _device_consts(planner_consts(cfg, model_bits, svc, eps))
    stack = lambda g, fill=0.0: np.stack(                   # noqa: E731
        [_pad(g(consts[f]), kp, fill) for f in live])
    valid = np.zeros((len(live), kp), bool)
    for row, f in enumerate(live):
        valid[row, :len(idxs[f])] = True
    with jax.enable_x64(True):
        out = _plan_many(c, stack(lambda s: s.t_cp), stack(lambda s: s.e_cp),
                         stack(lambda s: s.b_prime),
                         stack(lambda s: s.phi_max, cfg.phi_min),
                         jnp.asarray(valid),
                         np.asarray([b_prevs[f] for f in live], np.int64),
                         int(max_bcd))
        out = [np.asarray(o) for o in out]
    for row, f in enumerate(live):
        r = _unpack([o[row] for o in out], len(idxs[f]), int(max_bcd))
        s = consts[f]
        plans[f] = RoundPlan(
            alpha=alphas[f], selected=idxs[f], l=r["l"], phi=r["phi"],
            b_gen=r["b_gen"], t_cp=s.t_cp, t_mu=r["t_mu"],
            t_bar=r["t_bar"], e_total=s.e_cp + r["e_mu"], t_rsu=r["t_rsu"],
            bcd_iters=r["bcd_iters"], converged=r["converged"],
            history=r["history"], selection=sels[f])
    return plans
