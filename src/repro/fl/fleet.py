"""Vectorized fleet execution engine.

All K selected vehicles run their h local-SGD steps (including the FedProx
proximal branch) in ONE jitted dispatch: `jax.vmap` over a leading client
axis, every vehicle starting from the shared global model, fused with the
eq. (4) EMD-weighted aggregation as an on-device stacked-pytree weighted
reduction over the client axis (core/emd.py::aggregate_stacked, unrolled in
fixed order for cross-bucket bitwise stability). This replaces the
sequential per-vehicle
`client_update` loop + host-side `aggregate` of the reference path with a
single XLA program per round.

Fleet-size bucketing: K varies per round with SUBP1 selection, so batch
arrays are padded up to the next power-of-two bucket >= 4 (validity encoded
as zero aggregation weight) and jit compiles once per bucket instead of
once per distinct K. Padded slots train on all-zero batches — finite compute,
zero weight — and provably do not perturb the aggregate (tests/test_fleet.py
checks bitwise stability across buckets).

On accelerators the incoming global params are donated to the dispatch
(donate_argnums), so the aggregated model reuses their buffers; on CPU the
non-donating variant is used because XLA:CPU's aliasing perturbs fusion
bucket-dependently (breaking bitwise cross-bucket stability). Callers must
treat the passed pytree as consumed either way (GenFVServer rebinds
`self.params` to the output).

Design notes: DESIGN.md §"Vectorized fleet engine".
"""
from __future__ import annotations

from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.emd import aggregate_stacked, aggregate_stacked_guarded, kappas
# one bucket scheme for every padded dispatch in the repo: the fleet engine
# and the batched planner share it (defined in core/planner.py; re-exported
# here for existing callers)
from repro.core.planner import bucket_size  # noqa: F401
from repro.fl.client import local_sgd_steps
from repro.obs import NULL_OBS


def _fleet_step_impl(cfg, h: int, lr: float, prox_mu: float, global_params,
                     imgs, labels, weights, aug_params, aug_weight):
    """The fused dispatch. imgs [K,h,B,H,W,C], labels [K,h,B], weights [K]
    (kappa1 * rho_n, zero on padding), aug_weight scalar (kappa2).

    Returns (aggregated global params, per-vehicle per-step losses [K,h]).
    """
    def one_vehicle(bi, bl):
        return local_sgd_steps(global_params, cfg, bi, bl, h, lr, prox_mu)

    stacked, losses = jax.vmap(one_vehicle)(imgs, labels)
    new_global = aggregate_stacked(stacked, weights, aug_params, aug_weight)
    return new_global, losses


# Two compiled variants. Donating the incoming global params lets XLA reuse
# their buffers for the aggregated output (no extra copy of the model on the
# accelerator), but the aliasing constraint perturbs XLA:CPU's fusion in a
# bucket-size-dependent way (~1 ULP drift between K=4 and K=8 buckets), which
# breaks the cross-bucket bitwise-stability guarantee — so on CPU the engine
# defaults to the non-donating variant (DESIGN.md §"Buffer donation").
_fleet_step_donated = partial(jax.jit, static_argnums=(0, 1, 2, 3),
                              donate_argnums=(4,))(_fleet_step_impl)
_fleet_step = partial(jax.jit, static_argnums=(0, 1, 2, 3))(_fleet_step_impl)


def _fleet_step_guarded_impl(cfg, h: int, lr: float, prox_mu: float,
                             global_params, imgs, labels, weights,
                             aug_params, aug_weight):
    """Fault-tolerant variant of the fused dispatch: identical vmapped local
    SGD, but the aggregation rejects non-finite (poisoned) client updates
    in-kernel and renormalizes survivor weights. Still one XLA program.

    Returns (aggregated global params, losses [K,h], finite_mask [K]).
    """
    def one_vehicle(bi, bl):
        return local_sgd_steps(global_params, cfg, bi, bl, h, lr, prox_mu)

    stacked, losses = jax.vmap(one_vehicle)(imgs, labels)
    new_global, finite = aggregate_stacked_guarded(
        stacked, weights, aug_params, aug_weight, fallback=global_params)
    return new_global, losses, finite


_fleet_step_guarded_donated = partial(jax.jit, static_argnums=(0, 1, 2, 3),
                                      donate_argnums=(4,))(_fleet_step_guarded_impl)
_fleet_step_guarded = partial(jax.jit, static_argnums=(0, 1, 2, 3))(
    _fleet_step_guarded_impl)


class FleetEngine:
    """Round executor: sample -> pad to bucket -> one fused dispatch.

    One engine per (model cfg, h, batch size, lr); bucketed jit caches live
    in jax's global compilation cache keyed on the static args + shapes.
    `obs` (a `repro.obs` tracer) times the host stacking, the copy to the
    device and the fused step as children of the caller's
    `round/aggregate` span.
    """

    def __init__(self, cfg_model, local_steps: int, batch_size: int,
                 lr: float, max_bucket: int = 64, donate: bool | None = None,
                 obs=None):
        # max_bucket caps trace size: the fixed-order reduction unrolls
        # O(bucket) adds per leaf, so huge buckets inflate compile time —
        # raise it explicitly for fleets beyond 64 concurrent vehicles
        self.cfg = cfg_model
        self.h = int(local_steps)
        self.batch_size = int(batch_size)
        self.lr = float(lr)
        self.max_bucket = max_bucket
        # donate=None: donate the global params on accelerators, keep the
        # bitwise bucket-stable non-donating dispatch on CPU (see above)
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self.donate = bool(donate)
        self._step = _fleet_step_donated if self.donate else _fleet_step
        self._step_guarded = (_fleet_step_guarded_donated if self.donate
                              else _fleet_step_guarded)
        self._zeros = None  # cached kappa2=0 stand-in for a missing aug model
        self.obs = obs if obs is not None else NULL_OBS

    # -- host-side batch sampling (mirrors client_update's rng protocol) ---
    def sample_batches(self, rng: np.random.Generator, images, labels):
        """One vehicle's h fixed-shape mini-batches (with replacement)."""
        idx = rng.integers(0, len(labels), size=(self.h, self.batch_size))
        return images[idx], labels[idx]

    # ----------------------------------------------------------------------
    def run(self, global_params, imgs_list: List, labels_list: List,
            rhos: Sequence[float], emd_bar: float = 0.0, aug_params=None,
            prox_mu: float = 0.0, bucket: int | None = None,
            guard: bool = False) -> Tuple[object, np.ndarray]:
        """Train all K vehicles and aggregate, in one dispatch.

        imgs_list/labels_list: per-vehicle stacked batches ([h,B,H,W,C] /
        [h,B]); rhos: data weights over the K vehicles; aug_params: the
        RSU-augmented model (None -> plain weighted FedAvg, kappa2 = 0).
        `global_params` must be treated as consumed (donated on
        accelerators). Returns (new globals, mean loss [K]); with
        guard=True (fault-injection runs) the guarded dispatch is used and a
        third element — per-vehicle finite mask [K] — is returned.
        """
        k = len(imgs_list)
        if k == 0:
            raise ValueError("FleetEngine.run needs at least one vehicle")
        kb = bucket_size(k, max_bucket=self.max_bucket) if bucket is None \
            else int(bucket)
        if kb < k:
            raise ValueError(f"bucket {kb} smaller than fleet {k}")

        if aug_params is None:
            emd_bar = 0.0              # kappa2 = 0: pure weighted FedAvg
            if self._zeros is None:
                self._zeros = jax.tree.map(jnp.zeros_like, global_params)
            aug_params = self._zeros
        elif self.donate and aug_params is global_params:
            # empty-AIGC-pool rounds anchor kappa2 on the round-start globals
            # (server.train_augmented returns self.params untrained); copy so
            # donation of global_params can't clobber the aug input
            aug_params = jax.tree.map(jnp.copy, aug_params)
        k1, k2 = kappas(emd_bar)

        obs = self.obs
        with obs.span("round/aggregate/stack"):
            imgs = np.stack([np.asarray(x, np.float32) for x in imgs_list])
            labels = np.stack([np.asarray(x, np.int32) for x in labels_list])
            if kb > k:
                pad = ((0, kb - k),) + ((0, 0),) * (imgs.ndim - 1)
                imgs = np.pad(imgs, pad)
                labels = np.pad(labels,
                                ((0, kb - k),) + ((0, 0),) * (labels.ndim - 1))
            weights = np.zeros(kb, np.float32)
            weights[:k] = k1 * np.asarray(rhos, np.float64)
        with obs.span("round/aggregate/put") as sp:
            batch = jax.device_put((imgs, labels, weights))
            sp.sync = batch
        if obs.enabled:
            obs.count("xfer/h2d_bytes", sum(a.nbytes for a in batch),
                      site="fleet")

        # self-fencing: the loss readback waits for the dispatch
        with obs.span("round/aggregate/step", key=(kb, guard)):
            args = (self.cfg, self.h, self.lr, float(prox_mu), global_params,
                    *batch, aug_params, jnp.float32(k2))
            if guard:
                new_params, losses, finite = self._step_guarded(*args)
                return (new_params, np.asarray(losses[:k]).mean(axis=1),
                        np.asarray(finite[:k]))
            new_params, losses = self._step(*args)
            return new_params, np.asarray(losses[:k]).mean(axis=1)
