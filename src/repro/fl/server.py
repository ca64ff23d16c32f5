"""RSU-side logic: augmented-model training on AIGC data and the EMD-weighted
aggregation (paper Sec. III-A step 5, eq. 4)."""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.emd import aggregate, data_weights, kappas, mean_emd
from repro.fl.client import client_update
from repro.obs import NULL_OBS


class GenFVServer:
    def __init__(self, cfg_model, global_params, generator, rng, obs=None):
        self.cfg_model = cfg_model
        self.obs = obs if obs is not None else NULL_OBS
        self.params = global_params
        self.generator = generator
        self.rng = rng
        self.pool_imgs: np.ndarray | None = None   # accumulated AIGC data
        self.pool_labels: np.ndarray | None = None
        # round-keyed generators (gen/service.py) take a round_idx kwarg;
        # bare `generate(labels, rng)` generators (third-party factories)
        # must keep working, so detect once here instead of try/except on
        # the hot path
        import inspect
        try:
            sig = inspect.signature(generator.generate)
            self._gen_round_kw = "round_idx" in sig.parameters
        except (TypeError, ValueError):
            self._gen_round_kw = False

    # ---- model augmentation (step 5) -------------------------------------
    def generate(self, label_counts: np.ndarray, round_idx: int = 0):
        labels = np.repeat(np.arange(len(label_counts)), label_counts)
        if len(labels) == 0:
            return 0
        if self._gen_round_kw:
            imgs = self.generator.generate(labels, self.rng,
                                           round_idx=round_idx)
        else:
            imgs = self.generator.generate(labels, self.rng)
        obs = self.obs
        with obs.span("round/generate/pool"):
            if self.pool_imgs is None:
                self.pool_imgs = imgs
                self.pool_labels = labels.astype(np.int32)
            else:
                self.pool_imgs = np.concatenate([self.pool_imgs, imgs])
                self.pool_labels = np.concatenate(
                    [self.pool_labels, labels.astype(np.int32)])
        if obs.enabled:
            obs.gauge("gen/pool_bytes",
                      self.pool_imgs.nbytes + self.pool_labels.nbytes)
        return len(labels)

    def train_augmented(self, h: int, batch_size: int, lr: float):
        """omega_a update: h local steps on the generated pool (Sec. III-C1)."""
        if self.pool_imgs is None or len(self.pool_labels) < 2:
            return self.params, 0.0
        return client_update(self.params, self.cfg_model, self.pool_imgs,
                             self.pool_labels, self.rng, h, batch_size, lr)

    # ---- fused vehicle SGD + aggregation (fleet engine path) --------------
    def fleet_round(self, engine, imgs_list: List, labels_list: List,
                    sizes: Sequence[int], emds: Sequence[float],
                    aug_model=None, prox_mu: float = 0.0, *,
                    guard: bool = False, rhos=None, kappa_emds=None):
        """Run all selected vehicles' local SGD and the eq. (4) aggregation
        as one fused dispatch (fl/fleet.py). `self.params` is donated to the
        dispatch and rebound to the aggregated output. The sequential
        reference path is `client_update` per vehicle + `aggregate`.

        Fault-tolerance hooks (fl/faults.py callers only; defaults keep the
        fault-free dispatch byte-identical): `guard=True` switches to the
        finiteness-guarded kernel and returns a 4th element (finite mask);
        `rhos` overrides the data weights (the round loop pre-computes them
        jointly over fresh + buffered-stale participants); `kappa_emds`
        decouples the kappa2 EMD pool from `emds` for the same reason."""
        rhos = data_weights(sizes) if rhos is None \
            else np.asarray(rhos, np.float64)
        emd_bar = mean_emd(emds if kappa_emds is None else kappa_emds) \
            if aug_model is not None else 0.0
        if guard:
            self.params, losses, finite = engine.run(
                self.params, imgs_list, labels_list, rhos, emd_bar,
                aug_model, prox_mu, guard=True)
            return self.params, kappas(emd_bar), losses, finite
        self.params, losses = engine.run(self.params, imgs_list, labels_list,
                                         rhos, emd_bar, aug_model, prox_mu)
        return self.params, kappas(emd_bar), losses

    # ---- async merge-on-arrival (repro.fl.stream) -------------------------
    def absorb(self, model, weight: float):
        """Fold one late-arriving update into the global between rounds:
        params <- (1-w)*params + w*model. The streaming engine calls this
        for uploads that land in the gap after their round committed, with
        `weight` already carrying the rho·gamma^age staleness discount —
        the same first-order mass a next-round `add_weighted` merge would
        have granted the update, applied at its arrival instant instead.
        Float32 accumulation, matching `add_weighted`."""
        w = float(weight)
        self.params = jax.tree.map(
            lambda p, m: ((1.0 - w) * p.astype(jnp.float32)
                          + w * m.astype(jnp.float32)).astype(p.dtype),
            self.params, model)
        return self.params

    # ---- aggregation (eq. 4) ----------------------------------------------
    def aggregate(self, vehicle_models: List, sizes: Sequence[int],
                  emds: Sequence[float], aug_model=None, *,
                  rhos=None, kappa_emds=None):
        if not vehicle_models:
            if aug_model is not None:
                self.params = aug_model
            return self.params, (1.0, 0.0)
        rhos = data_weights(sizes) if rhos is None \
            else np.asarray(rhos, np.float64)
        emd_bar = mean_emd(emds if kappa_emds is None else kappa_emds)
        if aug_model is None:
            # FL-only: plain weighted FedAvg (kappa2 = 0)
            aug_model = vehicle_models[0]
            emd_bar = 0.0
        self.params = aggregate(vehicle_models, rhos, aug_model, emd_bar)
        return self.params, kappas(emd_bar)
