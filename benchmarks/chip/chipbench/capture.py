"""Keeps what the comparison needs of a sample of the window's rounds.

Thin pass-through wrappers on the runner's generator, its RSU training
and its fleet engine record each call's inputs and outputs by reference.
Which rounds are kept is decided before each round executes (its fleet
size K is known once it is planned): the round with the largest K so far,
and a reservoir sample, drawn from the seed, of `SAMPLE` others. For a
kept round the global parameters are copied on the device before it runs
(the fused dispatch donates them) and after it ends; nothing else is
copied, and a round that is not kept drops its references when the next
one starts.
"""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np


SAMPLE = 2      # rounds kept besides the largest


@jax.jit
def _snapshot(tree):
    return jax.tree.map(jnp.copy, tree)


class Recorder:
    def __init__(self, runner, seed: int):
        self.runner = runner
        self.rng = np.random.default_rng([int(seed), 0x43484b])
        self.sample = SAMPLE
        self.kept = {}          # round -> record
        self._reservoir = []    # rounds in the reservoir sample
        self._seen = 0
        self._largest = None    # (K, round)
        self.cur = None
        srv, eng = runner.server, runner.engine
        gen_generate, srv_train, eng_run = (srv.generator.generate,
                                            srv.train_augmented, eng.run)

        def generate(labels, rng, round_idx=0):
            out = gen_generate(labels, rng, round_idx=round_idx)
            if self.cur is not None:
                self.cur["gen"] = (np.asarray(labels), out, round_idx)
            return out

        def train_augmented(h, batch_size, lr):
            pool = (srv.pool_imgs, srv.pool_labels)
            state = copy.deepcopy(runner.rng.bit_generator.state)
            aug, loss = srv_train(h, batch_size, lr)
            if self.cur is not None:
                self.cur["aug"] = dict(pool=pool, rng_state=state, h=h,
                                       batch=batch_size, lr=lr, out=aug,
                                       loss=float(loss))
            return aug, loss

        def run(global_params, imgs_list, labels_list, rhos, emd_bar=0.0,
                aug_params=None, prox_mu=0.0, bucket=None, guard=False):
            rec = self.cur
            out = eng_run(global_params, imgs_list, labels_list, rhos,
                          emd_bar, aug_params, prox_mu, bucket, guard)
            if rec is not None:
                rec["fleet"] = dict(imgs=list(imgs_list),
                                    labels=list(labels_list),
                                    rhos=np.asarray(rhos, np.float64),
                                    emd_bar=float(emd_bar), aug=aug_params,
                                    prox_mu=float(prox_mu), losses=out[1],
                                    lr=eng.lr)
            return out

        srv.generator.generate = generate
        srv.train_augmented = train_augmented
        eng.run = run

    def before(self, t: int, pending, plan, b_prev: int) -> None:
        """Called between `plan` and `finish_round` of round t."""
        k = len(plan.selected)
        keep = []
        if self._largest is None or k > self._largest[0]:
            if self._largest is not None:
                keep.append(("drop_largest", self._largest[1]))
            self._largest = (k, t)
            keep.append(("largest", t))
        self._seen += 1
        if len(self._reservoir) < self.sample:
            self._reservoir.append(t)
            keep.append(("reservoir", t))
        else:
            j = int(self.rng.integers(0, self._seen))
            if j < self.sample:
                keep.append(("drop_reservoir", self._reservoir[j]))
                self._reservoir[j] = t
                keep.append(("reservoir", t))
        for what, r in keep:
            if what.startswith("drop_"):
                self._release(r)
        if any(r == t for what, r in keep if not what.startswith("drop_")):
            self.cur = dict(round=t, pending=pending, plan=plan,
                            b_prev=int(b_prev),
                            params_in=_snapshot(self.runner.server.params))
        else:
            self.cur = None

    def after(self, log) -> None:
        """Called once round `self.cur` has finished (and been fenced)."""
        if self.cur is None:
            return
        self.cur["log"] = log
        self.cur["params_out"] = _snapshot(self.runner.server.params)
        self.kept[self.cur["round"]] = self.cur
        self.cur = None

    def _release(self, r: int) -> None:
        if r in self._reservoir or (self._largest and self._largest[1] == r):
            return
        self.kept.pop(r, None)

    def records(self):
        return [self.kept[r] for r in sorted(self.kept)]
