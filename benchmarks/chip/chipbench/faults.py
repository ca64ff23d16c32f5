"""Faults planted under the timed path, to show that the comparison
catches them (tests, and `tools/control.py --faults` on the chip). Each is
a context manager that patches one of the round loop's classes and puts
it back on exit.

  unchanged      the fused fleet dispatch returns the global params it
                 was given (a step that leaves its state unchanged)
  half_batch     the fused fleet dispatch trains only the first half of
                 the round's vehicles, the weights renormalised over them
  altered_image  the sampler returns one image of the round negated
  altered_update the aggregated params come back with the head bias
                 shifted by 0.1
  stale_eval     the round's test accuracy is taken of the global params
                 the round started with (an answer altered where it is
                 produced)
The fleet-wide exchange between chips does not exist on one chip.
`CAUGHT` lists the faults the cells' limits catch; `stale_eval` is read
on the chip (`tools/control.py --faults`) until `eval_gap` has a limit
set from that reading (PERF.md, Open questions).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("unchanged", "half_batch", "altered_image", "altered_update",
          "stale_eval")
CAUGHT = FAULTS[:4]


@contextlib.contextmanager
def planted(name: str):
    from repro.fl import GenFVRunner
    from repro.fl.fleet import FleetEngine
    from repro.gen.service import BatchedDDPMGenerator
    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
    cls, attr = {"altered_image": (BatchedDDPMGenerator, "generate"),
                 "stale_eval": (GenFVRunner, "finish_round")}.get(
                     name, (FleetEngine, "run"))
    orig = getattr(cls, attr)

    def run(self, global_params, imgs, labels, rhos, *args, **kw):
        if name == "unchanged":
            keep = global_params
            _, losses = orig(self, jax.tree.map(jnp.copy, global_params),
                             imgs, labels, rhos, *args, **kw)[:2]
            return keep, losses
        if name == "half_batch":
            k = max(1, len(imgs) // 2)
            r = np.asarray(rhos, np.float64)[:k]
            return orig(self, global_params, imgs[:k], labels[:k],
                        r / r.sum(), *args, **kw)
        out = orig(self, global_params, imgs, labels, rhos, *args, **kw)
        params = dict(out[0])
        params["head"] = dict(params["head"], b=params["head"]["b"] + 0.1)
        return (params,) + tuple(out[1:])

    def generate(self, labels, rng, round_idx=0):
        out = np.array(orig(self, labels, rng, round_idx=round_idx))
        if len(out):
            out[0] = -out[0]
        return out

    def finish_round(self, pending, plan):
        start = jax.tree.map(jnp.copy, self.server.params)
        ev = self._eval
        self._eval = lambda p, x, y: ev(start, x, y)
        try:
            return orig(self, pending, plan)
        finally:
            self._eval = ev

    setattr(cls, attr, {"altered_image": generate,
                        "stale_eval": finish_round}.get(name, run))
    try:
        yield
    finally:
        setattr(cls, attr, orig)
