"""Warm-up of exactly the programs a cell's window drives, through the
round loop's public entry points, at the buckets the cell file lists.

* fleet: `FleetEngine.run(..., bucket=b)` on a throwaway copy of the
  global params (the dispatch donates them), and the device slice
  (`losses[:k]`) of its output for every fleet size K the cell's rounds
  reach;
* sampler: `sample_schedule(..., bucket=b)` and the device slice
  (`images[:n]`) of its output for every b_gen the cell's rounds reach;
* planner: `plan_round(..., alpha_override=...)` selecting b vehicles.
The round loop slices on the device with a static size, so each size is
a program of its own; the sizes come from the cell file (its traffic
fixes them) and are compiled from a thread pool.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np


def _by_bucket(sizes, buckets, shape):
    """[(zeros of the bucket's output shape, sizes in that bucket)]."""
    from repro.core.planner import bucket_size
    return [(jnp.zeros((b,) + shape, jnp.float32),
             [n for n in sizes if bucket_size(n) == b]) for b in buckets]


def _slice(job) -> None:
    a, n = job
    a[:n].block_until_ready()         # compiled, then dropped at once


def _slices(arrays_and_sizes, workers: int = 8) -> int:
    jobs = [(a, n) for a, sizes in arrays_and_sizes for n in sizes
            if n < a.shape[0]]
    with ThreadPoolExecutor(workers) as ex:
        for _ in ex.map(_slice, jobs):
            pass
    return len(jobs)


def warm_fleet(runner, buckets, sizes, aug: bool) -> int:
    eng = runner.engine
    h, b = eng.h, eng.batch_size
    img = np.zeros((h, b, 32, 32, 3), np.float32)
    lab = np.zeros((h, b), np.int32)
    for k in buckets:
        params = jax.tree.map(jnp.copy, runner.server.params)
        aug_p = jax.tree.map(jnp.copy, runner.server.params) if aug else None
        out, _ = eng.run(params, [img] * k, [lab] * k, np.full(k, 1.0 / k),
                         1.0 if aug else 0.0, aug_p, 0.0, bucket=k)
        jax.block_until_ready(out)
    return _slices(_by_bucket(sizes, buckets, (h,)))


def warm_sampler(runner, buckets, sizes) -> int:
    from repro.gen.sampler import sample_schedule
    from repro.gen.service import gen_round_key
    gen = runner.server.generator
    key = gen_round_key(0, 0)
    for k in buckets:
        labels = np.arange(k, dtype=np.int32) % runner.classes
        sample_schedule(gen.params, gen.ddpm, key, labels, gen.sampler_steps,
                        bucket=k)
    return _slices(_by_bucket(sizes, buckets, (32, 32, 3)))


def warm_planner(runner, buckets) -> None:
    from repro.core import plan_round
    fleet, _ = runner.world.fleet(runner.hists, runner.sizes)
    for k in buckets:
        if len(fleet) < k:
            fleet = (fleet * (k // max(len(fleet), 1) + 1))
        alpha = np.zeros(len(fleet), np.int32)
        alpha[:k] = 1
        plan_round(runner.cfg, fleet, runner.model_bits,
                   runner.cfg.local_steps, b_prev=0, svc=runner.svc,
                   alpha_override=alpha, planner="jax")
