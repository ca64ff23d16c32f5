"""Builds the round loop's `GenFVRunner` from a cell and its config: the
program's own classes, with the benchmark's seeded data and weights and
the configuration's pinned per-image generation time t0.

The cell's `traffic_seed` is the round loop's own seed: it draws the
label counts, the partition, the vehicular world and the batch indices,
and so fixes every round's fleet, K and b_gen. The run's `--seed` draws
everything else: the pixels, both models' weights and the generator's
noise streams. Every seed thus does the same work, round by round, on
other data and weights."""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import numpy as np

from chipbench import cells, data


def _layout(tree):
    return jax.tree.map(lambda a: (a.shape, a.dtype), tree)


def build_runner(cfgd: dict, celld: dict, seed: int, obs=None):
    """Returns (runner, generator params). Weights come from the seed and
    are handed to the runner in place of its own initialisation. The
    config's `model.arch` and `generator.arch` name the files under
    `archs/` that make the weights and build the program's objects; each
    object's parameter layout must be that of the seeded weights."""
    from repro.configs.genfv_cifar import genfv_config
    from repro.fl import GenFVRunner, RunConfig
    from repro.gen.calib import MeasuredService

    run_f, model, gen = cfgd["run"], cfgd["model"], cfgd["generator"]
    classes = model["num_classes"]
    client_arch, gen_arch = cells.arch(model["arch"]), cells.arch(gen["arch"])
    strategy = celld["strategy"]
    run = RunConfig(dataset=run_f["dataset"], alpha=celld["alpha"],
                    strategy=strategy, train_size=run_f["train_size"],
                    test_size=run_f["test_size"],
                    seed=int(celld["traffic_seed"]),
                    vectorized=True, scenario=celld["scenario"],
                    planner="jax", sampler_steps=celld["sampler_steps"],
                    obs=obs, rounds=1 << 30,
                    **client_arch.runner_fields(model))
    fl_cfg = genfv_config(run_f["dataset"], celld["alpha"], **cfgd["fl"])
    client_params, gen_params = data.make_weights(seed, classes, model, gen)
    if _layout(gen_arch.program_layout(classes, gen)) != _layout(gen_params):
        raise ValueError(f"the seeded {gen['arch']} weights do not match the "
                         f"layout of the program's generator for {gen}")
    generator = gen_arch.serve(gen_params, classes, gen, seed=int(seed),
                               sampler_steps=celld["sampler_steps"], obs=obs)
    svc = MeasuredService(t_image=float(gen["t_image"]),
                          steps=int(gen["t_image_steps"]))
    runner = GenFVRunner(run, fl_cfg=fl_cfg, generator=generator,
                         dataset_fn=partial(data.make_dataset,
                                            pixel_seed=int(seed)), svc=svc)
    if _layout(runner.server.params) != _layout(client_params):
        raise ValueError(f"the seeded {model['arch']} weights do not match "
                         f"the round loop's parameter layout")
    runner.server.params = client_params
    return runner, gen_params


def cfg_dict(runner) -> dict:
    """The runner's effective GenFVConfig (scenario applied) as a dict."""
    return {k: (float(v) if isinstance(v, (float, np.floating)) else v)
            for k, v in dataclasses.asdict(runner.cfg).items()}
