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

from chipbench import data


def build_runner(cfgd: dict, celld: dict, seed: int, obs=None):
    """Returns (runner, unet params). Weights come from the seed and are
    handed to the runner in place of its own initialisation."""
    from repro.configs.genfv_cifar import genfv_config
    from repro.fl import GenFVRunner, RunConfig
    from repro.gen.calib import MeasuredService
    from repro.gen.service import BatchedDDPMGenerator, runner_ddpm

    run_f, model, gen = cfgd["run"], cfgd["model"], cfgd["generator"]
    strategy = celld["strategy"]
    run = RunConfig(dataset=run_f["dataset"], alpha=celld["alpha"],
                    strategy=strategy, train_size=run_f["train_size"],
                    test_size=run_f["test_size"],
                    width_mult=model["width_mult"],
                    seed=int(celld["traffic_seed"]),
                    vectorized=True, scenario=celld["scenario"],
                    planner="jax", generator="ddpm",
                    sampler_steps=celld["sampler_steps"], obs=obs,
                    rounds=1 << 30)
    fl_cfg = genfv_config(run_f["dataset"], celld["alpha"], **cfgd["fl"])
    cnn_params, unet_params = data.make_weights(
        seed, model["num_classes"], model["width_mult"], gen["base_width"])
    ddpm = runner_ddpm(model["num_classes"])
    if (ddpm.timesteps, ddpm.base_width) != (gen["timesteps"],
                                              gen["base_width"]):
        raise ValueError(f"the program serves a DDPM of {ddpm.timesteps} "
                         f"steps at base width {ddpm.base_width}; the "
                         f"config asks for {gen}")
    generator = BatchedDDPMGenerator(unet_params, ddpm, seed=int(seed),
                                     sampler_steps=celld["sampler_steps"],
                                     obs=obs)
    svc = MeasuredService(t_image=float(gen["t_image"]),
                          steps=int(gen["t_image_steps"]))
    runner = GenFVRunner(run, fl_cfg=fl_cfg, generator=generator,
                         dataset_fn=partial(data.make_dataset,
                                            pixel_seed=int(seed)), svc=svc)
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), runner.server.params)
    ours = jax.tree.map(lambda a: (a.shape, a.dtype), cnn_params)
    if shapes != ours:
        raise ValueError("the seeded ResNet-18 does not match the round "
                         "loop's parameter layout")
    runner.server.params = cnn_params
    return runner, unet_params


def cfg_dict(runner) -> dict:
    """The runner's effective GenFVConfig (scenario applied) as a dict."""
    return {k: (float(v) if isinstance(v, (float, np.floating)) else v)
            for k, v in dataclasses.asdict(runner.cfg).items()}
