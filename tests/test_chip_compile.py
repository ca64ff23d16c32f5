"""Compile rehearsals for one TPU v5e chip, run without the chip.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached. These tests lower the main path's device programs
for v5e and compile them, so a program the chip's compiler would refuse
(an op without a TPU lowering, a donation that cannot alias, f64 the
backend cannot emulate) fails here instead of on the chip:

* the SUBP2-4 planner kernel under x64, single fleet and vmapped;
* the DDPM sampler dispatch at the runner's generator width;
* the donating fused fleet dispatch, plain and guarded. The full-width
  ResNet-18 program takes minutes to compile, so this guards the
  lowering at the smallest width, bucket 4 and h=1; `chip_smoke.py` runs
  it at full width on the chip.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library at a time, and a
test worker that holds it keeps it until it exits.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import GenFVConfig
from repro.configs.genfv_cifar import cnn_config
from repro.core import planner
from repro.core.generation import DiffusionService
from repro.diffusion.ddpm import make_ddpm
from repro.fl import fleet
from repro.gen.sampler import _sample_strided
from repro.gen.service import RUNNER_BASE_WIDTH, runner_ddpm
from repro.models.cnn import init_cnn


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of any cache the environment set
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 - any failure: no compiler
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tree_sds(sharding, tree):
    return jax.tree.map(lambda x: _sds(sharding, x.shape, x.dtype), tree)


@pytest.mark.parametrize("batched", [False, True], ids=["one", "many"])
def test_planner_kernel_compiles_x64(one_chip, batched):
    """SUBP2-4 BCD in float64: TPUs have no native f64, so the compiler
    must emulate it; fleets of 3 in bucket 4 for the vmapped kernel."""
    cfg = GenFVConfig()
    lead = (3,) if batched else ()
    with jax.enable_x64(True):
        c = planner.planner_consts(cfg, 1e8, DiffusionService(steps=50),
                                   cfg.bcd_eps)
        cs = planner.PlannerConsts(
            *(_sds(one_chip, (), jnp.asarray(v).dtype) for v in c))
        f64 = _sds(one_chip, lead + (4,), jnp.float64)
        args = (cs, f64, f64, f64, f64,
                _sds(one_chip, lead + (4,), jnp.bool_),
                _sds(one_chip, lead, jnp.int64), cfg.bcd_max_iter)
        kernel = planner._plan_many if batched else planner._plan_one
        compiled = kernel.lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_sampler_compiles(one_chip):
    """The per-round DDPM dispatch at the runner's generator width."""
    ddpm = runner_ddpm(10)
    assert ddpm.base_width == RUNNER_BASE_WIDTH
    params = _tree_sds(one_chip, jax.eval_shape(
        lambda: make_ddpm(jax.random.PRNGKey(0), ddpm)))
    kb = 16
    compiled = _sample_strided.lower(
        params, ddpm, _sds(one_chip, (2,), jnp.uint32),
        _sds(one_chip, (kb,), jnp.int32), 50,
        _sds(one_chip, (kb,), jnp.uint32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


@pytest.mark.parametrize("guarded", [False, True], ids=["plain", "guarded"])
def test_fleet_dispatch_donates_on_v5e(one_chip, guarded):
    """The accelerator-only donating variants lower for v5e and alias the
    aggregated output onto every leaf of the donated global params."""
    cfg = cnn_config("cifar10", 0.0625)
    kb, h, b, hw = 4, 1, 8, 16
    params = _tree_sds(one_chip, jax.eval_shape(
        lambda: init_cnn(jax.random.PRNGKey(0), cfg)))
    step = (fleet._fleet_step_guarded_donated if guarded
            else fleet._fleet_step_donated)
    compiled = step.lower(
        cfg, h, 5e-2, 0.0, params,
        _sds(one_chip, (kb, h, b, hw, hw, 3), jnp.float32),
        _sds(one_chip, (kb, h, b), jnp.int32),
        _sds(one_chip, (kb,), jnp.float32), params,
        _sds(one_chip, (), jnp.float32)).compile()
    # every params leaf (the first flat arguments) is aliased to an output:
    # the HloModule line carries `input_output_alias={ {out}: (arg, {},
    # may-alias), ... }`
    header = compiled.as_text().splitlines()[0]
    aliased = sorted(int(i) for i in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", header))
    assert aliased == list(range(len(jax.tree.leaves(params))))
