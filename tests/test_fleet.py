"""Fleet engine (fl/fleet.py): equivalence with the sequential reference
path, bucket/padding invariants, and the bench smoke run.

The sequential reference is the seed implementation: per-vehicle jitted
`client_update` + host-side `core/emd.py::aggregate`. The engine must match
it to tight numerical tolerance (vmap may schedule convs differently, so
bitwise equality is only guaranteed *across bucket sizes*, not across
engines).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import GenFVConfig
from repro.configs.genfv_cifar import cnn_config
from repro.core.emd import aggregate, data_weights, mean_emd
from repro.data.synthetic import make_image_dataset
from repro.fl.client import client_update
from repro.fl.fleet import FleetEngine, bucket_size
from repro.fl.rounds import GenFVRunner, RunConfig
from repro.models.cnn import init_cnn

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")
CFG = cnn_config("cifar10", 0.0625)
K, H, B = 3, 2, 4
EMDS = [0.4, 0.6, 0.5]


@pytest.fixture(scope="module")
def setup():
    params = init_cnn(jax.random.PRNGKey(0), CFG)
    aug = init_cnn(jax.random.PRNGKey(1), CFG)
    imgs, labels = make_image_dataset("cifar10", 240, seed=0)
    imgs = imgs[:, ::2, ::2, :]          # 16x16: keep tier-1 fast
    datasets = [(imgs[i::K], labels[i::K]) for i in range(K)]
    sizes = [len(d[1]) for d in datasets]
    return params, aug, datasets, sizes


def _engine_batches(engine, datasets, seed=0):
    rng = np.random.default_rng(seed)
    bi, bl = zip(*[engine.sample_batches(rng, di, dl) for di, dl in datasets])
    return list(bi), list(bl)


def _leaves_allclose(a, b, tol=2e-6):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=tol, rtol=tol)


def _leaves_equal(a, b):
    return all(bool(jnp.all(x == y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_bucket_size():
    assert [bucket_size(k) for k in (1, 2, 3, 4, 5, 16, 17)] == \
        [4, 4, 4, 4, 8, 16, 32]          # floor 4: see fl/fleet.py
    assert bucket_size(2, min_bucket=1) == 2
    with pytest.raises(ValueError):
        bucket_size(10, max_bucket=8)


@pytest.mark.parametrize("prox_mu", [0.0, 0.5])
def test_engine_matches_sequential(setup, prox_mu):
    """Vmapped fleet + fused aggregation == per-vehicle client_update + host
    aggregate, including the FedProx branch, with K=3 padded into bucket 4
    (so padded-slot masking is exercised too)."""
    params, aug, datasets, sizes = setup
    rng = np.random.default_rng(0)
    models, seq_losses = [], []
    for di, dl in datasets:
        m, l = client_update(params, CFG, di, dl, rng, H, B, 5e-2,
                             prox_mu=prox_mu)
        models.append(m)
        seq_losses.append(l)
    ref = aggregate(models, data_weights(sizes), aug, mean_emd(EMDS))

    engine = FleetEngine(CFG, H, B, 5e-2, donate=False)
    bi, bl = _engine_batches(engine, datasets)   # same rng protocol -> same batches
    new, losses = engine.run(params, bi, bl, data_weights(sizes),
                             mean_emd(EMDS), aug, prox_mu=prox_mu)
    _leaves_allclose(ref, new)
    np.testing.assert_allclose(losses, seq_losses, atol=1e-5, rtol=1e-5)


def test_engine_no_aug_is_weighted_fedavg(setup):
    """aug_params=None must reduce to kappa2=0 weighted FedAvg (the FL-only
    baseline), matching the host path with a zero-EMD aggregate."""
    params, _, datasets, sizes = setup
    rng = np.random.default_rng(0)
    models = [client_update(params, CFG, di, dl, rng, H, B, 5e-2)[0]
              for di, dl in datasets]
    ref = aggregate(models, data_weights(sizes), models[0], 0.0)

    engine = FleetEngine(CFG, H, B, 5e-2, donate=False)
    bi, bl = _engine_batches(engine, datasets)
    new, _ = engine.run(params, bi, bl, data_weights(sizes), aug_params=None)
    _leaves_allclose(ref, new)


def test_bucket_padding_bitwise_stable(setup):
    """K=3 vehicles run in bucket 4, 8, and 16 must produce bitwise-identical
    aggregates and losses: masked padding must not change the result."""
    params, aug, datasets, sizes = setup
    engine = FleetEngine(CFG, H, B, 5e-2, donate=False)
    bi, bl = _engine_batches(engine, datasets)
    outs, losses = {}, {}
    for bucket in (4, 8, 16):
        outs[bucket], losses[bucket] = engine.run(
            params, bi, bl, data_weights(sizes), mean_emd(EMDS), aug,
            prox_mu=0.5, bucket=bucket)
    for bucket in (8, 16):
        assert _leaves_equal(outs[4], outs[bucket]), \
            f"aggregate drifted between bucket 4 and {bucket}"
        np.testing.assert_array_equal(losses[4], losses[bucket])


def test_exact_bucket_vs_padded(setup):
    """A fleet that exactly fills its bucket (K=4 -> bucket 4, no padding)
    must match the same fleet padded into a larger bucket."""
    params, aug, datasets, sizes = setup
    engine = FleetEngine(CFG, H, B, 5e-2, donate=False)
    bi, bl = _engine_batches(engine, datasets)
    bi4, bl4 = bi + [bi[0]], bl + [bl[0]]    # 4th vehicle reuses data
    sizes4, emds4 = sizes + [sizes[0]], EMDS + [EMDS[0]]
    exact, _ = engine.run(params, bi4, bl4, data_weights(sizes4),
                          mean_emd(emds4), aug, bucket=4)
    padded, _ = engine.run(params, bi4, bl4, data_weights(sizes4),
                           mean_emd(emds4), aug, bucket=16)
    assert _leaves_equal(exact, padded)


def test_donating_engine_copies_aliased_aug(setup):
    """The accelerator default donates the global params. When the aug model
    is the round-start globals themselves (empty AIGC pool), the engine must
    copy it rather than pass a donated buffer a second time; the result
    matches the non-donating dispatch and the passed-in params are
    consumed."""
    params, _, datasets, sizes = setup
    ref_engine = FleetEngine(CFG, H, B, 5e-2, donate=False)
    bi, bl = _engine_batches(ref_engine, datasets)
    ref, _ = ref_engine.run(params, bi, bl, data_weights(sizes),
                            mean_emd(EMDS), params)

    own = jax.tree.map(jnp.copy, params)     # the module fixture stays live
    engine = FleetEngine(CFG, H, B, 5e-2, donate=True)
    new, _ = engine.run(own, bi, bl, data_weights(sizes), mean_emd(EMDS),
                        own)
    _leaves_allclose(ref, new)
    assert all(x.is_deleted() for x in jax.tree.leaves(own))


def test_engine_rejects_bad_args(setup):
    params, _, datasets, sizes = setup
    engine = FleetEngine(CFG, H, B, 5e-2, donate=False)
    with pytest.raises(ValueError):
        engine.run(params, [], [], [])
    bi, bl = _engine_batches(engine, datasets)
    with pytest.raises(ValueError):
        engine.run(params, bi, bl, data_weights(sizes), bucket=2)  # 2 < K=3


def test_runner_vectorized_matches_sequential():
    """End-to-end GenFVRunner: the vectorized engine path and the sequential
    reference path consume the same rng stream, so per-round losses agree to
    vmap tolerance and accuracy matches."""
    fast = dict(rounds=1, train_size=400, test_size=32, width_mult=0.125,
                strategy="fedavg")
    fl_cfg = GenFVConfig(batch_size=8, local_steps=2, num_vehicles=6)
    curves = {}
    for vec in (True, False):
        r = GenFVRunner(RunConfig(vectorized=vec, **fast), fl_cfg=fl_cfg)
        res = r.train()
        curves[vec] = res
    np.testing.assert_allclose(curves[True].curve("loss"),
                               curves[False].curve("loss"), atol=1e-4)
    np.testing.assert_array_equal(curves[True].curve("accuracy"),
                                  curves[False].curve("accuracy"))
    np.testing.assert_array_equal(curves[True].curve("selected"),
                                  curves[False].curve("selected"))


def test_bench_rounds_quick_smoke(tmp_path):
    """The perf bench must stay runnable (--quick) so engine regressions
    fail fast; asserts the JSON artifact shape, not the speedup (CI noise)."""
    out = tmp_path / "BENCH_rounds.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_rounds", "--quick",
         "--out", str(out)],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stderr[-2000:]
    data = json.loads(out.read_text())
    assert data["quick"] is True
    assert [row["K"] for row in data["results"]] == [4, 8]
    assert [row["bucket"] for row in data["results"]] == [4, 8]
    for row in data["results"]:
        assert row["rounds_per_sec_vectorized"] > 0
        assert row["rounds_per_sec_sequential"] > 0
        assert row["speedup"] > 0
