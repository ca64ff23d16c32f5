"""The RSU's generated-image pool (`GenFVServer`): appends into buffers that
grow geometrically hold bitwise what concatenating every batch would, views
handed out stay fixed, the RSU's training reads the same pool, a checkpoint
restores it through `set_pool`, and the append's counters."""
import jax
import numpy as np
import pytest

from repro.configs.base import GenFVConfig
from repro.fl.client import client_update
from repro.fl.rounds import GenFVRunner, RunConfig
from repro.fl.server import GenFVServer
from repro.obs import Obs

APPENDS = 60
MAX_BATCH = 1400
CLASSES = 10


class _Noise:
    """Generator stand-in: seeded float32 noise of a fixed row shape."""

    def __init__(self, shape=(2, 2, 3), dtype=np.float32):
        self.shape, self.dtype = shape, dtype

    def generate(self, labels, rng, round_idx=0):
        return rng.standard_normal((len(labels),) + self.shape) \
            .astype(self.dtype)


def _server(generator, obs=None, params=None, cfg=None):
    return GenFVServer(cfg, params, generator, np.random.default_rng(0),
                       obs=obs)


def _label_counts(rng, size):
    return rng.multinomial(size, np.full(CLASSES, 1.0 / CLASSES))


def _drive(srv, seed, appends=APPENDS):
    """`appends` appends of 0 to MAX_BATCH images; yields the reference
    pool (every batch concatenated) after each."""
    sizes = np.random.default_rng(seed).integers(0, MAX_BATCH + 1, appends)
    count_rng = np.random.default_rng(seed + 1)
    gen_rng = np.random.default_rng()
    gen_rng.bit_generator.state = srv.rng.bit_generator.state
    imgs, labels = [], []
    for size in sizes:
        counts = _label_counts(count_rng, int(size))
        assert srv.generate(counts) == size
        if size:
            batch_labels = np.repeat(np.arange(CLASSES), counts)
            imgs.append(srv.generator.generate(batch_labels, gen_rng))
            labels.append(batch_labels.astype(np.int32))
        yield (np.concatenate(imgs) if imgs else None,
               np.concatenate(labels) if labels else None)


def _buffer(view):
    return None if view is None else view.base


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pool_matches_concatenation_and_views_stay_fixed(seed):
    srv = _server(_Noise())
    views, grows, buf = [], 0, None
    for ref_imgs, ref_labels in _drive(srv, seed):
        imgs, labels = srv.pool_imgs, srv.pool_labels
        if ref_imgs is None:
            assert imgs is None and labels is None
            continue
        for got, ref in ((imgs, ref_imgs), (labels, ref_labels)):
            assert got.dtype == ref.dtype and got.shape == ref.shape
            assert np.array_equal(got, ref)
        if buf is not None and _buffer(imgs) is not buf:
            grows += 1
        buf = _buffer(imgs)
        views.append((imgs, labels, imgs.copy(), labels.copy()))
    assert grows >= 3
    for imgs, labels, imgs_then, labels_then in views:
        assert np.array_equal(imgs, imgs_then)
        assert np.array_equal(labels, labels_then)


def test_pool_counters_bound_the_bytes_written():
    obs = Obs()
    srv = _server(_Noise(), obs=obs)
    grows, buf = 0, None
    for _ in _drive(srv, 3):
        if srv.pool_imgs is None:
            continue
        if buf is not None and _buffer(srv.pool_imgs) is not buf:
            grows += 1
        buf = _buffer(srv.pool_imgs)
    m = obs.metrics
    pool = srv.pool_imgs.nbytes + srv.pool_labels.nbytes
    assert m.gauge_value("gen/pool_bytes") == pool
    assert pool <= m.counter_value("gen/pool_copy_bytes") <= 3 * pool
    assert m.counter_value("gen/pool_grows") == grows >= 3
    capacity = m.gauge_value("gen/pool_capacity_bytes")
    assert capacity == len(buf) * (srv.pool_imgs[0].nbytes + 4)
    assert pool <= capacity <= 2 * pool


@pytest.mark.parametrize("bad", [dict(dtype=np.float64),
                                 dict(shape=(3, 2, 3))])
def test_pool_refuses_a_mismatched_batch(bad):
    srv = _server(_Noise())
    srv.generate(np.full(CLASSES, 3))
    pool = srv.pool_imgs
    srv.generator = _Noise(**bad)
    with pytest.raises(ValueError, match="the pool holds float32"):
        srv.generate(np.full(CLASSES, 2))
    assert np.array_equal(srv.pool_imgs, pool)


def test_set_pool_copies_into_a_buffer_with_headroom():
    rng = np.random.default_rng(0)
    imgs = rng.standard_normal((50, 2, 2, 3)).astype(np.float32)
    labels = rng.integers(0, CLASSES, 50).astype(np.int32)
    srv = _server(_Noise())
    srv.set_pool(imgs, labels)
    kept = imgs.copy()
    imgs[:] = 0
    assert np.array_equal(srv.pool_imgs, kept)
    assert np.array_equal(srv.pool_labels, labels)
    assert len(_buffer(srv.pool_imgs)) > 50
    srv.set_pool(None, None)
    assert srv.pool_imgs is None and srv.pool_labels is None


def test_train_augmented_matches_a_concatenated_pool():
    from repro.configs.genfv_cifar import cnn_config
    from repro.models.cnn import init_cnn
    cfg = cnn_config("cifar10", 0.0625)
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    srv = _server(_Noise((32, 32, 3)), params=params, cfg=cfg)
    for ref_imgs, ref_labels in _drive(srv, 4, appends=3):
        pass
    assert len(ref_labels) > 2 * MAX_BATCH // 3
    state = srv.rng.bit_generator.state
    got, got_loss = srv.train_augmented(2, 8, 0.05)
    ref_rng = np.random.default_rng()
    ref_rng.bit_generator.state = state
    ref, ref_loss = client_update(params, cfg, ref_imgs, ref_labels, ref_rng,
                                  2, 8, 0.05)
    assert got_loss == ref_loss
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_restores_a_partly_full_pool(tmp_path):
    """Save after two rounds, restore through `set_pool` into a fresh
    runner, run two more: pool and params bitwise those of the run that
    was never interrupted."""
    run = RunConfig(strategy="genfv", scenario="rush_hour", seed=0,
                    rounds=4, train_size=300, test_size=32,
                    width_mult=0.0625)
    cfg = GenFVConfig(batch_size=8, local_steps=2, num_vehicles=6)
    full = GenFVRunner(run, fl_cfg=cfg)
    for t in range(4):
        full.run_round(t)

    path = str(tmp_path / "runner.npz")
    cut = GenFVRunner(run, fl_cfg=cfg)
    for t in range(2):
        cut.run_round(t)
    saved = len(cut.server.pool_labels)
    assert 0 < saved < len(full.server.pool_labels)
    cut.save_checkpoint(path)

    resumed = GenFVRunner(run, fl_cfg=cfg)
    assert resumed.load_checkpoint(path) == 2
    assert np.array_equal(resumed.server.pool_imgs, cut.server.pool_imgs)
    assert len(_buffer(resumed.server.pool_imgs)) > saved
    for t in range(2, 4):
        resumed.run_round(t)
    for got, ref in ((resumed.server.pool_imgs, full.server.pool_imgs),
                     (resumed.server.pool_labels, full.server.pool_labels)):
        assert got.dtype == ref.dtype and np.array_equal(got, ref)
    for a, b in zip(jax.tree.leaves(resumed.server.params),
                    jax.tree.leaves(full.server.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert resumed.logs == full.logs
