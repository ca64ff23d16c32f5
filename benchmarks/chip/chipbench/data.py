"""Inputs and weights made from the seed.

Images: the procedural stand-in for CIFAR-10 / GTSRB (no network): each
class is a fixed low-frequency "shape" shared by class pairs plus a
class-unique high-frequency texture, and a sample is its class pattern
rolled by up to 3 pixels plus Gaussian noise (sigma 0.25), clipped to
[-1, 1]. Labels are uniform over the classes. The same (dataset, n, seed)
gives the same arrays in every process.

Weights: the client model and the RSU's generator, of the architectures
the config names (`archs/`), both made on the device by one jitted call
from the seed, in float32 as they are served.
"""
from __future__ import annotations

import zlib
from functools import lru_cache

import jax
import numpy as np

from chipbench import cells

IMG = 32
CLASSES = {"cifar10": 10, "cifar100": 100, "gtsrb": 43}


def _wave(seed: int, f_lo: float, f_hi: float, n_waves: int = 4):
    rng = np.random.default_rng(seed % (2 ** 31))
    yy, xx = np.mgrid[0:IMG, 0:IMG].astype(np.float64) / IMG
    img = np.zeros((IMG, IMG, 3))
    for _ in range(n_waves):
        fx, fy = rng.uniform(f_lo, f_hi, 2)
        px, py = rng.uniform(0, 2 * np.pi, 2)
        amp = rng.uniform(0.3, 1.0, 3)
        img += (np.sin(2 * np.pi * (fx * xx + px))
                * np.cos(2 * np.pi * (fy * yy + py)))[..., None] * amp
    return img / (np.abs(img).max() + 1e-9)


@lru_cache(maxsize=None)
def class_patterns(name: str) -> np.ndarray:
    """[classes, 32, 32, 3] float32 patterns of one dataset."""
    out = []
    for c in range(CLASSES[name]):
        crc = lambda *k: zlib.crc32("/".join(map(str, k)).encode())  # noqa
        img = (0.6 * _wave(crc(name, "coarse", c // 2), 0.5, 2.5)
               + 0.4 * _wave(crc(name, "fine", c), 6.0, 12.0))
        out.append(img / (np.abs(img).max() + 1e-9))
    return np.stack(out).astype(np.float32)


def make_dataset(name: str, n: int, seed: int = 0, noise: float = 0.25,
                 chunk: int = 8192, pixel_seed: int | None = None):
    """(images [n,32,32,3] float32, labels [n] int32). The round loop's
    `dataset_fn`. Labels come from `seed`; with a `pixel_seed` the shifts
    and the noise come from (seed, pixel_seed), so that the label counts
    (which decide the partition, the EMDs and with them selection) stay
    those of `seed` while the pixels change."""
    pats = class_patterns(name)
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, len(pats), size=n).astype(np.int32)
    if pixel_seed is not None:
        rng = np.random.default_rng([int(seed), int(pixel_seed)])
    shifts = rng.integers(-3, 4, size=(n, 2))
    imgs = np.empty((n, IMG, IMG, 3), np.float32)
    ar = np.arange(IMG)
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        rows = (ar[None, :] - shifts[lo:hi, 0:1]) % IMG
        cols = (ar[None, :] - shifts[lo:hi, 1:2]) % IMG
        p = pats[labels[lo:hi, None, None], rows[:, :, None], cols[:, None, :]]
        eps = rng.standard_normal((hi - lo, IMG, IMG, 3), np.float32) * noise
        np.clip(0.8 * p + eps, -1.0, 1.0, out=imgs[lo:hi])
    return imgs, labels


def make_weights(seed: int, num_classes: int, model: dict, generator: dict):
    """(client params, generator params) from the seed, on the device;
    `model` and `generator` are the config's groups of those names."""
    client, gen = cells.arch(model["arch"]), cells.arch(generator["arch"])

    def _init_all(key):
        k1, k2 = jax.random.split(key)
        return (client.init(k1, num_classes, model),
                gen.init(k2, num_classes, generator))
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % (2 ** 31))
    key = jax.random.fold_in(key, seed // (2 ** 31))
    return jax.jit(_init_all)(key)
