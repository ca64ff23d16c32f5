"""Class-conditional DDPM UNet (32x32) and its strided ancestral sampler.

UNet: 32 -> 16 -> 8 resolution at [c, 2c, 4c] channels, residual blocks
(GroupNorm, SiLU, 3x3 convs) with the time-plus-class embedding added
after the first conv, one self-attention block at 8x8, nearest-neighbour
upsampling with skip concatenation.

Sampler: the DDIM-style stride of the `timesteps`-step linear-beta
schedule at eta = 1, one key stream per image: image i's noise at
denoising position s is N(0, 1) from fold_in(fold_in(round_key, i), s),
its x_T from position tag `steps`. The round key is the first two words of
SeedSequence((seed, round, 0x41494743)).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

EMB = 256
GEN_KEY = 0x41494743


def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5


def _gn(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def _res_init(key, c_in, c_out):
    k = jax.random.split(key, 4)
    p = {"gn1": _gn(c_in), "conv1": _he(k[0], (3, 3, c_in, c_out), 9 * c_in),
         "emb": jax.random.normal(k[1], (EMB, c_out), jnp.float32)
         * (1.0 / EMB) ** 0.5,
         "gn2": _gn(c_out),
         "conv2": _he(k[2], (3, 3, c_out, c_out), 9 * c_out)}
    if c_in != c_out:
        p["proj"] = _he(k[3], (1, 1, c_in, c_out), c_in)
    return p


def init(key, num_classes: int, base: int):
    """He-normal everywhere (output convs included), so that the noise
    prediction is of order one, as in a trained model."""
    c1, c2, c3 = base, 2 * base, 4 * base
    k = jax.random.split(key, 16)
    s = (1.0 / c3) ** 0.5
    return {
        "cls_emb": jax.random.normal(k[0], (num_classes, EMB)) * 0.02,
        "t_w1": jax.random.normal(k[1], (EMB, EMB)) * (1.0 / EMB) ** 0.5,
        "t_w2": jax.random.normal(k[2], (EMB, EMB)) * (1.0 / EMB) ** 0.5,
        "in": _he(k[3], (3, 3, 3, c1), 27),
        "d1a": _res_init(k[4], c1, c1),
        "down1": _he(k[5], (3, 3, c1, c2), 9 * c1),
        "d2a": _res_init(k[6], c2, c2),
        "down2": _he(k[7], (3, 3, c2, c3), 9 * c2),
        "mid1": _res_init(k[8], c3, c3),
        "mid_attn": {"gn": _gn(c3),
                     "wq": jax.random.normal(k[9], (c3, c3)) * s,
                     "wk": jax.random.normal(k[10], (c3, c3)) * s,
                     "wv": jax.random.normal(k[11], (c3, c3)) * s,
                     "wo": jax.random.normal(k[12], (c3, c3)) * s},
        "mid2": _res_init(k[13], c3, c3),
        "u2": _res_init(k[14], c3 + c2, c2),
        "u1": _res_init(k[15], c2 + c1, c1),
        "out_gn": _gn(c1),
        "out": _he(jax.random.fold_in(k[15], 1), (3, 3, c1, 3), 9 * c1),
    }


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _groupnorm(p, x, groups=8, eps=1e-5):
    n, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(n, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + eps)
    return xg.reshape(n, h, w, c) * p["scale"] + p["bias"]


def _res(p, x, emb):
    h = _conv(jax.nn.silu(_groupnorm(p["gn1"], x)), p["conv1"])
    h = h + (emb @ p["emb"])[:, None, None, :]
    h = _conv(jax.nn.silu(_groupnorm(p["gn2"], h)), p["conv2"])
    if "proj" in p:
        x = _conv(x, p["proj"])
    return x + h


def _attn(p, x):
    n, h, w, c = x.shape
    t = _groupnorm(p["gn"], x).reshape(n, h * w, c)
    q, k, v = t @ p["wq"], t @ p["wk"], t @ p["wv"]
    a = jax.nn.softmax(jnp.einsum("nqc,nkc->nqk", q, k) * c ** -0.5, axis=-1)
    return x + (jnp.einsum("nqk,nkc->nqc", a, v) @ p["wo"]).reshape(n, h, w, c)


def _up(x, size):
    return jnp.repeat(jnp.repeat(x, size // x.shape[1], axis=1),
                      size // x.shape[2], axis=2)


def apply(p, x, t, y):
    """Noise prediction eps(x_t, t, y) for x [N,32,32,3], t, y [N] int."""
    half = EMB // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / half)
    ang = t[:, None].astype(jnp.float32) * freqs[None]
    emb = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(x.dtype)
    emb = emb + p["cls_emb"][y]
    emb = jax.nn.silu(emb @ p["t_w1"]) @ p["t_w2"]
    h0 = _conv(x, p["in"])
    h1 = _res(p["d1a"], h0, emb)
    h2 = _res(p["d2a"], _conv(h1, p["down1"], 2), emb)
    h3 = _res(p["mid1"], _conv(h2, p["down2"], 2), emb)
    h3 = _res(p["mid2"], _attn(p["mid_attn"], h3), emb)
    u = _res(p["u2"], jnp.concatenate([_up(h3, 16), h2], -1), emb)
    u = _res(p["u1"], jnp.concatenate([_up(u, 32), h1], -1), emb)
    return _conv(jax.nn.silu(_groupnorm(p["out_gn"], u)), p["out"])


def strided_timesteps(timesteps: int, steps: int) -> np.ndarray:
    if steps == 1:
        return np.array([timesteps - 1], np.int64)
    return np.round(np.linspace(0.0, timesteps - 1, steps)).astype(np.int64)


def round_key(seed: int, round_idx: int):
    ss = np.random.SeedSequence(entropy=(int(seed), int(round_idx), GEN_KEY))
    return jnp.asarray(ss.generate_state(2, np.uint32))


def _noise(key, idx, tag):
    return jax.vmap(lambda i: jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(key, i), tag),
        (32, 32, 3)))(idx)


@partial(jax.jit, static_argnames=("timesteps", "steps", "dtype"))
def _sample_chunk(params, key, y, idx, timesteps, steps, dtype):
    """The schedule's scalars in float32; the image state and the UNet in
    `dtype`."""
    betas = jnp.linspace(1e-4, 0.02, timesteps)
    abars = jnp.cumprod(1.0 - betas)
    ts = jnp.asarray(strided_timesteps(timesteps, steps))
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = _noise(key, idx, jnp.int32(steps)).astype(dtype)
    n = y.shape[0]

    def body(s, x):
        i = steps - 1 - s
        t = ts[i]
        a_t = abars[t]
        a_prev = jnp.where(i > 0, abars[ts[jnp.maximum(i - 1, 0)]], 1.0)
        eps = apply(params, x, jnp.full((n,), t, jnp.int32), y)
        x0 = (x - jnp.sqrt(1 - a_t) * eps) / jnp.sqrt(a_t)
        var = (1 - a_prev) / (1 - a_t) * (1 - a_t / a_prev)
        sigma = jnp.sqrt(jnp.maximum(var, 0))
        mean = jnp.sqrt(a_prev) * x0 \
            + jnp.sqrt(jnp.maximum(1 - a_prev - sigma ** 2, 0)) * eps
        noise = _noise(key, idx, i.astype(jnp.int32))
        return (mean + jnp.where(i > 0, sigma, 0) * noise).astype(dtype)

    x = jax.lax.fori_loop(0, steps, body, x)
    return jnp.clip(x.astype(jnp.float32), -1.0, 1.0)


def sample(params, seed: int, round_idx: int, labels, timesteps: int,
           steps: int, dtype=jnp.float32, chunk: int = 256) -> np.ndarray:
    """Images of one round's schedule, in chunks of `chunk` images."""
    labels = np.asarray(labels, np.int32)
    key = round_key(seed, round_idx)
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(labels), chunk):
            y = np.zeros(chunk, np.int32)
            part = labels[lo:lo + chunk]
            y[:len(part)] = part
            idx = np.arange(lo, lo + chunk, dtype=np.uint32)
            imgs = _sample_chunk(params, key, jnp.asarray(y), jnp.asarray(idx),
                                 timesteps, steps, dtype)
            out.append(np.asarray(imgs)[:len(part)])
    return np.concatenate(out) if out else np.zeros((0, 32, 32, 3),
                                                    np.float32)
