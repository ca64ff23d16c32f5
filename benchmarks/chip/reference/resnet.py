"""ResNet-18 (CIFAR variant, GroupNorm in place of BatchNorm): init,
forward, cross-entropy loss and plain SGD.

Topology: 3x3 stem of width 64, four stages of two basic blocks at
64/128/256/512 channels (stride 2 at the first block of stages 2-4, 1x1
projection where the shape changes), global average pool, linear head.
GroupNorm uses 8 groups (fewer where the channel count is not divisible).
The parameter layout is the one the round loop trains, so the benchmark
can hand the same seeded weights to both.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

STAGE_WIDTHS = (64, 128, 256, 512)
STAGE_BLOCKS = (2, 2, 2, 2)


def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5


def _gn(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def init(key, num_classes: int, width_mult: float = 1.0, channels: int = 3):
    """He-normal convs, unit GroupNorm, 1/sqrt(fan_in) head."""
    widths = [int(w * width_mult) for w in STAGE_WIDTHS]
    keys = iter(jax.random.split(key, 2 + 3 * sum(STAGE_BLOCKS)))
    w0 = widths[0]
    params = {"stem": _he(next(keys), (3, 3, channels, w0), 9 * channels),
              "gn_stem": _gn(w0), "stages": []}
    c_in = w0
    for s, (c_out, n) in enumerate(zip(widths, STAGE_BLOCKS)):
        stage = []
        for b in range(n):
            stride = 2 if (b == 0 and s > 0) else 1
            blk = {"conv1": _he(next(keys), (3, 3, c_in, c_out), 9 * c_in),
                   "gn1": _gn(c_out),
                   "conv2": _he(next(keys), (3, 3, c_out, c_out), 9 * c_out),
                   "gn2": _gn(c_out)}
            k_proj = next(keys)
            if stride != 1 or c_in != c_out:
                blk["proj"] = _he(k_proj, (1, 1, c_in, c_out), c_in)
                blk["gn_proj"] = _gn(c_out)
            stage.append(blk)
            c_in = c_out
        params["stages"].append(stage)
    params["head"] = {
        "w": jax.random.normal(next(keys), (c_in, num_classes), jnp.float32)
        * (1.0 / c_in) ** 0.5,
        "b": jnp.zeros((num_classes,), jnp.float32)}
    return params


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


def forward(params, images):
    """images [N,32,32,C] -> logits [N,classes]."""
    x = jax.nn.relu(_groupnorm(params["gn_stem"], _conv(images,
                                                         params["stem"])))
    for s, stage in enumerate(params["stages"]):
        for b, blk in enumerate(stage):
            stride = 2 if (b == 0 and s > 0) else 1
            h = jax.nn.relu(_groupnorm(blk["gn1"],
                                       _conv(x, blk["conv1"], stride)))
            h = _groupnorm(blk["gn2"], _conv(h, blk["conv2"]))
            sc = x
            if "proj" in blk:
                sc = _groupnorm(blk["gn_proj"], _conv(x, blk["proj"], stride))
            x = jax.nn.relu(sc + h)
    x = x.mean(axis=(1, 2))
    return x @ params["head"]["w"] + params["head"]["b"]


def loss(params, images, labels):
    """Mean cross-entropy, the log-softmax taken in float32."""
    logits = forward(params, images).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@partial(jax.jit, static_argnames=("dtype",))
def sgd_step(params, images, labels, lr, dtype=jnp.float32):
    """One plain SGD step w <- w - lr * grad, all in `dtype`."""
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    images = images.astype(dtype)
    value, grads = jax.value_and_grad(loss)(params, images, labels)
    lr = jnp.asarray(lr, dtype)
    return jax.tree.map(lambda p, g: p - lr * g, params, grads), value


@partial(jax.jit, static_argnames=("dtype",))
def accuracy(params, images, labels, dtype=jnp.float32):
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    logits = forward(params, images.astype(dtype))
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))


def local_sgd(params, images, labels, lr, dtype=jnp.float32):
    """h SGD steps over stacked batches images [h,B,...], labels [h,B].
    Returns (params, per-step losses)."""
    losses = []
    with jax.default_matmul_precision("highest"):
        for i in range(images.shape[0]):
            params, value = sgd_step(params, jnp.asarray(images[i]),
                                     jnp.asarray(labels[i]), lr, dtype=dtype)
            losses.append(float(value))
    return params, losses
