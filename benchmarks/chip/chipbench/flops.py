"""Counting rules for the operations and bytes of the round loop's
kernels, from their shapes. Each architecture file under `archs/` lists
its layers with `conv` and sums them by these rules.

Model FLOPs count the multiply-adds of convolutions and matrix products
(2 FLOPs each), a convolution's only over the taps that fall inside the
image ("SAME" zero padding is no work); GroupNorm, activations, softmax
and the SGD update are left out. A training step on one image is the forward pass, the weight
gradients (as many FLOPs as the forward pass) and the input gradients of
every layer but the stem, whose input is the image.

Bytes are a lower bound: the weights read and written once per step, the
input batch read once, and every layer output written once and read back
once (twice in training: forward and backward).
"""
from __future__ import annotations


def _taps(size: int, k: int, stride: int = 1) -> int:
    """Kernel taps inside the image, summed over one axis's outputs of a
    "SAME" convolution."""
    out = -(-size // stride)
    lo = max((out - 1) * stride + k - size, 0) // 2
    return sum(sum(1 for j in range(k) if 0 <= o * stride - lo + j < size)
               for o in range(out))


def conv(size, k, c_in, c_out, stride=1):
    """(MACs, output elements) of one "SAME" convolution on one image."""
    out = -(-size // stride)
    return _taps(size, k, stride) ** 2 * c_in * c_out, out * out * c_out
