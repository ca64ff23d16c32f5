"""Operations and bytes of the round loop's kernels, from their shapes.

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

RESNET_WIDTHS = (64, 128, 256, 512)


def _taps(size: int, k: int, stride: int = 1) -> int:
    """Kernel taps inside the image, summed over one axis's outputs of a
    "SAME" convolution."""
    out = -(-size // stride)
    lo = max((out - 1) * stride + k - size, 0) // 2
    return sum(sum(1 for j in range(k) if 0 <= o * stride - lo + j < size)
               for o in range(out))


def _conv(size, k, c_in, c_out, stride=1):
    """(MACs, output elements) of one "SAME" convolution on one image."""
    out = -(-size // stride)
    return _taps(size, k, stride) ** 2 * c_in * c_out, out * out * c_out


def _resnet_layers(width_mult: float, num_classes: int, size: int = 32,
                   channels: int = 3):
    """[(macs, out_elems)] per conv / matmul of one image's forward."""
    w = [int(x * width_mult) for x in RESNET_WIDTHS]
    out = [_conv(size, 3, channels, w[0])]
    c_in, hw = w[0], size
    for s, c in enumerate(w):
        for b in range(2):
            stride = 2 if (b == 0 and s > 0) else 1
            out.append(_conv(hw, 3, c_in, c, stride))
            out.append(_conv(hw // stride, 3, c, c))
            if stride != 1 or c_in != c:
                out.append(_conv(hw, 1, c_in, c, stride))
            c_in, hw = c, hw // stride
    out.append((c_in * num_classes, num_classes))
    return out


def resnet_params(width_mult: float, num_classes: int) -> int:
    w = [int(x * width_mult) for x in RESNET_WIDTHS]
    n = 27 * w[0] + 2 * w[0]
    c_in = w[0]
    for s, c in enumerate(w):
        for b in range(2):
            n += 9 * c_in * c + 9 * c * c + 4 * c
            if (b == 0 and s > 0) or c_in != c:
                n += c_in * c + 2 * c
            c_in = c
    return n + c_in * num_classes + num_classes


def resnet_fwd_flops(width_mult: float, num_classes: int) -> float:
    return 2.0 * sum(m for m, _ in _resnet_layers(width_mult, num_classes))


def resnet_train_flops(width_mult: float, num_classes: int) -> float:
    """Forward + backward of one image."""
    layers = _resnet_layers(width_mult, num_classes)
    fwd = sum(m for m, _ in layers)
    return 2.0 * (3 * fwd - layers[0][0])


def resnet_train_bytes(width_mult: float, num_classes: int, batch: int,
                       steps: int) -> float:
    """`steps` SGD steps of one client on batches of `batch` images."""
    p = resnet_params(width_mult, num_classes) * 4
    acts = sum(e for _, e in _resnet_layers(width_mult, num_classes)) * 4
    per_step = 2 * p + batch * (32 * 32 * 3 * 4 + 3 * acts)
    return float(steps * per_step)


def _unet_layers(base: int, emb: int = 256):
    c1, c2, c3 = base, 2 * base, 4 * base
    out = [(2 * emb * emb, emb), _conv(32, 3, 3, c1)]

    def res(hw, ci, co):
        r = [_conv(hw, 3, ci, co), (emb * co, co), _conv(hw, 3, co, co)]
        if ci != co:
            r.append(_conv(hw, 1, ci, co))
        return r
    out += res(32, c1, c1)
    out.append(_conv(32, 3, c1, c2, 2))
    out += res(16, c2, c2)
    out.append(_conv(16, 3, c2, c3, 2))
    out += res(8, c3, c3)
    L = 64
    out += [(3 * L * c3 * c3, 3 * L * c3), (L * L * c3, L * L),
            (L * L * c3, L * c3), (L * c3 * c3, L * c3)]
    out += res(8, c3, c3)
    out += res(16, c3 + c2, c2)
    out += res(32, c2 + c1, c1)
    out.append(_conv(32, 3, c1, 3))
    return out


def unet_params(base: int, num_classes: int, emb: int = 256) -> int:
    c1, c2, c3 = base, 2 * base, 4 * base

    def res(ci, co):
        return (2 * ci + 9 * ci * co + emb * co + 2 * co + 9 * co * co
                + (ci * co if ci != co else 0))
    return (num_classes * emb + 2 * emb * emb + 27 * c1 + res(c1, c1)
            + 9 * c1 * c2 + res(c2, c2) + 9 * c2 * c3 + res(c3, c3)
            + 2 * c3 + 4 * c3 * c3 + res(c3, c3) + res(c3 + c2, c2)
            + res(c2 + c1, c1) + 2 * c1 + 27 * c1)


def unet_step_flops(base: int) -> float:
    """One denoising step (one UNet forward) of one image."""
    return 2.0 * sum(m for m, _ in _unet_layers(base))


def unet_step_bytes(base: int, num_classes: int, images: int) -> float:
    """One denoising step of a batch of `images`."""
    acts = sum(e for _, e in _unet_layers(base)) * 4
    return float(unet_params(base, num_classes) * 4
                 + images * (2 * 32 * 32 * 3 * 4 + 2 * acts))
