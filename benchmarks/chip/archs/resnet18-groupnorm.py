"""Client architecture `resnet18-groupnorm`: the CIFAR ResNet-18 with
GroupNorm that the round loop trains on every vehicle and on the RSU
(plain reference in `reference/resnet.py`).

The interface of a client architecture file:
  init(key, num_classes, spec)          seeded weights, float32
  runner_fields(spec)                   the `RunConfig` fields that make
                                        the program build this model
  param_count(num_classes, spec)
  fwd_flops(num_classes, spec)          one image's forward pass
  train_flops(num_classes, spec)        one image's SGD step
  train_bytes(num_classes, spec, batch, steps)
  local_sgd(params, images, labels, lr, dtype)   the plain reference
  accuracy(params, images, labels, dtype)
`spec` is the config's `model` group. Counts follow `chipbench.flops`.
"""
from chipbench import flops
from reference import resnet

local_sgd = resnet.local_sgd
accuracy = resnet.accuracy


def _widths(spec) -> list:
    """Stage widths after `width_mult`; a spec of another topology than
    the reference's is refused."""
    got = (list(spec["stage_widths"]), list(spec["stage_blocks"]),
           spec["stem_width"], spec["image_size"], spec["dtype"])
    want = (list(resnet.STAGE_WIDTHS), list(resnet.STAGE_BLOCKS),
            resnet.STAGE_WIDTHS[0], 32, "float32")
    if got != want:
        raise ValueError(f"resnet18-groupnorm is (stage widths, stage "
                         f"blocks, stem width, image size, dtype) {want}; "
                         f"the config gives {got}")
    return [int(x * spec["width_mult"]) for x in resnet.STAGE_WIDTHS]


def init(key, num_classes: int, spec: dict):
    _widths(spec)
    return resnet.init(key, num_classes, float(spec["width_mult"]),
                       spec["channels"])


def runner_fields(spec: dict) -> dict:
    _widths(spec)
    return {"width_mult": spec["width_mult"]}


def _layers(num_classes: int, spec: dict):
    """[(macs, out_elems)] per conv / matmul of one image's forward."""
    w = _widths(spec)
    size = spec["image_size"]
    out = [flops.conv(size, 3, spec["channels"], w[0])]
    c_in, hw = w[0], size
    for s, c in enumerate(w):
        for b in range(2):
            stride = 2 if (b == 0 and s > 0) else 1
            out.append(flops.conv(hw, 3, c_in, c, stride))
            out.append(flops.conv(hw // stride, 3, c, c))
            if stride != 1 or c_in != c:
                out.append(flops.conv(hw, 1, c_in, c, stride))
            c_in, hw = c, hw // stride
    out.append((c_in * num_classes, num_classes))
    return out


def param_count(num_classes: int, spec: dict) -> int:
    w = _widths(spec)
    n = 9 * spec["channels"] * w[0] + 2 * w[0]
    c_in = w[0]
    for s, c in enumerate(w):
        for b in range(2):
            n += 9 * c_in * c + 9 * c * c + 4 * c
            if (b == 0 and s > 0) or c_in != c:
                n += c_in * c + 2 * c
            c_in = c
    return n + c_in * num_classes + num_classes


def fwd_flops(num_classes: int, spec: dict) -> float:
    return 2.0 * sum(m for m, _ in _layers(num_classes, spec))


def train_flops(num_classes: int, spec: dict) -> float:
    """Forward + backward of one image."""
    layers = _layers(num_classes, spec)
    fwd = sum(m for m, _ in layers)
    return 2.0 * (3 * fwd - layers[0][0])


def train_bytes(num_classes: int, spec: dict, batch: int,
                steps: int) -> float:
    """`steps` SGD steps of one client on batches of `batch` images."""
    p = param_count(num_classes, spec) * 4
    acts = sum(e for _, e in _layers(num_classes, spec)) * 4
    size, ch = spec["image_size"], spec["channels"]
    per_step = 2 * p + batch * (size * size * ch * 4 + 3 * acts)
    return float(steps * per_step)
