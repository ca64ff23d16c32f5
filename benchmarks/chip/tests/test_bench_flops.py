"""The FLOP and parameter counts the roofline and MFU metrics use,
against the round loop's own models and XLA's cost analysis."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import flops


@pytest.mark.parametrize("width,classes", [(1.0, 10), (1.0, 43),
                                           (0.25, 10)])
def test_resnet_param_count_matches_round_loop(width, classes):
    from repro.configs.genfv_cifar import CNNConfig
    from repro.models.cnn import init_cnn
    cfg = CNNConfig(name="t", num_classes=classes, width_mult=width)
    shapes = jax.eval_shape(lambda k: init_cnn(k, cfg), jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == flops.resnet_params(width, classes)


def test_unet_param_count_matches_round_loop():
    from repro.diffusion.unet import init_unet
    shapes = jax.eval_shape(lambda k: init_unet(k, 10, base=16),
                            jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == \
        flops.unet_params(16, 10)


def _xla_flops(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    cost = lowered.compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["flops"])


def test_resnet_train_step_flops_match_xla():
    """One SGD step of the round loop's ResNet-18 on a batch of 8.

    XLA counts every operation; the model count leaves out GroupNorm,
    ReLU, softmax and the update, which at full width add 2-6% to the
    convolutions. The tolerance, model <= XLA <= 1.08 x model, is that
    share with room; a missing or doubled layer moves the count by 10%
    or more."""
    from repro.configs.genfv_cifar import cnn_config
    from repro.fl.client import local_sgd_steps
    from repro.models.cnn import init_cnn
    cfg = cnn_config("cifar10", 1.0)
    params = jax.eval_shape(lambda k: init_cnn(k, cfg), jax.random.key(0))
    b = 8
    imgs = jax.ShapeDtypeStruct((1, b, 32, 32, 3), jnp.float32)
    labels = jax.ShapeDtypeStruct((1, b), jnp.int32)
    xla = _xla_flops(lambda p, x, y: local_sgd_steps(p, cfg, x, y, 1, 0.05),
                     params, imgs, labels)
    model = b * flops.resnet_train_flops(1.0, 10)
    assert model <= xla <= 1.08 * model, xla / model


def test_unet_step_flops_match_xla():
    """One UNet forward of the served generator (base width 16) on 8
    images. Same rule and tolerance as the ResNet step: the model count
    leaves out GroupNorm, SiLU and the softmax."""
    from repro.diffusion.unet import init_unet, unet_apply
    params = jax.eval_shape(lambda k: init_unet(k, 10, base=16),
                            jax.random.key(0))
    b = 8
    x = jax.ShapeDtypeStruct((b, 32, 32, 3), jnp.float32)
    t = jax.ShapeDtypeStruct((b,), jnp.int32)
    xla = _xla_flops(unet_apply, params, x, t, t)
    model = b * flops.unet_step_flops(16)
    assert model <= xla <= 1.08 * model, xla / model
