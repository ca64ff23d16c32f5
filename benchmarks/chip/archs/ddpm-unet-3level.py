"""Generator architecture `ddpm-unet-3level`: the class-conditional DDPM
the RSU serves, a three-level UNet ([c, 2c, 4c] channels at 32, 16 and
8 pixels, one residual block per level, self-attention at 8x8) under a
linear-beta schedule, sampled on a strided schedule (plain reference in
`reference/unet.py`).

The interface of a generator architecture file:
  init(key, num_classes, spec)          seeded weights, float32
  program(num_classes, spec)            the program's model for this spec
  program_layout(num_classes, spec)     its parameters' shapes and dtypes,
                                        from the program's own init
  serve(params, num_classes, spec, seed, sampler_steps, obs)
                                        the program's generator serving
                                        `params`, as the round loop takes it
  param_count(num_classes, spec)
  step_flops(num_classes, spec)         one denoising step of one image
  step_bytes(num_classes, spec, images) one denoising step of a batch
  sample(params, spec, seed, round_idx, labels, steps, dtype)
                                        the plain reference's images
`spec` is the config's `generator` group: `base_width` (c) and
`timesteps` set the model. Counts follow `chipbench.flops`.
"""
from chipbench import flops
from reference import unet


def _base(spec) -> int:
    return int(spec["base_width"])


def init(key, num_classes: int, spec: dict):
    return unet.init(key, num_classes, _base(spec))


def program(num_classes: int, spec: dict):
    from repro.diffusion.ddpm import DDPM
    return DDPM(timesteps=int(spec["timesteps"]), num_classes=num_classes,
                base_width=_base(spec))


def program_layout(num_classes: int, spec: dict):
    import jax
    from repro.diffusion.ddpm import make_ddpm
    ddpm = program(num_classes, spec)
    return jax.eval_shape(lambda k: make_ddpm(k, ddpm),
                          jax.random.PRNGKey(0))


def serve(params, num_classes: int, spec: dict, seed: int,
          sampler_steps: int, obs=None):
    from repro.gen.service import BatchedDDPMGenerator
    return BatchedDDPMGenerator(params, program(num_classes, spec),
                                seed=int(seed), sampler_steps=sampler_steps,
                                obs=obs)


def sample(params, spec: dict, seed: int, round_idx: int, labels,
           steps: int, dtype):
    return unet.sample(params, seed, round_idx, labels,
                       int(spec["timesteps"]), steps, dtype)


def _layers(base: int, emb: int = unet.EMB):
    conv = flops.conv
    c1, c2, c3 = base, 2 * base, 4 * base
    out = [(2 * emb * emb, emb), conv(32, 3, 3, c1)]

    def res(hw, ci, co):
        r = [conv(hw, 3, ci, co), (emb * co, co), conv(hw, 3, co, co)]
        if ci != co:
            r.append(conv(hw, 1, ci, co))
        return r
    out += res(32, c1, c1)
    out.append(conv(32, 3, c1, c2, 2))
    out += res(16, c2, c2)
    out.append(conv(16, 3, c2, c3, 2))
    out += res(8, c3, c3)
    L = 64
    out += [(3 * L * c3 * c3, 3 * L * c3), (L * L * c3, L * L),
            (L * L * c3, L * c3), (L * c3 * c3, L * c3)]
    out += res(8, c3, c3)
    out += res(16, c3 + c2, c2)
    out += res(32, c2 + c1, c1)
    out.append(conv(32, 3, c1, 3))
    return out


def param_count(num_classes: int, spec: dict, emb: int = unet.EMB) -> int:
    base = _base(spec)
    c1, c2, c3 = base, 2 * base, 4 * base

    def res(ci, co):
        return (2 * ci + 9 * ci * co + emb * co + 2 * co + 9 * co * co
                + (ci * co if ci != co else 0))
    return (num_classes * emb + 2 * emb * emb + 27 * c1 + res(c1, c1)
            + 9 * c1 * c2 + res(c2, c2) + 9 * c2 * c3 + res(c3, c3)
            + 2 * c3 + 4 * c3 * c3 + res(c3, c3) + res(c3 + c2, c2)
            + res(c2 + c1, c1) + 2 * c1 + 27 * c1)


def step_flops(num_classes: int, spec: dict) -> float:
    """One denoising step (one UNet forward) of one image."""
    return 2.0 * sum(m for m, _ in _layers(_base(spec)))


def step_bytes(num_classes: int, spec: dict, images: int) -> float:
    """One denoising step of a batch of `images`."""
    acts = sum(e for _, e in _layers(_base(spec))) * 4
    return float(param_count(num_classes, spec) * 4
                 + images * (2 * 32 * 32 * 3 * 4 + 2 * acts))
