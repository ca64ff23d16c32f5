"""Roofline share of the bucketed diffusion sampler (`_sample_strided`):
the least time for b_gen * sampler_steps denoising steps (padding
excluded) over the device time of the program. FLOPs set the roofline.
Counts come from the generator architecture's file (`archs/`)."""


def read(ctx):
    red, arch = ctx["red"], ctx["generator"]
    dev = ctx["module_time"](red, "sample_strided")
    if not dev:
        return None
    gen, classes = ctx["config"]["generator"], \
        ctx["config"]["model"]["num_classes"]
    steps = ctx["cell"]["sampler_steps"]
    images = sum(r["b_gen"] for r in ctx["rounds"])
    flops = images * steps * arch.step_flops(classes, gen)
    nbytes = sum(steps * arch.step_bytes(classes, gen, r["b_gen"])
                 for r in ctx["rounds"] if r["b_gen"])
    least = max(flops / ctx["peak"]["flops"],
                nbytes / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / dev
