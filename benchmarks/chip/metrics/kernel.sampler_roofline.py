"""Roofline share of the bucketed DDPM sampler (`_sample_strided`): the
least time for b_gen * sampler_steps UNet steps (padding excluded) over
the device time of the program. FLOPs set the roofline."""


def read(ctx):
    red, fl = ctx["red"], ctx["flops"]
    dev = ctx["module_time"](red, "sample_strided")
    if not dev:
        return None
    gen, m = ctx["config"]["generator"], ctx["config"]["model"]
    steps = ctx["cell"]["sampler_steps"]
    images = sum(r["b_gen"] for r in ctx["rounds"])
    flops = images * steps * fl.unet_step_flops(gen["base_width"])
    nbytes = sum(steps * fl.unet_step_bytes(gen["base_width"],
                                            m["num_classes"], r["b_gen"])
                 for r in ctx["rounds"] if r["b_gen"])
    least = max(flops / ctx["peak"]["flops"],
                nbytes / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / dev
