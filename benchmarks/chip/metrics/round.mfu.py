"""Model FLOPs of the traced rounds over the window times peak FLOP/s:
vehicle training (K*h*B images), RSU augmented training (h*factor*B
images, genfv), sampling (b_gen * steps UNet steps) and the eval forward
pass; no padding lanes and no recomputation."""


def read(ctx):
    red, fl = ctx["red"], ctx["flops"]
    if not red["window_s"]:
        return None
    m, f = ctx["config"]["model"], ctx["config"]["fl"]
    train = fl.resnet_train_flops(m["width_mult"], m["num_classes"])
    fwd = fl.resnet_fwd_flops(m["width_mult"], m["num_classes"])
    unet = fl.unet_step_flops(ctx["config"]["generator"]["base_width"])
    h, b = f["local_steps"], f["batch_size"]
    gen = ctx["cell"]["strategy"] in ("genfv", "aigc_only")
    aug = h * ctx["rsu_steps_factor"] * b if gen else 0
    total = 0.0
    for r in ctx["rounds"]:
        total += (r["k"] * h * b + aug) * train
        total += r["b_gen"] * ctx["cell"]["sampler_steps"] * unet
        total += ctx["config"]["run"]["test_size"] * fwd
    return 100.0 * total / (red["window_s"] * ctx["peak"]["flops"])
