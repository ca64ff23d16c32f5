"""Model FLOPs of the traced rounds over the window times peak FLOP/s:
vehicle training (K*h*B images), RSU augmented training (h*factor*B
images) and sampling (b_gen * steps denoising steps) where the strategy
generates, and the eval forward pass; no padding lanes and no
recomputation. Counts come from the architecture files (`archs/`)."""


def read(ctx):
    red, client, generator = ctx["red"], ctx["client"], ctx["generator"]
    if not red["window_s"]:
        return None
    m, f = ctx["config"]["model"], ctx["config"]["fl"]
    classes = m["num_classes"]
    train = client.train_flops(classes, m)
    fwd = client.fwd_flops(classes, m)
    step = generator.step_flops(classes, ctx["config"]["generator"])
    h, b = f["local_steps"], f["batch_size"]
    # a strategy that does not generate still plans b_gen: nothing is
    # sampled or trained on it
    gen = ctx["cell"]["strategy"] in ("genfv", "aigc_only")
    aug = h * ctx["rsu_steps_factor"] * b if gen else 0
    total = 0.0
    for r in ctx["rounds"]:
        total += (r["k"] * h * b + aug) * train
        total += (r["b_gen"] if gen else 0) * ctx["cell"]["sampler_steps"] \
            * step
        total += ctx["config"]["run"]["test_size"] * fwd
    return 100.0 * total / (red["window_s"] * ctx["peak"]["flops"])
