"""Roofline share of the fused fleet program (`_fleet_step_donated`): the
least time the chip needs for the useful lanes' work, K*h*B ResNet-18
training steps on one image each (padding lanes excluded), over the
device time of the program. The roofline is the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s; at these sizes FLOPs set it."""


def read(ctx):
    red, fl = ctx["red"], ctx["flops"]
    dev = ctx["module_time"](red, "fleet_step")
    if not dev:
        return None
    m, fl_cfg = ctx["config"]["model"], ctx["config"]["fl"]
    h, b = fl_cfg["local_steps"], fl_cfg["batch_size"]
    ks = [r["k"] for r in ctx["rounds"]]
    flops = sum(k * h * b for k in ks) * fl.resnet_train_flops(
        m["width_mult"], m["num_classes"])
    nbytes = sum(ks) * fl.resnet_train_bytes(m["width_mult"],
                                             m["num_classes"], b, h)
    least = max(flops / ctx["peak"]["flops"],
                nbytes / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / dev
