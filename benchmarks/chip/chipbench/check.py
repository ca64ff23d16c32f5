"""Decides `correct`: the kept rounds of the window against the plain
references (`reference/`, reached through the architecture files the
config names), layer by layer, each layer fed the inputs the program's
layer was fed.

Numbers compared (the worst over the kept rounds), each against the limit
in the cell file:
  plan_alpha   SUBP1 indicators that differ (genfv: the EMD and deadline
               rule; fedavg: |sum(alpha) - max(1, floor(0.3 N))|)
  plan_gap     max |d| of the SUBP2-3 subcarriers, powers and t_bar,
               float64 planner against the numpy reference
  plan_bgen    |d| of the SUBP4 image count b*
  sample_gap   max |d| over every pixel of every image the round generated
  sample_rms   the worst image's root mean square |d|
  aug_loss     relative gap of the RSU training's mean loss
  aug_norm     worst-leaf gap of the RSU update's norm (below)
  fleet_loss   worst relative gap of a vehicle's mean local-SGD loss
  fleet_norm   worst-leaf gap of the aggregated round update's norm
  eval_gap     test images by which the round's accuracy count differs
and, read beside them, `*_norm_med` (the median leaf's gap) and
`*_norm_leaf` (the worst leaf's name). Only the numbers the cell file
gives a limit are compared.
A norm gap is |‖d_prog‖ - ‖d_ref‖| / max(‖d_ref‖, median leaf ‖d_ref‖)
for the update d = params_after - params_before of each leaf; leaves whose
reference update is under a thousandth of the median leaf's move by
round-off alone and are left out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import planner as ref_plan

GENERATING = ("genfv", "aigc_only")


def _host(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)


def _leaves(tree):
    return {jax.tree_util.keystr(k): np.asarray(v, np.float64)
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def norm_gap(before, after_prog, after_ref):
    """(worst gap, its leaf, median gap) of the per-leaf update norms."""
    b, p, r = _leaves(before), _leaves(after_prog), _leaves(after_ref)
    dref = {k: float(np.linalg.norm(r[k] - b[k])) for k in b}
    dprog = {k: float(np.linalg.norm(p[k] - b[k])) for k in b}
    med = float(np.median(list(dref.values())))
    worst, where, gaps = 0.0, "", []
    for k in b:
        if dref[k] < 1e-3 * med:
            continue
        scale = max(dref[k], med)
        g = abs(dprog[k] - dref[k]) / scale if scale > 0 else (
            0.0 if dprog[k] == 0 else float("inf"))
        g = g if g == g else float("inf")     # NaN counts as the worst
        gaps.append(g)
        if g > worst or not where:
            worst, where = g, k
    return worst, where, (float(np.median(gaps)) if gaps else 0.0)


def _vehicles(fleet):
    return [dict(x=v.x, v=v.v, phi_max=v.phi_max, f_mem=v.f_mem,
                 f_core=v.f_core, v_core=v.v_core, gain_db=v.gain_db,
                 emd=v.emd) for v in fleet]


def reference_outputs(rec, ctx, dtype=jnp.float32):
    """What each layer should have produced from the inputs the program's
    layer was given; `dtype` float32 is the reference, bfloat16 (float32
    for the planner) the control."""
    low = dtype != jnp.float32
    pdt = np.float32 if low else np.float64
    out = {}
    fleet = _vehicles(rec["pending"].fleet)
    cfg, h = ctx["cfg"], ctx["cfg"]["local_steps"]
    if ctx["strategy"] in ("genfv", "aigc_only", "fl_only"):
        out["alpha"] = ref_plan.select(cfg, fleet, ctx["model_bits"], h, pdt)
    out["plan"] = ref_plan.plan(cfg, fleet, rec["pending"].alpha,
                                ctx["model_bits"], h, rec["b_prev"],
                                ctx["t_image"], pdt)
    params_in = rec["params_in"]
    if "gen" in rec:
        labels, _, r = rec["gen"]
        out["images"] = ctx["generator"].sample(
            ctx["gen_params"], ctx["gen_spec"], ctx["seed"], r, labels,
            ctx["sampler_steps"], dtype)
    if "aug" in rec:
        a = rec["aug"]
        imgs, labels = a["pool"]
        if labels is None or len(labels) < 2:
            # nothing generated yet: omega_a is the round-start model
            out["aug"] = (_host(params_in), 0.0)
        else:
            # the RSU draws h x B pool indices with replacement from the
            # round loop's generator, as a vehicle draws its batches
            g = np.random.Generator(np.random.PCG64())
            g.bit_generator.state = a["rng_state"]
            idx = g.integers(0, len(labels), size=(a["h"], a["batch"]))
            p, losses = ctx["client"].local_sgd(params_in, imgs[idx],
                                                labels[idx], a["lr"], dtype)
            out["aug"] = (_host(p), float(np.mean(losses)))
    if "fleet" in rec:
        f = rec["fleet"]
        emd = f["emd_bar"] if f["aug"] is not None else 0.0
        k2 = min(max((emd / 2.0) ** 2, 0.0), 1.0)
        acc = jax.tree.map(lambda a: np.zeros(a.shape, np.float64),
                           params_in)
        losses = []
        for rho, bi, bl in zip(f["rhos"], f["imgs"], f["labels"]):
            p, ls = ctx["client"].local_sgd(params_in, bi, bl, f["lr"], dtype)
            losses.append(float(np.mean(ls)))
            acc = jax.tree.map(lambda s, x: s + (1 - k2) * rho * np.asarray(
                x, np.float64), acc, p)
        if f["aug"] is not None:
            acc = jax.tree.map(lambda s, x: s + k2 * np.asarray(
                x, np.float64), acc, f["aug"])
        out["fleet"] = (acc, losses)
    with jax.default_matmul_precision("highest"):
        out["acc"] = float(ctx["client"].accuracy(
            rec["params_out"], jnp.asarray(ctx["test"][0]),
            jnp.asarray(ctx["test"][1]), dtype=dtype))
    return out


def program_outputs(rec):
    out = {"alpha": np.asarray(rec["pending"].alpha), "plan": dict(
        l=rec["plan"].l, phi=rec["plan"].phi, b_gen=rec["plan"].b_gen,
        t_bar=rec["plan"].t_bar)}
    if "gen" in rec:
        out["images"] = rec["gen"][1]
    if "aug" in rec:
        out["aug"] = (_host(rec["aug"]["out"]), rec["aug"]["loss"])
    if "fleet" in rec:
        out["fleet"] = (_host(rec["params_out"]),
                        [float(x) for x in rec["fleet"]["losses"]])
    out["acc"] = float(rec["log"].accuracy)
    return out


def _rel(a, b):
    return abs(a - b) / abs(b) if b else (0.0 if a == b else float("inf"))


def compare(rec, got, ref, ctx):
    """{name: number} for one kept round."""
    n = {}
    if "alpha" in ref:
        n["plan_alpha"] = float(np.sum(np.asarray(got["alpha"])
                                       != ref["alpha"]))
    else:
        # random selection (fedavg) is held to its count; the control has
        # no selection of its own and is given the round's
        alpha = got.get("alpha", rec["pending"].alpha)
        want = max(1, int(0.3 * len(rec["pending"].fleet)))
        n["plan_alpha"] = float(abs(int(np.sum(alpha)) - want))
    gp, rp = got["plan"], ref["plan"]
    if len(rp["l"]):
        n["plan_gap"] = float(max(np.max(np.abs(gp["l"] - rp["l"])),
                                  np.max(np.abs(gp["phi"] - rp["phi"])),
                                  abs(gp["t_bar"] - rp["t_bar"])))
    n["plan_bgen"] = float(abs(gp["b_gen"] - rp["b_gen"]))
    if "images" in ref:
        d = np.abs(got["images"] - ref["images"])
        n["sample_gap"] = float(np.max(d, initial=0.0))
        n["sample_rms"] = float(np.max(np.sqrt(np.mean(
            d.reshape(len(d), -1) ** 2, axis=1)), initial=0.0))
    params_in = _host(rec["params_in"])
    if "aug" in ref:
        n["aug_loss"] = _rel(got["aug"][1], ref["aug"][1])
        n["aug_norm"], n["aug_norm_leaf"], n["aug_norm_med"] = norm_gap(
            params_in, got["aug"][0], ref["aug"][0])
    if "fleet" in ref:
        n["fleet_loss"] = max(_rel(a, b) for a, b in
                              zip(got["fleet"][1], ref["fleet"][1]))
        n["fleet_norm"], n["fleet_norm_leaf"], n["fleet_norm_med"] = \
            norm_gap(params_in, got["fleet"][0], ref["fleet"][0])
    n["eval_gap"] = float(round(abs(got["acc"] - ref["acc"])
                                * len(ctx["test"][1])))
    return n


def worst(numbers):
    """Elementwise worst over rounds; NaN is the worst. Leaf names are
    not numbers and are left out."""
    out = {}
    for d in numbers:
        for k, v in d.items():
            if isinstance(v, str):
                continue
            v = float("inf") if v != v else v
            out[k] = max(out.get(k, 0.0), v)
    return out


def judge(numbers: dict, limits: dict, window_compiles: int = 0):
    """(correct, [(name, value, limit)]) over the numbers the cell gives a
    limit; a number with a limit that no kept round produced fails. A
    compilation inside the measured window (a size the warm lists miss)
    fails the run as well: it would be timed as work."""
    rows = [(k, numbers.get(k, float("inf")), limits[k]) for k in limits]
    rows.append(("window_compiles", window_compiles, 0))
    return all(v <= lim for _, v, lim in rows), rows
