"""One-chip smoke run of the GenFV round loop on a TPU.

    python chip_smoke.py

Drives the paper's cell (Sec. VI) through `GenFVRunner` at the published
ResNet-18 width: CIFAR-10-sized procedural data (50,000 images), Dirichlet
alpha 0.1, the Section-VI `GenFVConfig` defaults (N=40, M=20, h=4,
batch 64, t_max=3 s), the highway scenario, the DDPM generator, the jitted
planner and the fused fleet dispatch, which donates the global params on
the chip. Weights are random from seed 0. Three rounds, then round 0 is
checked on the same chip against the plain references:

  (a) fused, donating fleet dispatch vs the sequential per-vehicle path
      (`vectorized=False`), under highest matmul precision, aggregated
      global params to max-abs FUSED_ATOL;
  (b) the jitted float64 planner vs the numpy reference on the same fleet,
      to DESIGN.md's table: alpha bitwise, l/phi/t_bar atol 1e-3, b_gen +-1.

Exits nonzero with no result line when JAX finds no TPU or any phase
fails. The last stdout line is one JSON object naming the device.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import time

ROUNDS = 3
TRAIN_SIZE = 50_000          # CIFAR-10's train count (configs/genfv_cifar.py)
#: max-abs bound of check (a). Both paths run the same f32 math on the same
#: batches; they differ only in how XLA orders the reductions of the
#: vmapped vs per-vehicle convs. Through 4 SGD steps of an 18-layer net the
#: gap is 5.5e-7 on XLA:CPU and 5.6e-5 on a v5e at full width. A lost or
#: doubled vehicle, a wrong weight or a clobbered donated buffer moves the
#: aggregate by a share of the round's update (max ~1e-1), three orders
#: above this bound.
FUSED_ATOL = 1e-4
PLAN_ATOL = 1e-3             # DESIGN.md §"The numpy-reference contract"


def require_tpu():
    """The device check comes first: no CPU fallback."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    return dev


def paper_cell(**overrides):
    from repro.fl import RunConfig
    return dataclasses.replace(
        RunConfig(dataset="cifar10", width_mult=1.0, strategy="genfv",
                  generator="ddpm", planner="jax", vectorized=True,
                  scenario="highway_free_flow", seed=0,
                  train_size=TRAIN_SIZE, rounds=ROUNDS), **overrides)


def _phases(obs, t: int) -> str:
    """One round's obs spans as `name=seconds[c]` (c: first use of the
    span's compile key)."""
    out = []
    for ev in obs.events:
        if ev["ph"] == "X" and ev["tags"].get("round") == t:
            mark = "c" if ev["stage"] == "compile" else ""
            out.append(f"{ev['name'].removeprefix('round/')}="
                       f"{ev['dur']:.3f}{mark}")
    return " ".join(out)


def run_rounds(dataset_fn):
    """The cell's rounds through the normal runner loop. Returns the
    runner and round 0's pending fleet (for check (b))."""
    import jax
    from repro.fl import GenFVRunner
    from repro.fl.fleet import bucket_size
    from repro.obs import Obs

    obs = Obs()
    t = time.perf_counter()
    runner = GenFVRunner(paper_cell(obs=obs), dataset_fn=dataset_fn)
    print(f"setup: runner built in {time.perf_counter() - t:.1f}s "
          f"(model {runner.model_bits / 1e6:.1f} Mbit, "
          f"{runner.cfg.num_vehicles} vehicles mean)")
    print(f"t0: {runner.svc.t_per_image:.6e} s/image measured by the "
          f"sampler (priced into eq. 12-13)")
    seen, first = set(), None
    for t in range(ROUNDS):
        t_start = time.perf_counter()
        pending = runner.begin_round(t)
        plan = runner.plan(pending)
        log = runner.finish_round(pending, plan)
        jax.block_until_ready(runner.server.params)
        wall = time.perf_counter() - t_start
        first = pending if first is None else first
        bucket = bucket_size(log.selected) if log.selected else 0
        stage = "compile" if bucket not in seen else "steady"
        seen.add(bucket)
        print(f"round {t}: K={len(plan.selected)} trained={log.selected} "
              f"bucket={bucket} b_gen={log.b_gen} t_bar={log.t_bar:.4f}s "
              f"loss={log.loss:.6f} acc={log.accuracy:.4f} "
              f"wall={wall:.3f}s ({stage})")
        print(f"  phases[s]: {_phases(obs, t)}")
        if not (log.loss == log.loss and abs(log.loss) < float("inf")):
            raise RuntimeError(f"round {t}: non-finite loss {log.loss}")
        if not 0.0 <= log.accuracy <= 1.0:
            raise RuntimeError(f"round {t}: accuracy {log.accuracy}")
        if bucket > 32:
            raise RuntimeError(f"round {t}: bucket {bucket} exceeds 32, the "
                               "largest fused dispatch that fits 16 GB")
    if not any(l.b_gen > 0 for l in runner.logs):
        raise RuntimeError("no round generated images (b_gen == 0)")
    return runner, first


def check_fused_vs_sequential(dataset_fn) -> float:
    """(a) Round 0 of the cell on both execution paths; max-abs delta of
    the aggregated global params."""
    import jax
    import numpy as np
    from repro.fl import GenFVRunner

    def leaves(r):
        return {jax.tree_util.keystr(k): np.asarray(x) for k, x in
                jax.tree_util.tree_leaves_with_path(r.server.params)}

    out, start = {}, None
    with jax.default_matmul_precision("highest"):
        for vec in (True, False):
            r = GenFVRunner(paper_cell(rounds=1, vectorized=vec),
                            dataset_fn=dataset_fn)
            start = start or leaves(r)
            r.run_round(0)
            out[vec] = leaves(r)
    deltas = {k: float(np.max(np.abs(out[True][k] - out[False][k])))
              for k in start}
    worst = max(deltas, key=deltas.get)
    delta = deltas[worst]
    update = max(float(np.max(np.abs(out[False][k] - start[k])))
                 for k in start)
    print(f"check (a) fused donating vs sequential, round 0: max|d|="
          f"{delta:.3e} at {worst} (|w| max "
          f"{float(np.max(np.abs(out[False][worst]))):.3e}; atol "
          f"{FUSED_ATOL:.0e}; round update max {update:.3e})")
    if not delta <= FUSED_ATOL:
        raise RuntimeError(f"check (a) failed: {delta:.3e} > {FUSED_ATOL}")
    return delta


def check_planner(runner, pending) -> dict:
    """(b) jax planner vs numpy reference on round 0's fleet; each path
    runs its own SUBP1."""
    import numpy as np
    from repro.core import plan_round

    plans = {p: plan_round(runner.cfg, pending.fleet, runner.model_bits,
                           runner.cfg.local_steps, b_prev=0, svc=runner.svc,
                           planner=p)
             for p in ("jax", "numpy")}
    pj, pn = plans["jax"], plans["numpy"]
    if not np.array_equal(pj.alpha, pn.alpha):
        raise RuntimeError("check (b) failed: alpha differs")
    d = {"l": float(np.max(np.abs(pj.l - pn.l), initial=0.0)),
         "phi": float(np.max(np.abs(pj.phi - pn.phi), initial=0.0)),
         "t_bar": abs(pj.t_bar - pn.t_bar),
         "b_gen": abs(pj.b_gen - pn.b_gen)}
    print(f"check (b) jax planner vs numpy, round 0 (K={len(pj.selected)}): "
          f"alpha equal, max|d| l={d['l']:.3e} phi={d['phi']:.3e} "
          f"t_bar={d['t_bar']:.3e} b_gen={d['b_gen']} "
          f"(atol {PLAN_ATOL:.0e}, b_gen +-1)")
    if max(d["l"], d["phi"], d["t_bar"]) > PLAN_ATOL or d["b_gen"] > 1:
        raise RuntimeError(f"check (b) failed: {d}")
    return d


def main() -> int:
    dev = require_tpu()
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    import jax
    from repro.compile_cache import use_compile_cache
    from repro.data.synthetic import make_image_dataset

    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"compile cache: {use_compile_cache()}")
    t_all = time.perf_counter()
    # one dataset build shared by the three runners (pure function of
    # (name, n, seed), as Sweep shares it)
    dataset_fn = functools.lru_cache(maxsize=4)(make_image_dataset)
    runner, pending = run_rounds(dataset_fn)
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"peak_bytes_in_use after rounds: {peak} ({peak / 2**30:.2f} GiB)")
    check_planner(runner, pending)
    check_fused_vs_sequential(dataset_fn)
    print(f"total: {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
