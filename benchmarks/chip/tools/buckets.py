"""Which fleet, planner and sampler buckets a cell's window reaches.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tools/buckets.py \
        c10.genfv.highway --seeds 16 [--rounds 402] [--workers 4] \
        [--first-seed 1000] [--out trajectories.json]
    JAX_PLATFORMS=cpu python3 benchmarks/chip/tools/buckets.py \
        c10.genfv.highway --warm-from trajectories.json --traffic-seed 1003

Runs the cell's round loop on the CPU for `--rounds` rounds per seed with
everything that decides selection and planning as on the chip: the same
seeded labels and partition, the same world, model_bits of the full-width
ResNet-18 and the configuration's pinned t0. Nothing is trained: the
fleet dispatch returns the params it is given, the RSU's training draws
its batch indices from the round loop's generator (h x B with
replacement, as `fl/client.py::client_update` does) and trains nothing,
eval reads 0, and the generator returns blank images of the scheduled
count. The random streams are thus drawn as in a chip run; a chip run
prints its K histogram and mean b_gen to compare.
Prints, per seed and over all seeds, the histogram of K (vehicles
trained), of the fleet bucket (trained K), of the planner bucket
(selected K) and of the sampler bucket (b_gen), and the b_gen range.
Each seed here is a candidate `traffic_seed` (the round loop's own seed,
which fixes every round's K and b_gen). `--out` keeps the trajectories;
`--warm-from` prints, for the cell's chosen traffic seed, the `warm`
block of its cell file: the buckets and the sizes its first `--rounds`
rounds reach. `--rounds` defaults to the rounds a window could hold at
`FLOOR_ROUND_S` a round (a program several times faster than today's),
plus the warm rounds before it, so that a faster program still finds
every size warm; a window that goes past the warm lists compiles, and
the run reads not correct.
"""
import argparse
import json
import sys
from collections import Counter
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

SIM_WIDTH = 0.0625
FLOOR_ROUND_S = 0.1


def default_rounds() -> int:
    from chipbench import cells, session
    return session.WARM_ROUNDS + int(cells.benchmark()["run_seconds"]
                                     / FLOOR_ROUND_S)


class _Blank:
    """Generator stand-in: `count` blank one-pixel images (the RSU's pool
    only has to count them), no random draws."""

    def generate(self, labels, rng, round_idx=0):
        import numpy as np
        return np.zeros((len(labels), 1, 1, 1), np.float32)


def _untrained(runner):
    """Round loop with the device work taken out, random draws kept."""
    import numpy as np
    srv = runner.server

    def run(global_params, imgs, labels, rhos, *a, **kw):
        return global_params, np.zeros(len(imgs))

    def train_augmented(h, batch_size, lr):
        if srv.pool_labels is None or len(srv.pool_labels) < 2:
            return srv.params, 0.0
        srv.rng.integers(0, len(srv.pool_labels), size=(h, batch_size))
        return srv.params, 0.0

    runner.engine.run = run
    srv.train_augmented = train_augmented
    runner._eval = lambda p, x, y: 0.0


def sim_runner(cell_name: str, seed: int, rounds: int):
    """The cell's round loop with the device work taken out (`_untrained`)
    and the round loop's seed `seed`."""
    from chipbench import cells, data, flops
    from repro.configs.genfv_cifar import genfv_config
    from repro.fl import GenFVRunner, RunConfig
    from repro.gen.calib import MeasuredService

    celld = cells.cell(cell_name)
    cfgd = cells.config(celld["config"])
    m, r, g = cfgd["model"], cfgd["run"], cfgd["generator"]
    bits = 32.0 * flops.resnet_params(m["width_mult"], m["num_classes"])
    run = RunConfig(dataset=r["dataset"], alpha=celld["alpha"],
                    strategy=celld["strategy"], train_size=r["train_size"],
                    test_size=r["test_size"], width_mult=SIM_WIDTH,
                    seed=seed, model_bits=bits, scenario=celld["scenario"],
                    planner="jax", generator="ddpm",
                    sampler_steps=celld["sampler_steps"], rounds=rounds)
    runner = GenFVRunner(
        run, fl_cfg=genfv_config(r["dataset"], celld["alpha"], **cfgd["fl"]),
        generator=_Blank(), dataset_fn=data.make_dataset,
        svc=MeasuredService(t_image=g["t_image"], steps=g["t_image_steps"]))
    _untrained(runner)
    return runner


def simulate(cell_name: str, seed: int, rounds: int) -> dict:
    from chipbench import cells
    from repro.core.planner import bucket_size

    celld = cells.cell(cell_name)
    runner = sim_runner(cell_name, seed, rounds)
    out = []
    for t in range(rounds):
        pending = runner.begin_round(t)
        plan = runner.plan(pending)
        lg = runner.finish_round(pending, plan)
        out.append((len(plan.selected), lg.selected, lg.b_gen))
    gen = celld["strategy"] in ("genfv", "aigc_only")
    return {"seed": seed,
            "selected": [s for s, _, _ in out],
            "trained": [k for _, k, _ in out],
            "planner_buckets": [bucket_size(s) for s, _, _ in out if s],
            "fleet_buckets": [bucket_size(k) for _, k, _ in out if k],
            "sampler_buckets": [bucket_size(b) for _, _, b in out
                                if gen and b],
            "b_gen": [b for _, _, b in out]}


def _hist(xs):
    return dict(sorted(Counter(xs).items()))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("cell")
    p.add_argument("--seeds", type=int, default=16)
    p.add_argument("--first-seed", type=int, default=1000)
    p.add_argument("--rounds", type=int, default=None)
    p.add_argument("--workers", type=int, default=4)
    p.add_argument("--out", default=None)
    p.add_argument("--warm-from", default=None)
    p.add_argument("--traffic-seed", type=int, default=None)
    a = p.parse_args()
    a.rounds = a.rounds or default_rounds()
    if a.warm_from:
        with open(a.warm_from) as f:
            r = next(t for t in json.load(f) if t["seed"] == a.traffic_seed)
        n = a.rounds
        sel, trained, b_gen = (r["selected"][:n], r["trained"][:n],
                               r["b_gen"][:n])
        from repro.core.planner import bucket_size
        warm = {"fleet_buckets": sorted({bucket_size(k) for k in trained
                                         if k}),
                "planner_buckets": sorted({bucket_size(k) for k in sel
                                           if k}),
                "sampler_buckets": sorted({bucket_size(b) for b in b_gen
                                           if b}),
                "fleet_sizes": sorted({k for k in trained if k}),
                "sampler_sizes": sorted({b for b in b_gen if b})}
        if not r["sampler_buckets"]:
            warm["sampler_buckets"], warm["sampler_sizes"] = [], []
        print(json.dumps(warm))
        return
    seeds = range(a.first_seed, a.first_seed + a.seeds)
    with ProcessPoolExecutor(a.workers) as ex:
        res = list(ex.map(simulate, [a.cell] * a.seeds, seeds,
                          [a.rounds] * a.seeds))
    if a.out:
        with open(a.out, "w") as f:
            json.dump(res, f)
    for r in res:
        print(json.dumps({"seed": r["seed"], "K": _hist(r["trained"]),
                          "mean_K": sum(r["trained"]) / len(r["trained"]),
                          "fleet_buckets": _hist(r["fleet_buckets"]),
                          "sampler_buckets": _hist(r["sampler_buckets"]),
                          "b_gen": [min(r["b_gen"]), max(r["b_gen"])],
                          "mean_b_gen": sum(r["b_gen"]) / len(r["b_gen"])}))
    allk = [k for r in res for k in r["trained"]]
    summary = {
        "cell": a.cell, "seeds": a.seeds, "rounds": a.rounds,
        "K": _hist(allk), "mean_K": sum(allk) / len(allk),
        "fleet_buckets": _hist(b for r in res for b in r["fleet_buckets"]),
        "planner_buckets": _hist(b for r in res
                                 for b in r["planner_buckets"]),
        "sampler_buckets": _hist(b for r in res
                                 for b in r["sampler_buckets"]),
        "b_gen_range": [min(b for r in res for b in r["b_gen"]),
                        max(b for r in res for b in r["b_gen"])],
        "mean_b_gen": (sum(b for r in res for b in r["b_gen"])
                       / sum(len(r["b_gen"]) for r in res))}
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
