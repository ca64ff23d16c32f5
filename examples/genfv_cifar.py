"""End-to-end GenFV driver (paper Sec. VI): federated training of the
ResNet-18-style CNN on the CIFAR10-like procedural dataset with Dirichlet
non-IID partitions, comparing GenFV against FL-only and FedAvg.

  PYTHONPATH=src python examples/genfv_cifar.py [--rounds 12] [--alpha 0.1]

This is the "train a ~100M-model-class workload for a few hundred steps"
driver at CPU scale: 12 rounds x 16 vehicles x 4 local steps = ~768 SGD
steps through the federated pipeline.
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs.base import GenFVConfig
from repro.exp import ExperimentSpec, Sweep
from repro.fl import RunConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--dataset", default="cifar10")
    ap.add_argument("--schemes", default="genfv,fl_only,fedavg")
    ap.add_argument("--scenario", default="highway_free_flow",
                    help="repro.sim traffic scenario, or 'legacy' for the "
                         "memoryless per-round fleet sampler")
    args = ap.parse_args()
    use_compile_cache()

    # one declarative grid over the scheme axis; Sweep shares the dataset
    # build across schemes and plans all their rounds in batched dispatches
    spec = ExperimentSpec(
        name="genfv_cifar",
        strategies=tuple(args.schemes.split(",")),
        alphas=(args.alpha,),
        base=RunConfig(dataset=args.dataset, rounds=args.rounds,
                       train_size=2000, test_size=192, width_mult=0.125,
                       seed=3, model_bits=11.2e6 * 32,
                       scenario=args.scenario))
    fl_cfg = GenFVConfig(batch_size=16, local_steps=4, num_vehicles=16)
    result = Sweep(spec, fl_cfg=fl_cfg, verbose=True).run()

    print("\n=== summary (mean of last 3 rounds) ===")
    for scheme in spec.strategies:
        acc = result.curve("accuracy", strategy=scheme)
        print(f"  {scheme:10s} acc={np.mean(acc[-3:]):.3f}  "
              f"curve={[round(a, 3) for a in acc.tolist()]}")


if __name__ == "__main__":
    main()
