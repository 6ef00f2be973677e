"""End-to-end driver (deliverable b): the paper's experiment — wireless MFL
training for a few hundred communication rounds, JCSBA vs. a baseline, on the
synthetic CREMA-D stand-in.  Saves curves + a comparison summary.

  PYTHONPATH=src python examples/wireless_mfl.py --rounds 120
"""
import argparse
import json
import os

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.fl.runtime import MFLExperiment


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=120)
    ap.add_argument("--dataset", default="crema_d")
    ap.add_argument("--n-samples", type=int, default=800)
    ap.add_argument("--baseline", default="random")
    ap.add_argument("--dirichlet-alpha", type=float, default=0.0,
                    help="label-skew Dirichlet concentration (0 = IID "
                         "equal shards, the paper's setting; smaller = "
                         "stronger non-IID)")
    ap.add_argument("--engine", default="batched",
                    help="round engine spec '<loop>[:<backend>]': loop is "
                         "seq (per-client reference), batched (default, one "
                         "vmapped client stage per round) or fused (the "
                         "whole experiment as one lax.scan with device-"
                         "resident eval — every algorithm: jcsba/random/"
                         "round_robin/selection/dropout); the optional "
                         "backend picks the JCSBA solver for parity runs "
                         "(jax default, np = float64 mirror, seq = original "
                         "scalar path — host loops only)")
    ap.add_argument("--out", default="examples/out_wireless_mfl.json")
    args = ap.parse_args()
    enable_compile_cache()

    eval_every = 4
    results = {}
    for algo in [args.baseline, "jcsba"]:
        fused = args.engine.partition(":")[0] == "fused"
        print(f"=== {algo}{' (fused)' if fused else ''} ===")
        exp = MFLExperiment(dataset=args.dataset, scheduler=algo,
                            n_samples=args.n_samples, seed=0,
                            dirichlet_alpha=args.dirichlet_alpha,
                            eval_every=eval_every, engine=args.engine)
        if fused:
            # one scan for the whole run: the device-resident eval samples
            # the same t % eval_every == 0 rounds as the host loop records
            exp.run_scanned(args.rounds)
        else:
            exp.run(args.rounds, verbose=False)
        fin = exp.final_metrics()
        curves = [(r.round, r.metrics.get("multimodal"), r.energy_total)
                  for r in exp.history if r.metrics]
        results[algo] = {"final": fin, "curve": curves}
        # host engines time their scheduler; the fused path has none
        sched = ("" if "mean_sched_time_s" not in fin else
                 f" sched={fin['mean_sched_time_s'] * 1e3:.1f}ms/round")
        print(f"{algo}: multimodal={fin.get('multimodal', 0):.4f} "
              f"energy={fin.get('energy_total', 0):.3f}J{sched}")

    mm_gain = (results["jcsba"]["final"].get("multimodal", 0)
               - results[args.baseline]["final"].get("multimodal", 0))
    print(f"\nJCSBA multimodal gain over {args.baseline}: {mm_gain*100:+.2f}% "
          f"(paper reports +4.06% over conventional algorithms)")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print("saved ->", args.out)


if __name__ == "__main__":
    main()
