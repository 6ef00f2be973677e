"""Backbone rounds: fused-round throughput and peak memory per model family
(lstm-cnn / transformer / ssd) at K ∈ {50, 5000}, remat on and off.

The model-adapter layer (fl/client.py) runs transformer- and SSD-backed
unimodal encoders through the same cohort-gather fused round as the paper's
LSTM/CNN submodels.  This benchmark commits the cost of that architecture
axis:

* ``rounds_per_s`` / ``ms_per_round`` — wall-clock fused-round throughput
  (compiled ``eng.step``, carry chained across reps);
* ``temp_bytes`` — XLA's peak temp allocation for the round program
  (``compiled.memory_analysis().temp_size_in_bytes``): the activation
  working set the remat engine token exists to shrink — remat rows
  checkpoint each client's loss (``ModelAdapter.cohort_step``), trading
  recompute for [J]-stacked activation memory.

Populations/engines mirror benchmarks/population_scale.py (vectorized
``synthetic_population`` → ``FusedRoundEngine.from_store``, RandomPolicy at
a fixed cohort J, 1 MHz/client bandwidth density, eval disabled).

  PYTHONPATH=src python -m benchmarks.backbone_rounds \
      --json-out BENCH_backbone_rounds.json                           # full
  PYTHONPATH=src python -m benchmarks.backbone_rounds --tiny \
      --json-out BENCH_backbone_rounds.json                           # CI
"""
from __future__ import annotations

import argparse
import json
import time
from typing import List, Optional

import numpy as np

from .population_scale import _round_xs, build_population


def _make_engine(K: int, J: int, dataset: str, arch: str, remat: bool,
                 n_per_client: int, seed: int):
    from repro.fl.client import make_adapter
    from repro.fl.fused_round import FusedRoundEngine
    from repro.wireless.params import WirelessParams
    from repro.wireless.policies import RandomPolicy

    params = WirelessParams(K=K, B_max=1e6 * K, E_add=2e-4)
    store = build_population(K, n_per_client, dataset, params, seed=seed)
    adapter = make_adapter(dataset, arch, remat=remat)
    eng = FusedRoundEngine.from_store(store, params, RandomPolicy(K, J),
                                      adapter, V=1.0, seed=seed)
    return eng, params


def bench_row(arch: str, K: int, remat: bool, J: int, reps: int,
              dataset: str = "iemocap", n_per_client: int = 2,
              seed: int = 0) -> dict:
    import jax
    from repro.wireless.channel import Channel

    eng, params = _make_engine(K, J, dataset, arch, remat, n_per_client,
                               seed)
    carry = eng.fresh_carry()
    rng = np.random.default_rng(seed + 1)
    channel = Channel(params, rng)
    xs = _round_xs(rng, channel, K)

    carry, _ = jax.block_until_ready(eng.step(carry, xs))  # compile + warmup
    xs_list = [_round_xs(rng, channel, K) for _ in range(reps)]
    t0 = time.perf_counter()
    for xs in xs_list:
        carry, aux = eng.step(carry, xs)
    jax.block_until_ready((carry, aux))
    ms = (time.perf_counter() - t0) / reps * 1e3

    mem = eng.lower(carry, xs, scanned=False).compile(
        ).memory_analysis()
    row = {"arch": arch, "K": K, "remat": remat, "J": J, "reps": reps,
           "dataset": dataset, "n_per_client": n_per_client,
           "ms_per_round": round(ms, 3),
           "rounds_per_s": round(1e3 / ms, 2),
           "scheduled": int(np.asarray(aux.ok).sum()),
           "temp_bytes": None if mem is None else int(mem.temp_size_in_bytes),
           "arg_bytes": None if mem is None
           else int(mem.argument_size_in_bytes)}
    tmp = "n/a" if mem is None else f"{mem.temp_size_in_bytes / 2 ** 20:.1f}"
    print(f"{arch:12s} K={K:6d} remat={int(remat)} {ms:9.2f} ms/round "
          f"({row['rounds_per_s']:7.2f} rounds/s)  temp={tmp} MiB",
          flush=True)
    return row


def run_benchmark(archs: List[str], Ks: List[int], J: int, reps: int,
                  dataset: str, n_per_client: int) -> dict:
    rows = []
    for arch in archs:
        for K in Ks:
            for remat in (False, True):
                rows.append(bench_row(arch, K, remat, J, reps, dataset,
                                      n_per_client))
    out = {"benchmark": "backbone_rounds", "dataset": dataset, "J": J,
           "regime": "cohort-gather fused rounds via FusedRoundEngine."
                     "from_store, RandomPolicy at fixed J, 1 MHz/client "
                     "bandwidth, eval disabled; one row per (arch, K, "
                     "remat): remat=true checkpoint-wraps each client's "
                     "loss in the cohort vmap (ModelAdapter.cohort_step); "
                     "temp_bytes is XLA's peak temp allocation for the "
                     "compiled round program",
           "per_round": rows}
    base = {(r["arch"], r["K"]): r for r in rows if not r["remat"]}
    for r in rows:
        b = base.get((r["arch"], r["K"]))
        if r["remat"] and b and r["temp_bytes"] and b["temp_bytes"]:
            print(f"{r['arch']:12s} K={r['K']:6d} remat temp ratio: "
                  f"{r['temp_bytes'] / b['temp_bytes']:.2f}x, "
                  f"slowdown {r['ms_per_round'] / b['ms_per_round']:.2f}x",
                  flush=True)
    return out


def main(argv: Optional[List[str]] = None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: K=50 only, 2 reps")
    ap.add_argument("--archs", default="lstm-cnn,transformer,ssd")
    ap.add_argument("--Ks", default=None,
                    help="comma-separated population sizes (default 50,5000)")
    ap.add_argument("--J", type=int, default=10, help="cohort size")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--dataset", default="iemocap")
    ap.add_argument("--n-per-client", type=int, default=2)
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)

    archs = [a for a in args.archs.split(",") if a]
    if args.Ks:
        Ks = [int(k) for k in args.Ks.split(",")]
    elif args.tiny:
        Ks = [50]
    else:
        Ks = [50, 5000]
    out = run_benchmark(archs, Ks, args.J,
                        args.reps or (2 if args.tiny else 5),
                        args.dataset, args.n_per_client)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json_out}")
    return out


if __name__ == "__main__":
    main()
