"""Population-scale fused rounds: per-round latency and working-set memory
vs K ∈ {50, 1k, 10k, 100k} at a fixed cohort J.

The cohort-gather round (fl/fused_round.py) keeps the BGD/aggregation hot
path O(J): the policy emits a static-J cohort index vector, ``round_step``
gathers the cohort's rows from the device-resident ``ClientStore``, and
Eq. 12 / tracker refresh run on [J] stacks (segment-sum scatter back to the
dense [K] rows).  Only O(K) *vector* physics (channel draw, feasibility,
queues) and the O(K·N·d) resident store scale with the population — so
per-round latency and the compiled program's temp working set should stay
nearly flat from K=50 to K=100k while the store grows by 2000x.  This
benchmark commits exactly that evidence:

* ``ms_per_round`` — wall-clock per fused round (compiled ``eng.step``,
  carry chained across reps so every round is a real state update).
* ``temp_bytes`` — XLA's peak temp allocation for the round program
  (``compiled.memory_analysis().temp_size_in_bytes``): the working set,
  excluding the resident store/carry arguments, which are reported
  separately (``arg_bytes``, ``store_mb``).

Populations are built with the vectorized ``data.partition.
synthetic_population`` (the per-client Python staging of ``partition``/
``stack_clients`` is prohibitive at K=100k) and enter the engine through
``FusedRoundEngine.from_store`` — no ``MFLExperiment`` host mirrors.
Wireless cost vectors follow Eqs. 15-18 exactly, vectorized over the
ownership masks; ``B_max`` keeps the paper's per-client bandwidth density
(1 MHz/client, as in benchmarks/fused_round.py) so schedules stay real.

``--mesh-smoke`` instead runs a short ``scan_v_grid`` sweep on the 2-D
("scenario", "clients") mesh — with ``--virtual-devices 4`` this exercises
the client-sharded store + masked-psum cohort gather on any machine (the
flag must be set before jax initializes, so it is handled at main() entry).

  PYTHONPATH=src python -m benchmarks.population_scale                # full
  PYTHONPATH=src python -m benchmarks.population_scale --tiny \
      --json-out BENCH_population_scale.json                          # CI
  PYTHONPATH=src python -m benchmarks.population_scale --mesh-smoke \
      --virtual-devices 4 --K 5000 --rounds 2                         # CI 2-D
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import List, Optional

import numpy as np



def build_population(K: int, n_per_client: int, dataset: str, params,
                     omega: float = 0.2, seed: int = 0):
    """Synthetic ClientStore with Eqs. 15-18 cost vectors, vectorized."""
    # deferred: importing repro pulls in jax, which must not initialize
    # before main() applies --virtual-devices to XLA_FLAGS
    from repro.data.partition import synthetic_population
    from repro.data.scenarios import DATASET_SHAPES
    from repro.wireless.cost import population_costs
    from repro.wireless.params import MODALITY_PROFILES

    shapes, n_classes = DATASET_SHAPES[dataset]
    store = synthetic_population(K, n_per_client, shapes, n_classes, omega,
                                 seed=seed)
    cost = population_costs(store.has_modality, store.modalities,
                            store.sizes, MODALITY_PROFILES[dataset], params)
    return dataclasses.replace(store,
                               gamma_bits=cost.gamma_bits.astype(np.float32),
                               tau_cmp=cost.tau_cmp.astype(np.float32),
                               e_cmp=cost.e_cmp.astype(np.float32))


def _make_engine(K: int, J: int, dataset: str, policy_name: str,
                 n_per_client: int, seed: int):
    from repro.fl.client import PaperModelAdapter
    from repro.fl.fused_round import FusedRoundEngine
    from repro.wireless.params import WirelessParams
    from repro.wireless.policies import JCSBAPolicy, RandomPolicy

    params = WirelessParams(K=K, B_max=1e6 * K, E_add=2e-4)
    store = build_population(K, n_per_client, dataset, params, seed=seed)
    if policy_name == "jcsba":
        policy = JCSBAPolicy(K, max_cohort=J)
    else:
        policy = RandomPolicy(K, J)
    eng = FusedRoundEngine.from_store(store, params,
                                      policy, PaperModelAdapter(dataset),
                                      V=1.0, seed=seed)
    return eng, params, store


def _round_xs(rng, channel, K: int):
    import jax.numpy as jnp
    from repro.fl.fused_round import RoundXs
    return RoundXs(jnp.asarray(channel.draw(), jnp.float32),
                   jnp.uint32(rng.integers(2 ** 31)),
                   jnp.asarray(rng.integers(2 ** 31, size=K,
                                            dtype=np.uint32)),
                   jnp.asarray(False))


# ---------------------------------------------------------------------------
def bench_K(K: int, J: int, reps: int, dataset: str = "iemocap",
            policy: str = "random", n_per_client: int = 2,
            seed: int = 0) -> dict:
    import jax
    from repro.wireless.channel import Channel

    eng, params, store = _make_engine(K, J, dataset, policy, n_per_client,
                                      seed)
    carry = eng.fresh_carry()
    rng = np.random.default_rng(seed + 1)
    channel = Channel(params, rng)
    xs = _round_xs(rng, channel, K)

    carry, _ = jax.block_until_ready(eng.step(carry, xs))   # compile + warmup
    # pregenerate the rounds' randomness (as draw_round_xs / scan would) so
    # the timing is the device program, not numpy's 100k-element draws
    xs_list = [_round_xs(rng, channel, K) for _ in range(reps)]
    t0 = time.perf_counter()
    for xs in xs_list:
        carry, aux = eng.step(carry, xs)
    jax.block_until_ready((carry, aux))
    ms = (time.perf_counter() - t0) / reps * 1e3

    mem = eng.lower(carry, xs, scanned=False).compile(
        ).memory_analysis()
    store_mb = sum(np.asarray(x).nbytes
                   for x in jax.tree.leaves(eng._store)) / 2 ** 20
    row = {"K": K, "J": J, "policy": policy, "dataset": dataset,
           "n_per_client": n_per_client, "reps": reps,
           "ms_per_round": round(ms, 3),
           "scheduled": int(np.asarray(aux.ok).sum()),
           "store_mb": round(store_mb, 2),
           "temp_bytes": None if mem is None else int(mem.temp_size_in_bytes),
           "arg_bytes": None if mem is None
           else int(mem.argument_size_in_bytes),
           "output_bytes": None if mem is None
           else int(mem.output_size_in_bytes)}
    tmp = "n/a" if mem is None else f"{mem.temp_size_in_bytes / 2 ** 20:.1f}"
    print(f"K={K:7d} J={J:3d} {policy:6s} {ms:9.2f} ms/round  "
          f"temp={tmp} MiB  store={store_mb:.1f} MiB", flush=True)
    return row


def run_benchmark(Ks: List[int], J: int, reps: int, dataset: str,
                  policy: str, n_per_client: int) -> dict:
    rows = [bench_K(K, J, reps, dataset, policy, n_per_client) for K in Ks]
    out = {"benchmark": "population_scale", "dataset": dataset, "J": J,
           "policy": policy,
           "regime": "cohort-gather fused rounds via FusedRoundEngine."
                     "from_store on a vectorized synthetic population; "
                     "B_max scaled to 1 MHz/client; eval disabled; "
                     "temp_bytes is XLA's peak temp allocation for the "
                     "compiled round (working set — the resident store is "
                     "arg_bytes/store_mb)",
           "per_round": rows}
    lat = {r["K"]: r["ms_per_round"] for r in rows}
    if len(Ks) > 1:
        ratio = lat[Ks[-1]] / lat[Ks[0]]
        out["latency_ratio_max_vs_min_K"] = round(ratio, 2)
        print(f"K={Ks[-1]} vs K={Ks[0]} per-round latency: {ratio:.2f}x "
              f"(population {Ks[-1] / Ks[0]:.0f}x larger)", flush=True)
    return out


# ---------------------------------------------------------------------------
def mesh_smoke(K: int, J: int, rounds: int, dataset: str, policy: str,
               n_per_client: int, seed: int = 0) -> dict:
    """One short V sweep on the 2-D ("scenario", "clients") mesh: the
    client-sharded store + masked-psum cohort gather end to end."""
    import jax
    from repro.fl.fused_round import RoundXs
    from repro.launch.mesh import make_population_mesh
    from repro.wireless.channel import Channel
    import jax.numpy as jnp

    n_dev = jax.device_count()
    eng, params, store = _make_engine(K, J, dataset, policy, n_per_client,
                                      seed)
    carry = eng.fresh_carry()
    rng = np.random.default_rng(seed + 1)
    channel = Channel(params, rng)
    per = [_round_xs(rng, channel, K) for _ in range(rounds)]
    xs = RoundXs(*(jnp.stack(x) for x in zip(*per)))
    V = [0.1, 1.0]

    mesh = make_population_mesh() if n_dev > 1 else None
    t0 = time.perf_counter()
    carries, auxs = jax.block_until_ready(
        eng.scan_v_grid(V, carry, xs, mesh=mesh))
    wall = time.perf_counter() - t0
    row = {"benchmark": "population_scale/mesh_smoke", "K": K, "J": J,
           "rounds": rounds, "policy": policy, "devices": n_dev,
           "mesh": None if mesh is None
           else {ax: int(n) for ax, n in mesh.shape.items()},
           "n_V": len(V), "wall_s": round(wall, 3),
           "scheduled_per_round": round(
               float(np.asarray(auxs.ok).sum(-1).mean()), 2)}
    print(f"mesh_smoke K={K} J={J} devices={n_dev} mesh={row['mesh']}: "
          f"{len(V)}x{rounds} rounds in {wall:.2f}s, "
          f"{row['scheduled_per_round']} scheduled/round", flush=True)
    assert row["scheduled_per_round"] > 0, "smoke scheduled nobody"
    return row


# ---------------------------------------------------------------------------
def main(argv: Optional[List[str]] = None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: K in {50, 500}, 2 reps")
    ap.add_argument("--Ks", default=None,
                    help="comma-separated population sizes "
                         "(default 50,1000,10000,100000)")
    ap.add_argument("--K", type=int, default=5000,
                    help="population size for --mesh-smoke")
    ap.add_argument("--J", type=int, default=10, help="cohort size")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--rounds", type=int, default=2,
                    help="rounds per scenario for --mesh-smoke")
    ap.add_argument("--dataset", default="iemocap")
    ap.add_argument("--policy", default="random",
                    choices=["random", "jcsba"],
                    help="random guarantees exactly J scheduled; jcsba "
                         "caps its cohort vector at J (max_cohort)")
    ap.add_argument("--n-per-client", type=int, default=2)
    ap.add_argument("--mesh-smoke", action="store_true",
                    help="run the 2-D mesh sweep smoke instead of the "
                         "latency/memory scaling table")
    ap.add_argument("--virtual-devices", type=int, default=None,
                    help="XLA_FLAGS host-device override (set before jax "
                         "initializes; lets the 2-D mesh run on one CPU)")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)

    if args.virtual_devices:
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{args.virtual_devices}").strip()

    if args.mesh_smoke:
        out = mesh_smoke(args.K, args.J, args.rounds, args.dataset,
                         args.policy, args.n_per_client)
    else:
        if args.Ks:
            Ks = [int(k) for k in args.Ks.split(",")]
        elif args.tiny:
            Ks = [50, 500]
        else:
            Ks = [50, 1000, 10000, 100000]
        out = run_benchmark(Ks, args.J, args.reps or (2 if args.tiny else 5),
                            args.dataset, args.policy, args.n_per_client)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json_out}")
    return out


if __name__ == "__main__":
    main()
