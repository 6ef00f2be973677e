"""Benchmark harness — one benchmark per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows (one per benchmark).

  PYTHONPATH=src python -m benchmarks.run            # quick mode
  PYTHONPATH=src python -m benchmarks.run --full     # full repro runs

Benchmarks:
  table3_*            — final multimodal/unimodal accuracy per algorithm
                        (paper Table 3; reads benchmarks/results/repro if the
                        full experiment ran, else runs a short version)
  v_frontier_*        — Fig.-4/Table-3 V-frontier: dense V grid, whole fused
                        experiments per (policy, V) — JCSBA + all four traced
                        baselines incl. dropout — sharded over the local
                        devices, with device-resident multimodal + unimodal
                        accuracy curves per point (``--v-frontier`` runs only
                        this and writes BENCH_v_frontier.json; see
                        benchmarks/v_frontier.py)
  solver_runtime      — JCSBA per-round solve time (paper §VI: 0.008 s)
  bound_descent       — Theorem-2 bound vs measured loss descent
  kernel_*            — Pallas kernel oracles (interpret) + XLA-path timing
  roofline_rows       — #(arch x shape) rows with all three terms present
  batched_rounds_*    — round engine throughput, sequential vs batched vmap
                        (``--tiny`` shrinks it to the CI smoke config: K=4,
                        2 rounds, both paths; ``--json-out`` dumps all rows
                        plus the raw benchmark payloads as JSON)
  jcsba_solver_*      — JCSBA per-round solve time, sequential numpy vs the
                        fused jitted population solver, plus the vmapped
                        scenario-grid sweep (see benchmarks/jcsba_solver.py)
  fused_round_*       — full MFL round wall-clock: split pipeline (solver jit
                        + host hop + client jit) vs the fused one-program
                        round, stepwise and under lax.scan, plus the
                        whole-experiment V-grid sweep
                        (see benchmarks/fused_round.py)
  fusion_kernel_*     — custom-VJP Pallas fusion loss on the cohort BGD hot
                        path: fused rounds XLA vs kernel-backed loss across
                        J and samples/client, raw loss value_and_grad, and
                        the Gram-form ζ/δ tracker refresh vs the
                        direct-difference path
                        (see benchmarks/fusion_kernel.py)
  backbone_rounds_*   — fused-round throughput + peak temp memory per model
                        family (lstm-cnn / transformer / ssd) with remat on
                        and off (see benchmarks/backbone_rounds.py)
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from typing import Optional

import numpy as np

from repro.launch.compile_cache import enable_compile_cache

ROWS = []
PAYLOADS = {}          # raw per-benchmark result dicts, for --json-out
TINY = False


def emit(name: str, us_per_call: Optional[float], derived: str = ""):
    """One CSV row; ``us_per_call=None`` leaves the timing column empty
    (error rows carry no timing)."""
    ROWS.append((name, us_per_call, derived))
    us = "" if us_per_call is None else f"{us_per_call:.1f}"
    print(f"{name},{us},{derived}", flush=True)


def _time(fn, n=3, warmup=1):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


# ---------------------------------------------------------------------------
def bench_table3(quick: bool):
    from benchmarks.experiments import aggregate_table3, run_one
    table = aggregate_table3()
    if not table:
        for ds in (["crema_d"] if quick else ["crema_d", "iemocap"]):
            for algo in ["random", "jcsba"]:
                run_one(ds, algo, 0, rounds=20 if quick else 100,
                        n_samples=400 if quick else 800)
        table = aggregate_table3()
    for key, vals in sorted(table.items()):
        mods = [k for k in vals if k not in ("multimodal", "energy_total")]
        derived = (f"mm={vals.get('multimodal', 0):.4f};"
                   + ";".join(f"{m}={vals[m]:.4f}" for m in sorted(mods))
                   + f";E={vals.get('energy_total', 0):.3f}J")
        emit(f"table3_{key.replace('/', '_')}", 0.0, derived)


def bench_v_frontier(quick: bool):
    """Fig.-4 / Table-3 V-frontier via the sharded fused V-grid scan: dense
    V grid, whole experiments per (policy, V) for JCSBA + all four traced
    baselines (dropout included), with device-resident accuracy *curves* at
    the eval_every cadence — zero host eval calls inside the scan."""
    from benchmarks.v_frontier import check_curves, run_frontier
    if TINY:
        out = run_frontier(("jcsba", "random", "dropout"),
                           V_grid=[0.01, 0.1, 1.0, 10.0],
                           K=6, rounds=4, n_samples=120, eval_every=2)
    elif quick:
        out = run_frontier(("jcsba", "random", "dropout"),
                           V_grid=[0.001, 0.01, 0.1, 1.0, 10.0, 100.0],
                           rounds=16, eval_every=4)
    else:
        out = run_frontier()                # all five policies, dense grid
    check_curves(out)
    PAYLOADS["v_frontier"] = out
    for pol, rows in out["policies"].items():
        for r in rows:
            mods = [k for k in r if k not in
                    ("V", "multimodal", "loss", "energy_J",
                     "mean_participants", "curve")]
            emit(f"v_frontier_{pol}_V={r['V']:g}", 0.0,
                 f"mm={r['multimodal']:.4f};"
                 + ";".join(f"{m}={r[m]:.4f}" for m in sorted(mods))
                 + f";E={r['energy_J']:.4f}J;part={r['mean_participants']};"
                 f"curve_pts={len(r['curve']['round'])}")


def bench_scenario_zoo(quick: bool):
    """Scenario zoo: one sharded scan_scenario_grid over a grid mixing
    split laws (iid / dirichlet-α / natural groups), per-modality ω_m
    vectors and corruption models, each row evaluated on its own held-out
    split inside the scan (see benchmarks/scenario_zoo.py)."""
    from benchmarks.scenario_zoo import (check_curves, default_zoo, run_zoo,
                                         tiny_zoo)
    if TINY:
        out = run_zoo(tiny_zoo(), rounds=4, eval_every=2)
    elif quick:
        out = run_zoo(default_zoo(K=8, n_per_client=4, n_test=64),
                      rounds=12, eval_every=4)
    else:
        out = run_zoo(default_zoo(K=10, n_per_client=8, n_test=128))
    check_curves(out)
    PAYLOADS["scenario_zoo"] = out
    for r in out["scenarios"]:
        emit(f"scenario_zoo_{r['name']}", 0.0,
             f"mm={r['multimodal']:.4f};E={r['energy_J']:.4f}J;"
             f"part={r['mean_participants']};"
             f"curve_pts={len(r['curve']['round'])}")


def bench_solver_runtime(quick: bool):
    from repro.core.aggregation import unified_weights
    from repro.core.convergence import BoundState
    from repro.wireless import cost as wcost
    from repro.wireless.channel import Channel
    from repro.wireless.params import MODALITY_PROFILES, WirelessParams
    from repro.wireless.schedulers import ScheduleContext, make_scheduler
    P = WirelessParams()
    rng = np.random.default_rng(0)
    mods = [("audio", "image"), ("audio",), ("image",)] * 3 + \
        [("audio", "image")]
    sizes = [80] * 10
    cc = wcost.client_costs(sizes, mods, MODALITY_PROFILES["crema_d"], P)
    ch = Channel(P, rng)
    w = unified_weights(sizes, mods, ["audio", "image"])
    bound = BoundState(10, ["audio", "image"], mods, w, sizes)
    sched = make_scheduler("jcsba", rng)
    h = ch.draw()

    def solve():
        ctx = ScheduleContext(h=h, Q=rng.uniform(0, 0.01, 10), cost=cc,
                              params=P, bound=bound, round_idx=0,
                              model_dist=np.zeros(10),
                              client_modalities=mods)
        sched.schedule(ctx)

    us = _time(solve, n=3 if quick else 10)
    emit("solver_runtime", us,
         f"per_round={us / 1e6:.4f}s;paper=0.008s;tau_max=0.01s")


def bench_bound(quick: bool):
    """Theorem 2: measured per-round descent statistics under JCSBA."""
    from repro.fl.runtime import MFLExperiment
    exp = MFLExperiment(dataset="crema_d", scheduler="jcsba", n_samples=400,
                        seed=0, eval_every=1)
    exp.run(30 if quick else 80)
    losses = [r.metrics["loss"] for r in exp.history if r.metrics]
    descents = np.diff(losses)
    frac_descent = float((descents <= 0).mean())
    emit("bound_descent", 0.0,
         f"frac_rounds_descending={frac_descent:.2f};"
         f"total_drop={losses[0] - np.mean(losses[-3:]):.4f}")


def bench_kernels(quick: bool):
    import jax
    import jax.numpy as jnp
    from repro.kernels.fusion_loss.ref import fusion_loss_ref
    from repro.models.layers import chunked_attention
    from repro.models.mamba2 import ssd_chunked
    rng = np.random.default_rng(0)

    M, T, V = 2, 512, 32768
    logits = jnp.asarray(rng.normal(size=(M, T, V)), jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, V, T), jnp.int32)
    avail = jnp.ones((M, T), jnp.float32)
    f = jax.jit(fusion_loss_ref)
    us = _time(lambda: jax.block_until_ready(f(logits, labels, avail)))
    emit("kernel_fusion_loss_xla_ref", us, f"M={M};T={T};V={V}")

    B, S, H, K, hd = 1, 1024, 8, 4, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, K, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, K, hd)), jnp.bfloat16)
    f2 = jax.jit(lambda q, k, v: chunked_attention(q, k, v, window=None,
                                                   chunk=256))
    us = _time(lambda: jax.block_until_ready(f2(q, k, v)))
    emit("kernel_flash_attention_xla_ref", us, f"S={S};H={H}")

    Bz, S2, nh, hp, N = 1, 2048, 8, 64, 64
    x = jnp.asarray(rng.normal(size=(Bz, S2, nh, hp)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(Bz, S2, nh))) * 0.1 + 0.01,
                     jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(size=nh)) - 0.1, jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(Bz, S2, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(Bz, S2, N)), jnp.float32)
    f3 = jax.jit(lambda *a: ssd_chunked(*a, chunk=256))
    us = _time(lambda: jax.block_until_ready(f3(x, dt, A, Bm, Cm)))
    emit("kernel_ssd_scan_xla_ref", us, f"S={S2};nh={nh}")


def bench_roofline(quick: bool):
    from benchmarks.roofline import table
    rows = table("16x16")
    emit("roofline_rows_16x16", 0.0, f"n={len(rows)}")
    rows2 = table("2x16x16")
    if rows2:
        emit("roofline_rows_2x16x16", 0.0, f"n={len(rows2)}")
    by_dom = {}
    for r in rows:
        by_dom[r["dominant"]] = by_dom.get(r["dominant"], 0) + 1
    emit("roofline_dominant_hist", 0.0,
         ";".join(f"{k}={v}" for k, v in sorted(by_dom.items())))


def bench_jcsba_solver(quick: bool):
    from benchmarks.jcsba_solver import run_benchmark
    if TINY:
        out = run_benchmark([6], rounds=2, sweep_rounds=2,
                            tau_grid=[0.01, 0.02], bmax_grid=[10e6],
                            datasets=["iemocap"])
    elif quick:
        out = run_benchmark([10, 50], rounds=3, sweep_rounds=5,
                            tau_grid=[0.01, 0.02], bmax_grid=[5e6, 10e6],
                            datasets=["crema_d"])
    else:
        out = run_benchmark([10, 50], rounds=5, sweep_rounds=10,
                            tau_grid=[0.005, 0.01, 0.02, 0.05],
                            bmax_grid=[5e6, 10e6, 20e6],
                            datasets=["crema_d", "iemocap"])
    PAYLOADS["jcsba_solver"] = out
    for r in out["per_round"]:
        emit(f"jcsba_solver_K={r['K']}_{r['solver']}",
             r["ms_per_round"] * 1e3,
             f"speedup_vs_seq={r['speedup_vs_seq']}x")
    for r in out["sweep"]:
        emit(f"jcsba_solver_sweep_K={r['K']}",
             r["wall_s"] / r["total_solves"] * 1e6,
             f"solves_per_sec={r['solves_per_sec']};"
             f"n_scenarios={r['n_scenarios']};rounds={r['rounds']}")


def bench_fused_round(quick: bool):
    from benchmarks.fused_round import run_benchmark
    if TINY:
        out = run_benchmark([4], rounds=2, sweep_rounds=2,
                            V_grid=[0.1, 1.0, 10.0])
    elif quick:
        out = run_benchmark([10, 50], rounds=3, sweep_rounds=5,
                            V_grid=[0.01, 0.1, 1.0, 10.0])
    else:
        out = run_benchmark([10, 50], rounds=5, sweep_rounds=10,
                            V_grid=[0.001, 0.01, 0.1, 0.5, 1.0, 2.0, 5.0,
                                    10.0])
    PAYLOADS["fused_round"] = out
    for r in out["per_round"]:
        emit(f"fused_round_{r['dataset']}_K={r['K']}_{r['engine']}",
             r["ms_per_round"] * 1e3,
             f"speedup_vs_split={r['speedup_vs_split']}x")
    s = out["v_sweep"]
    emit(f"fused_round_vsweep_K={s['K']}",
         s["wall_s"] / s["total_fused_rounds"] * 1e6,
         f"rounds_per_sec={s['rounds_per_sec']};n_V={len(s['V_grid'])};"
         f"rounds={s['rounds']}")


def bench_fusion_kernel(quick: bool):
    from benchmarks.fusion_kernel import run_benchmark
    if TINY:
        out = run_benchmark([4], spc_grid=[2.0], rounds=2,
                            raw_shape=(2, 64, 512), raw_blocks=(32, 256),
                            tracker_J=4, tracker_leaves=((32, 16), (16,)))
    elif quick:
        out = run_benchmark([6], spc_grid=[2.0], rounds=2,
                            raw_shape=(2, 256, 4096),
                            raw_blocks=(128, 2048))
    else:
        out = run_benchmark([6, 10], spc_grid=[2.0, 8.0], rounds=3)
    PAYLOADS["fusion_kernel"] = out
    for r in out["per_round"]:
        emit(f"fusion_kernel_round_K={r['K']}_spc={r['samples_per_client']:g}",
             1e6 / r["pallas_rounds_per_sec"],
             f"xla_rps={r['xla_rounds_per_sec']};"
             f"pallas_rps={r['pallas_rounds_per_sec']};"
             f"ratio={r['pallas_vs_xla']}x")
    raw = out["raw_loss"]
    emit("fusion_kernel_raw_loss", raw["pallas_ms"] * 1e3,
         f"xla_ms={raw['xla_ms']};pallas_ms={raw['pallas_ms']};"
         f"backend={raw['backend']}")
    t = out["tracker"]
    emit("fusion_kernel_tracker", t["gram_ms"] * 1e3,
         f"diff_ms={t['diff_ms']};gram_ms={t['gram_ms']};"
         f"speedup={t['gram_vs_diff']}x;drift={t['max_drift']:.2e}")


def bench_batched_rounds(quick: bool):
    from benchmarks.batched_rounds import run_benchmark
    if TINY:
        out = run_benchmark([4], rounds=2, datasets=["iemocap"])
    elif quick:
        out = run_benchmark([10, 50], rounds=3, datasets=["iemocap"])
    else:
        out = run_benchmark([10, 50, 200], rounds=5)
    PAYLOADS["batched_rounds"] = out
    for r in out["results"]:
        emit(f"batched_rounds_{r['dataset']}_K={r['K']}",
             1e6 / r["batched_rounds_per_sec"],
             f"seq_rps={r['seq_rounds_per_sec']};"
             f"batched_rps={r['batched_rounds_per_sec']};"
             f"speedup={r['speedup']}x")


def bench_backbone_rounds(quick: bool):
    from benchmarks.backbone_rounds import run_benchmark
    if TINY:
        out = run_benchmark(["lstm-cnn", "transformer", "ssd"], [50],
                            J=10, reps=2, dataset="iemocap", n_per_client=2)
    elif quick:
        out = run_benchmark(["lstm-cnn", "transformer", "ssd"], [50],
                            J=10, reps=3, dataset="iemocap", n_per_client=2)
    else:
        out = run_benchmark(["lstm-cnn", "transformer", "ssd"], [50, 5000],
                            J=10, reps=5, dataset="iemocap", n_per_client=2)
    PAYLOADS["backbone_rounds"] = out
    for r in out["per_round"]:
        emit(f"backbone_rounds_{r['arch']}_K={r['K']}_remat={int(r['remat'])}",
             r["ms_per_round"] * 1e3,
             f"rounds_per_s={r['rounds_per_s']};temp_bytes={r['temp_bytes']}")


def bench_serving(quick: bool):
    from benchmarks.serving import run_benchmark
    out = run_benchmark(tiny=TINY or quick)
    PAYLOADS["serving"] = out
    for r in out["prefill"]:
        emit(f"serving_prefill_{r['arch']}_S={r['prompt_len']}",
             r["bulk_ms"] * 1e3,
             f"teacher_forced_ms={r['teacher_forced_ms']};"
             f"bulk_ms={r['bulk_ms']};speedup={r['speedup']}x")
    s = out["steady_state"]
    emit(f"serving_steady_{s['arch']}_B={s['batch']}",
         s["decode"]["mean_ms"] * 1e3,
         f"tok_per_s={s['tokens_per_s']};p99_ms={s['decode']['p99_ms']}")
    c = out["continuous"]
    emit(f"serving_continuous_{c['arch']}",
         c["post_swap_decode"]["p99_ms"] * 1e3,
         f"tok_per_s={c['tokens_per_s']};"
         f"swap_spike_p99_ms={c['swap_spike_p99_ms']};"
         f"swap_ms={c['swap_wall']['mean_ms']};"
         f"recompiles={c['recompiles_post_warmup']}")


# ---------------------------------------------------------------------------
def main() -> None:
    global TINY
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke mode (shrinks supporting benches)")
    ap.add_argument("--v-frontier", action="store_true",
                    help="run only the Fig.-4 V-frontier (sharded fused "
                         "V-grid scan with eval metrics) and write "
                         "BENCH_v_frontier.json")
    ap.add_argument("--json-out", default=None,
                    help="dump emitted rows + raw payloads as JSON")
    args, _ = ap.parse_known_args()
    enable_compile_cache()
    TINY = args.tiny
    quick = not args.full
    benches = {
        "table3": bench_table3,
        "v_frontier": bench_v_frontier,
        "scenario_zoo": bench_scenario_zoo,
        "solver_runtime": bench_solver_runtime,
        "bound": bench_bound,
        "kernels": bench_kernels,
        "roofline": bench_roofline,
        "batched_rounds": bench_batched_rounds,
        "jcsba_solver": bench_jcsba_solver,
        "fused_round": bench_fused_round,
        "fusion_kernel": bench_fusion_kernel,
        "backbone_rounds": bench_backbone_rounds,
        "serving": bench_serving,
    }
    if args.v_frontier:
        args.only = "v_frontier"
    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        try:
            fn(quick)
        except Exception as e:  # record it, run the other benches, exit 1
            traceback.print_exc()
            emit(f"{name}_ERROR", None, f"{type(e).__name__}:{e}")
            failed.append(name)
    if args.v_frontier and "v_frontier" in PAYLOADS:
        with open("BENCH_v_frontier.json", "w") as f:
            json.dump(PAYLOADS["v_frontier"], f, indent=2)
        print("wrote BENCH_v_frontier.json", flush=True)
    if args.json_out:
        payload = {"rows": [{"name": n, "us_per_call": u, "derived": d}
                            for n, u, d in ROWS],
                   "payloads": PAYLOADS}
        with open(args.json_out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json_out}", flush=True)
    if failed:
        sys.exit(f"benches raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
