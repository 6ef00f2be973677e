#!/usr/bin/env python3
"""Smoke run of the fused wireless-MFL trainer on a TPU.

    python chip_smoke.py               # phases (a)-(c) on one chip
    python chip_smoke.py --four-chips  # only the 2-D sharded V sweep, 4 chips

Phases, each printed as one JSON line before the final result line:

(a) ``paper``: the paper regime — CREMA-D, the LSTM-50 audio and 3x16-conv
    CNN image submodels at their published widths, K=10 clients, JCSBA,
    7442 samples (the corpus size) — through ``MFLExperiment(engine="fused")``
    and ``run_scanned``, against the host ``engine="batched"`` twin.
(b) ``paper_pallas``: the same experiment with the Pallas fusion loss
    (``engine="fused:pallas"``) against (a), with the kernel compiled into
    the round program (``tpu_custom_call`` in its compiled text).
(c) ``transformer_pallas`` / ``ssd_pallas``: the backbone encoders with the
    flash_attention / ssd_scan kernels against their ``engine="fused"`` twins,
    at 3000 samples (``BACKBONE``).

Every comparison runs under ``jax.default_matmul_precision("highest")``.
Any failed check raises, and the script exits non-zero.  Without a TPU it
exits non-zero before running anything and prints no result line.  The last
line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

#: the paper regime: CREMA-D at its corpus size, K=10 clients, JCSBA
PAPER = dict(dataset="crema_d", scheduler="jcsba", K=10, n_samples=7442)
#: the backbone phases' deployment: the same cell at 3000 samples.  At 7442
#: the XLA twin of the ssd round program needs 20.7 GB of temporaries
#: (compiled for a v5e), more than the chip's 16 GB; at 3000 it needs 8.3 GB
BACKBONE = dict(PAPER, n_samples=3000)
#: scanned rounds per timed call; each experiment runs two such calls —
#: the first compiles, the second is the steady-state timing
ROUNDS = 3
#: largest |Δ| allowed between two engines' params after the run.  Both
#: sides compute in f32 at "highest" matmul precision; they differ only in
#: reduction order and fusion, which stays orders of magnitude below this.
PARAM_TOL = 1e-3
#: largest |Δ| allowed between two engines' test metrics (accuracy is a
#: fraction of the 1489-sample held-out split; loss is in nats)
METRIC_TOL = 1e-2
#: drift penalties of the four-chip sweep, one per scenario shard.  The
#: one-device reference vmaps the whole grid: at two V its round program
#: needs 11.4 GB of temporaries (compiled for a v5e), at four it would not
#: fit the chip's 16 GB
V_GRID = (0.1, 10.0)


class SmokeFailure(RuntimeError):
    """A phase's output disagreed with its reference."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _max_diff(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _check_finite(exp, name: str) -> None:
    for rec in exp.history:
        _check(all(math.isfinite(v) for v in rec.metrics.values()),
               f"{name}: non-finite metrics in round {rec.round}: "
               f"{rec.metrics}")
    _check(all(bool(np.all(np.isfinite(np.asarray(x))))
               for x in jax.tree.leaves(exp.global_params)),
           f"{name}: non-finite params")


def _compare(name: str, exp, ref) -> float:
    """Same participants every round, same metrics within METRIC_TOL,
    params within PARAM_TOL; returns the largest param difference."""
    parts = [r.participants for r in exp.history]
    _check(parts == [r.participants for r in ref.history],
           f"{name}: participant sets differ from the reference")
    for r, q in zip(exp.history, ref.history):
        _check(r.metrics.keys() == q.metrics.keys(),
               f"{name}: round {r.round} metric keys differ")
        for k, v in r.metrics.items():
            _check(abs(v - q.metrics[k]) <= METRIC_TOL,
                   f"{name}: round {r.round} {k} {v} vs {q.metrics[k]}")
    diff = _max_diff(exp.global_params, ref.global_params)
    _check(diff <= PARAM_TOL,
           f"{name}: params differ by {diff} > {PARAM_TOL}")
    return diff


def run_fused(engine: str, *, rounds: int = ROUNDS, arch: str = "lstm-cnn",
              **cfg):
    """One fused experiment driven by two ``run_scanned(rounds)`` calls.
    Returns (experiment, compile seconds, steady seconds per round)."""
    from repro.fl.runtime import MFLExperiment
    exp = MFLExperiment(engine=engine, arch=arch, **cfg)
    t0 = time.perf_counter()
    exp.run_scanned(rounds)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    exp.run_scanned(rounds)
    steady = time.perf_counter() - t0
    return exp, max(first - steady, 0.0), steady / rounds


def compiled_text(exp, rounds: int) -> str:
    """Compiled text of the experiment's ``rounds``-round scan program (a
    compile-cache hit after ``run_fused``).  Drawing the inputs advances
    the experiment's host randomness, so call it after the runs."""
    from repro.fl.fused_round import draw_round_xs
    eng = exp._get_fused_engine()
    return eng.lower(exp._carry, draw_round_xs(exp, rounds)).compile(
        ).as_text()


def _report(phase: str, exp, compile_s: float, steady_s: float, diff: float,
            text: str, **extra) -> dict:
    row = {"phase": phase, "engine": exp.engine, "arch": exp.arch,
           "compile_s": compile_s, "steady_s_per_round": steady_s,
           "final_metrics": exp.history[-1].metrics, "max_param_diff": diff,
           "tpu_custom_call": "tpu_custom_call" in text, **extra}
    print(json.dumps(row), flush=True)
    return row


def phase_paper(cfg: dict, *, rounds: int = ROUNDS):
    """(a) the fused paper regime against the host batched twin, run for
    as many rounds as ``run_fused``'s two calls."""
    from repro.fl.runtime import MFLExperiment
    with jax.default_matmul_precision("highest"):
        exp, c, s = run_fused("fused", rounds=rounds, **cfg)
        text = compiled_text(exp, rounds)
        ref = MFLExperiment(engine="batched", **cfg)
        ref.run(2 * rounds)
    _check_finite(exp, "paper")
    _check_finite(ref, "paper/batched")
    diff = _compare("paper", exp, ref)
    return exp, _report("paper", exp, c, s, diff, text,
                        reference="batched")


def phase_paper_pallas(cfg: dict, ref, *, interpret: bool = False,
                       rounds: int = ROUNDS) -> dict:
    """(b) the Pallas fusion loss against (a)'s fused experiment ``ref``.
    ``interpret`` says whether kernels run interpreted here (never on a
    TPU), i.e. whether the compiled text must lack or hold the kernel."""
    with jax.default_matmul_precision("highest"):
        exp, c, s = run_fused("fused:pallas", rounds=rounds, **cfg)
        text = compiled_text(exp, rounds)
    _check_finite(exp, "paper_pallas")
    _check(("tpu_custom_call" in text) != interpret,
           f"paper_pallas: tpu_custom_call present={not interpret} "
           f"expected in the compiled round program")
    diff = _compare("paper_pallas", exp, ref)
    return _report("paper_pallas", exp, c, s, diff, text, reference="paper")


def phase_backbone(arch: str, cfg: dict, *, interpret: bool = False,
                   rounds: int = 1) -> dict:
    """(c) one backbone encoder with its mixer kernel against the
    ``engine="fused"`` twin."""
    name = f"{arch}_pallas"
    with jax.default_matmul_precision("highest"):
        exp, c, s = run_fused("fused:pallas", rounds=rounds, arch=arch,
                              **cfg)
        text = compiled_text(exp, rounds)
        ref, *_ = run_fused("fused", rounds=rounds, arch=arch, **cfg)
    _check_finite(exp, name)
    _check(("tpu_custom_call" in text) != interpret,
           f"{name}: tpu_custom_call present={not interpret} expected in "
           f"the compiled round program")
    diff = _compare(name, exp, ref)
    return _report(name, exp, c, s, diff, text, reference="fused")


def phase_four_chips(cfg: dict, *, rounds: int = 2,
                     n_scenario: int = 2, n_clients: int = 2) -> dict:
    """The sharded sweep alone: ``scan_v_grid`` on a 2-D
    ``("scenario", "clients")`` mesh against the same grid on one device.
    Prints which device holds which client slice of the store and of the
    per-client randomness, as the compiled sweep places them."""
    from repro.fl.fused_round import draw_round_xs
    from repro.fl.runtime import MFLExperiment
    from repro.launch.mesh import make_population_mesh

    mesh = make_population_mesh(n_scenario, n_clients)
    _check(mesh is not None, "four_chips: no multi-device mesh")
    exp = MFLExperiment(engine="fused", **cfg)
    eng = exp._get_fused_engine()
    carry = eng.init_carry()
    xs = draw_round_xs(exp, rounds)
    with jax.default_matmul_precision("highest"):
        lowered = eng.lower_v_grid(V_GRID, carry, xs, mesh)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        sharded = jax.block_until_ready(
            eng.scan_v_grid(V_GRID, carry, xs, mesh=mesh))
        t0 = time.perf_counter()
        sharded = jax.block_until_ready(
            eng.scan_v_grid(V_GRID, carry, xs, mesh=mesh))
        steady = time.perf_counter() - t0
        single = jax.block_until_ready(
            eng.scan_v_grid(V_GRID, carry, xs, mesh=None))

    # the sweep's arguments: (V, carry, xs, store, test_set)
    _, _, xs_info, store_info, _ = lowered.args_info[0]
    _, _, xs_sh, store_sh, _ = compiled.input_shardings[0]
    leaf = sorted(store_info.features)[0]
    placement = {
        f"store.features.{leaf}": _placement(
            store_sh.features[leaf], store_info.features[leaf].shape, 0),
        "xs.client_seeds": _placement(
            xs_sh.client_seeds, xs_info.client_seeds.shape, 1),
    }
    for name, where in placement.items():
        print(json.dumps({"shards": name, "placement": where}), flush=True)
        _check(len({d for d, _ in where}) == mesh.devices.size
               and len({s for _, s in where}) == n_clients,
               f"four_chips: {name} is not split over the clients axis of "
               f"every device")

    (c_sh, a_sh), (c_1, a_1) = sharded, single
    _check(np.array_equal(np.asarray(a_sh.ok), np.asarray(a_1.ok)),
           "four_chips: participant sets differ from one device")
    diff = _max_diff(c_sh.params, c_1.params)
    _check(diff <= PARAM_TOL, f"four_chips: params differ by {diff}")
    metric_diff = _max_diff(a_sh.metrics, a_1.metrics)
    _check(metric_diff <= METRIC_TOL,
           f"four_chips: metrics differ by {metric_diff}")
    row = {"phase": "four_chips", "mesh": dict(mesh.shape),
           "mesh_devices": [str(d) for d in mesh.devices.flat],
           "V_grid": list(V_GRID), "rounds": rounds, "compile_s": compile_s,
           "steady_s_per_sweep": steady, "max_param_diff": diff,
           "max_metric_diff": metric_diff}
    print(json.dumps(row), flush=True)
    return row


def _placement(sharding, shape, axis: int):
    """[(device, client slice)] for every device holding a shard;
    ``axis`` is the array's client axis."""
    out = []
    for dev, idx in sorted(sharding.devices_indices_map(shape).items(),
                           key=lambda kv: kv[0].id):
        sl = idx[axis]
        out.append((str(dev), f"{sl.start or 0}:{sl.stop or shape[axis]}"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded V sweep on four chips")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend {backend!r})",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    devices = jax.devices()
    if args.four_chips:
        _check(len(devices) == 4,
               f"--four-chips needs 4 devices, found {len(devices)}")
        phase_four_chips(PAPER)
    else:
        paper, _ = phase_paper(PAPER)
        phase_paper_pallas(PAPER, paper)
        for arch in ("transformer", "ssd"):
            phase_backbone(arch, BACKBONE)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
