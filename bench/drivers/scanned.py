"""Driver ``scanned``: whole rounds under one ``lax.scan`` per call.

The entry the window drives is ``MFLExperiment(engine="fused").run_scanned(R)``
with the mix's fixed R.  Every call pregenerates its rounds' randomness on
the host, runs the one round program, waits for it (``block_until_ready``
inside the program's own ``FusedRoundEngine.run``), decodes the records and
exports the carry.

Set-up is everything up to the first timed call: imports, the corpus, the
partition and the weights from the seed, and one warm-up call with the same
R, which compiles the cell's program.  That warm-up call is the run's first
R rounds; its per-round outputs and the state after it are what the
correctness check compares with the reference.

The window replays the warm-up call's R rounds from the seed's start state,
call after call, until ``seconds`` have passed: before each call the carry
on the device, the round index, the host generator's state and the history
are put back as they were before the warm-up (``replay``).  So every call
times the rounds the check compared, with the same work on every seed and
on both sides of a comparison, and no call reaches a later stretch of the
seed's trajectory, where the schedule may run empty.  A replayed round whose
schedule or participants differ from the warm-up call's, or whose metrics
are not finite, counts as failed.  The round program donates no buffer, so
the kept carry stays valid.  The engine's ``trace_count`` and JAX's compile
events must not move inside the window.

With ``trace`` the window is ``trace_calls`` replayed calls under the
profiler, each inside a ``run_scanned`` span, the restore before it and the
bookkeeping after it inside ``harness`` spans.
"""
from __future__ import annotations

import copy
import gc
import math
import shutil
import time
from pathlib import Path

import jax
import numpy as np

from bench import trace as T
from bench.reference.fl import Record
from bench.reference.inputs import placement

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts the backend compiles JAX reports while it is listening."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_a, **_k):
        if name == COMPILE_EVENT:
            self.n += 1


def build(cfg: dict, traffic: dict, seed: int):
    """The experiment from the seed, with the configuration's cell
    placement in place of the distances it draws itself."""
    from repro.fl.runtime import MFLExperiment
    exp = MFLExperiment(
        dataset=cfg["dataset"], arch=cfg["arch"], K=cfg["K"],
        omega=cfg["omega"], n_samples=cfg["n_samples"], eta=cfg["eta"],
        V=cfg["V"], seed=seed, engine=cfg["engine"],
        scheduler=traffic["scheduler"],
        scheduler_kwargs=traffic.get("scheduler_kwargs") or None,
        eval_every=traffic["eval_every"])
    exp.channel.dist_m = placement(cfg, seed)
    return exp


def _host(tree):
    return jax.tree.map(lambda x: np.array(x), tree)


def first_call(exp, rounds: int) -> Record:
    """The warm-up call, with the per-round outputs the records leave out
    (the objective and the Eq. 12 weights) kept as the engine returns them."""
    eng = exp._get_fused_engine()
    params0 = _host(exp.global_params)
    seen = []
    run = eng.run

    def keep(carry, xs, scanned):
        out = run(carry, xs, scanned)
        seen.append(out[1])
        return out

    eng.run = keep
    try:
        exp.run_scanned(rounds)
    finally:
        del eng.run
    aux = seen[0]
    mods = list(eng.mods)
    metrics = [{k: float(v[i]) for k, v in aux.metrics.items()}
               for i in range(rounds)]
    return Record(
        a=np.asarray(aux.a, bool), ok=np.asarray(aux.ok, bool),
        J=np.asarray(aux.J, np.float64),
        weights={m: np.asarray(aux.weights[m], np.float64) for m in mods},
        energy=np.asarray(aux.energy_total, np.float64), metrics=metrics,
        params0=params0, params=_host(exp.global_params),
        Q=np.array(exp.queues.Q, np.float64),
        zeta=np.array([exp.bound.zeta[m] for m in mods], np.float64),
        delta=np.stack([np.array(exp.bound.delta[m], np.float64)
                        for m in mods]))


def keep(exp):
    """What ``replay`` puts back: the carry (device arrays, which no call
    donates), the round index, the host generator's state, the history's
    length."""
    return (exp._carry, exp._round,
            copy.deepcopy(exp.rng.bit_generator.state), len(exp.history))


def replay(exp, kept) -> None:
    """Put the kept state back, so that the next ``run_scanned(R)`` runs
    the same rounds from the same carry and the same host draws."""
    exp._carry, exp._round, state, n0 = kept
    exp.rng.bit_generator.state = state
    del exp.history[n0:]


def _failed_rounds(recs, record: Record) -> int:
    """Rounds whose metrics are not finite, or whose participants or failed
    uploads differ from the checked warm-up call's."""
    a, ok = record.a, record.ok
    return sum(
        not all(math.isfinite(v) for v in r.metrics.values())
        or r.participants != np.flatnonzero(ok[i]).tolist()
        or r.failures != np.flatnonzero(a[i] & ~ok[i]).tolist()
        for i, r in enumerate(recs))


def _finite_params(exp) -> bool:
    return all(bool(np.all(np.isfinite(np.asarray(x))))
               for x in jax.tree.leaves(exp.global_params))


def run(cfg: dict, traffic: dict, *, seed: int, seconds: float, trace: bool,
        out_dir: Path, t_start: float) -> dict:
    with jax.default_matmul_precision(cfg["precision"]["matmul"]):
        return _run(cfg, traffic, seed, seconds, trace, out_dir, t_start)


def _run(cfg, traffic, seed, seconds, trace, out_dir, t_start) -> dict:
    R = traffic["rounds_per_call"]
    counter = CompileCounter()
    exp = build(cfg, traffic, seed)
    eng = exp._get_fused_engine()
    kept = keep(exp)
    t0 = time.perf_counter()
    record = first_call(exp, R)
    warmup_s = time.perf_counter() - t0
    traces = eng.trace_count
    compiles = counter.n
    setup_s = time.time() - t_start

    rounds = calls = failed = busy_rounds = 0
    participants = []
    if not trace:
        t0 = time.perf_counter()
        while True:
            replay(exp, kept)
            recs = exp.run_scanned(R)
            rounds += R
            calls += 1
            failed += _failed_rounds(recs, record)
            busy_rounds += sum(bool(r.participants) for r in recs)
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        traced = None
    else:
        shutil.rmtree(out_dir, ignore_errors=True)
        out_dir.mkdir(parents=True)
        t0 = time.perf_counter()
        with jax.profiler.trace(str(out_dir),
                                profiler_options=T.profile_options()):
            for _ in range(traffic["trace_calls"]):
                with jax.profiler.TraceAnnotation("harness"):
                    replay(exp, kept)
                with jax.profiler.TraceAnnotation(T.CALL_SPAN):
                    recs = exp.run_scanned(R)
                with jax.profiler.TraceAnnotation("harness"):
                    rounds += R
                    calls += 1
                    failed += _failed_rounds(recs, record)
                    participants += [(r.participants, bool(r.metrics))
                                     for r in recs]
                    busy_rounds += sum(bool(r.participants) for r in recs)
        window_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        events = T.read_xplane(T.find_xplane(out_dir))
        traced = T.reduce_events(*events, rounds=rounds)
        print(f"scanned: trace written and read in "
              f"{time.perf_counter() - t1:.1f} s, "
              f"{sum(map(len, events[0].values()))} device ops", flush=True)
        traced["participants"] = participants
    if eng.trace_count != traces or counter.n != compiles:
        raise RuntimeError(
            f"the window compiled: {eng.trace_count - traces} retraces, "
            f"{counter.n - compiles} compiles")
    if not _finite_params(exp):
        failed = rounds
    print(f"scanned: warm-up call {warmup_s:.3f} s (compiles the program), "
          f"set-up {setup_s:.3f} s, window {window_s:.3f} s, {calls} calls, "
          f"{rounds} rounds, {busy_rounds} with participants "
          f"({busy_rounds / calls:g} of {R} a call), {failed} failed",
          flush=True)

    holder = [exp]

    def release():
        holder.clear()
        gc.collect()

    return {"record": record, "attempted": rounds, "failed": failed,
            "calls": calls, "trace": traced, "release": release,
            "e2e": {"rounds_per_s": rounds / window_s, "setup_s": setup_s}}
