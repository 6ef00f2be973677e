"""Batched JCSBA solver: jax/numpy parity, legacy cross-checks, properties.

Three layers of evidence that the fused solver is the same algorithm:
  * float32 jitted backend == float64 numpy mirror on the same random bits
    (bit-identical schedules, allocations to ~Hz);
  * batched allocation == legacy scalar ``bandwidth.allocate`` KKT point;
  * every feasible allocation satisfies the latency constraint (In1) and the
    bandwidth budget — as a property over random instances.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.aggregation import unified_weights
from repro.core.convergence import BoundState, objective_batched
from repro.wireless import bandwidth as bw
from repro.wireless import cost as wcost
from repro.wireless.channel import Channel, uplink_rate
from repro.wireless.params import MODALITY_PROFILES, WirelessParams
from repro.wireless.schedulers import ScheduleContext, make_scheduler
from repro.wireless.solver import (SolverHyper, build_solver_data,
                                   solve_round, solve_round_np)
from repro.wireless.solver import ref as sref

HP = SolverHyper()
HP_SMALL = SolverHyper(S=8, G=3)


def _data(K=6, seed=0, tau_max=None, dataset="crema_d", V=1.0):
    params = WirelessParams(K=K, **({} if tau_max is None
                                    else {"tau_max": tau_max}))
    rng = np.random.default_rng(seed)
    prof = MODALITY_PROFILES[dataset]
    mods = ([("audio", "image"), ("audio",), ("image",)] * (K // 3 + 1))[:K]
    sizes = [50] * K
    cc = wcost.client_costs(sizes, mods, prof, params)
    ch = Channel(params, rng)
    w = unified_weights(sizes, mods, ["audio", "image"])
    bound = BoundState(K, ["audio", "image"], mods, w, sizes)
    # perturb the trackers so the bound term is not at its symmetric init
    for m in bound.mods:
        bound.zeta[m] = float(rng.uniform(0.5, 2.0))
        bound.delta[m] = rng.uniform(0.1, 0.6, K)
    data = build_solver_data(ch.draw(), rng.uniform(0, 0.01, K), cc, params,
                             bound, V)
    return data, bound, cc, params, mods, rng


def _rand_pop(data, rng, P=12):
    K = len(data["Q"])
    return rng.integers(0, 2, (P, K)).astype(bool)


# ---------------------------------------------------------------------------
# batched allocation: jax vs numpy reference vs legacy scalar
# ---------------------------------------------------------------------------
def _allocate_both(data, A, hp=HP):
    from repro.wireless.solver import jaxsolver as sjax
    bmin, ok = sref.bmin_np(data["gamma"], data["h"], data["tau_rem"],
                            data["B_max"], data["p_tx"], data["N0"], hp)
    Bn, fn = sref.allocate_np(A, bmin, ok, data["Q"], data["gamma"],
                              data["h"], data["B_max"], data["p_tx"],
                              data["N0"], hp)
    d32 = sjax.to_device(data)
    bmin_j, ok_j = sjax._bmin(d32["gamma"], d32["h"], d32["tau_rem"],
                              d32["B_max"], d32["p_tx"], d32["N0"], hp)
    Bj, fj = sjax.allocate_batch(A, bmin_j, ok_j, d32["Q"], d32["gamma"],
                                 d32["h"], d32["B_max"], d32["p_tx"],
                                 d32["N0"], hp)
    return (Bn, fn), (np.asarray(Bj, np.float64), np.asarray(fj))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_allocate_parity_jax_vs_np(seed):
    data, *_ = _data(K=6, seed=seed)
    rng = np.random.default_rng(seed + 100)
    A = _rand_pop(data, rng)
    (Bn, fn), (Bj, fj) = _allocate_both(data, A)
    assert np.array_equal(fn, fj)
    assert np.allclose(Bj, Bn, rtol=1e-3, atol=2.0)


def test_allocate_infeasible_is_mask_not_none():
    # tiny latency budget: nobody can make the deadline -> every non-empty
    # candidate infeasible, B identically zero, empty candidate feasible
    data, *_ = _data(K=6, seed=3, tau_max=1e-6)
    A = np.vstack([np.eye(6, dtype=bool), np.zeros((1, 6), bool)])
    (Bn, fn), (Bj, fj) = _allocate_both(data, A)
    assert not fn[:6].any() and fn[6]
    assert np.array_equal(fn, fj)
    assert (Bn == 0).all() and (Bj == 0).all()


@pytest.mark.parametrize("seed", [0, 1, 4])
def test_allocate_matches_legacy_scalar(seed):
    """Single-candidate rows of the batched solve land on the same KKT point
    as the sequential bandwidth.allocate."""
    data, _, cc, params, _, rng = _data(K=6, seed=seed)
    checked = 0
    for _ in range(6):
        a = rng.integers(0, 2, 6).astype(bool)
        if not a.any():
            continue
        part = np.flatnonzero(a)
        Bl = bw.allocate(data["Q"][part], data["gamma"][part],
                         data["h"][part], data["tau_rem"][part], params)
        (Bn, fn), _ = _allocate_both(data, a[None])
        if Bl is None:
            assert not fn[0]
            continue
        assert fn[0]
        assert np.allclose(Bn[0][part], Bl, rtol=2e-3, atol=5.0)
        checked += 1
    assert checked >= 2


# ---------------------------------------------------------------------------
# Theorem-1 bound: scalar vs batched-np vs batched-jnp
# ---------------------------------------------------------------------------
def test_bound_objective_three_way_parity():
    data, bound, *_ = _data(K=6, seed=5)
    rng = np.random.default_rng(7)
    A = _rand_pop(data, rng, P=16)
    want = np.array([bound.objective(a.astype(float)) for a in A])
    got_np = sref.bound_objective_np(A, data["zeta2"], data["delta2"],
                                     data["wbar"], data["has"], data["D"],
                                     data["eta"], data["rho"])
    got_j = np.asarray(objective_batched(
        A.astype(np.float32), data["zeta2"].astype(np.float32),
        data["delta2"].astype(np.float32), data["wbar"].astype(np.float32),
        data["has"], data["D"].astype(np.float32),
        data["eta"], data["rho"]))
    assert np.allclose(got_np, want, rtol=1e-10, atol=1e-12)
    assert np.allclose(got_j, want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# full solve + scheduler decisions: jax vs np on the same draws
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 11])
def test_immune_solve_parity(seed):
    data, *_ = _data(K=6, seed=seed)
    seeds = np.zeros((2, 6), bool)
    aj, Jj, Bj = solve_round(data, seeds, 1234 + seed, HP_SMALL)
    an, Jn, Bn = solve_round_np(data, seeds, 1234 + seed, HP_SMALL)
    assert np.array_equal(aj, an)
    assert Jj == pytest.approx(Jn, rel=1e-4, abs=1e-6)
    assert np.allclose(Bj, Bn, rtol=1e-3, atol=2.0)


def _solve_and_resolve(data, seeds, seed_int, hp=HP):
    """solve_core's (a*, J*, B*) and, in the same jitted program, B re-solved
    for a* alone by allocate_batch."""
    import jax

    from repro.wireless.solver import jaxsolver as sjax

    def both(d, seeds, key):
        a, J, B = sjax.solve_core(d, seeds, key, hp)
        bmin, ok = sjax._bmin(d["gamma"], d["h"], d["tau_rem"], d["B_max"],
                              d["p_tx"], d["N0"], hp)
        B_re, _ = sjax.allocate_batch(a[None], bmin, ok, d["Q"], d["gamma"],
                                      d["h"], d["B_max"], d["p_tx"], d["N0"],
                                      hp)
        return a, J, B, B_re[0]

    out = jax.jit(both)(sjax.to_device(data), np.asarray(seeds, bool),
                        jax.random.PRNGKey(seed_int))
    return tuple(np.asarray(x) for x in out)


@pytest.mark.parametrize("seed,tau_max", [(0, None), (1, None), (2, None),
                                          (3, None), (5, 1e-6)],
                         ids=["seed0", "seed1", "seed2", "seed3",
                              "only-empty-feasible"])
def test_solve_carried_bandwidth_is_the_resolve(seed, tau_max):
    """The bandwidth carried beside the winning row is, bit for bit, the one
    a fresh allocate_batch of the winner alone gives."""
    data, *_ = _data(K=10, seed=seed, tau_max=tau_max)
    seeds = np.zeros((2, 10), bool)
    seeds[0] = np.random.default_rng(seed).integers(0, 2, 10).astype(bool)
    a, J, B, B_re = _solve_and_resolve(data, seeds, 500 + seed)
    assert np.isfinite(J)
    assert np.array_equal(B, B_re)
    if tau_max is not None:
        assert not a.any() and (B == 0).all()
    else:
        assert a.any() and (B[a] > 0).all()


def _kappa_chains(jaxpr, n):
    """Carry lengths of every scan of length ``n`` in ``jaxpr`` and its
    sub-jaxprs (a static-trip ``fori_loop`` traces to such a scan)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == n:
            found.append(eqn.outvars[-1].aval.shape[0])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _kappa_chains(sub, n)
    return found


def test_solve_runs_one_kappa_chain_per_generation():
    """Structural guard on the solve's sequential depth: one κ-bisection
    chain on the initial population, one per generation on the candidates
    and fresh rows together, and no re-solve of the winner — G + 1 chains."""
    import jax

    from repro.wireless.solver import jaxsolver as sjax
    data, *_ = _data(K=10, seed=0)
    jaxpr = jax.make_jaxpr(lambda d, s, k: sjax.solve_core(d, s, k, HP))(
        sjax.to_device(data), np.zeros((2, 10), bool),
        jax.random.PRNGKey(0)).jaxpr
    chains = _kappa_chains(jaxpr, HP.n_bisect_k)
    assert sorted(chains) == sorted([HP.S, HP.n_cand + HP.n_fresh])


def test_scheduler_decision_parity_across_rounds():
    """Per-round ScheduleDecision parity: solver='jax' and solver='np' track
    the same schedule/allocation over multiple rounds (warm starts, rng
    stream and Lyapunov-queue coupling included)."""
    decs = {}
    for solver in ("jax", "np"):
        data_rng = np.random.default_rng(0)
        _, bound, cc, params, mods, _ = _data(K=6, seed=0)
        sched = make_scheduler("jcsba", np.random.default_rng(42),
                               solver=solver)
        out = []
        for t in range(3):
            ctx = ScheduleContext(
                h=10 ** data_rng.uniform(-7, -4, 6),
                Q=data_rng.uniform(0, 0.02, 6), cost=cc, params=params,
                bound=bound, round_idx=t, model_dist=np.zeros(6),
                client_modalities=mods)
            out.append(sched.schedule(ctx))
        decs[solver] = out
    for dj, dn in zip(decs["jax"], decs["np"]):
        assert np.array_equal(dj.a, dn.a)
        assert np.allclose(dj.B, dn.B, rtol=1e-3, atol=2.0)
        assert dj.objective == pytest.approx(dn.objective, rel=1e-4,
                                             abs=1e-6)


def test_scheduler_seq_backend_still_works():
    _, bound, cc, params, mods, rng = _data(K=6, seed=1)
    sched = make_scheduler("jcsba", np.random.default_rng(0), solver="seq")
    ctx = ScheduleContext(h=10 ** rng.uniform(-7, -4, 6),
                          Q=np.zeros(6), cost=cc, params=params, bound=bound,
                          round_idx=0, model_dist=np.zeros(6),
                          client_modalities=mods)
    dec = sched.schedule(ctx)
    assert dec.a.shape == (6,) and np.isfinite(dec.objective)


def test_unknown_solver_backend_rejected():
    with pytest.raises(ValueError):
        make_scheduler("jcsba", np.random.default_rng(0), solver="torch")


# ---------------------------------------------------------------------------
# properties: feasible allocations respect In1 and the bandwidth budget
# ---------------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_property_feasible_allocations_meet_constraints(seed):
    rng = np.random.default_rng(seed)
    K = int(rng.integers(2, 8))
    params = WirelessParams(K=K)
    data = {
        "Q": rng.uniform(0.0, 2.0, K),
        "gamma": rng.uniform(3e5, 1.2e6, K),
        "h": 10 ** rng.uniform(-7, -4, K),
        "tau_rem": rng.uniform(0.004, 0.0095, K),
        "B_max": params.B_max, "p_tx": params.p_tx, "N0": params.N0,
    }
    A = rng.integers(0, 2, (10, K)).astype(bool)
    bmin, ok = sref.bmin_np(data["gamma"], data["h"], data["tau_rem"],
                            data["B_max"], data["p_tx"], data["N0"], HP)
    B, feas = sref.allocate_np(A, bmin, ok, data["Q"], data["gamma"],
                               data["h"], data["B_max"], data["p_tx"],
                               data["N0"], HP)
    for p in range(len(A)):
        a = A[p]
        if not feas[p]:
            # genuinely infeasible: some client can never meet the deadline,
            # or the minimum bandwidths alone blow the budget (Eq. 42)
            bl = [bw.b_min(data["gamma"][i], data["h"][i],
                           data["tau_rem"][i], params)
                  for i in np.flatnonzero(a)]
            assert any(b is None for b in bl) or sum(bl) > params.B_max
            assert (B[p] == 0).all()
            continue
        assert (B[p][~a] == 0).all()
        assert (B[p][a] > 0).all() or not a.any()
        assert B[p].sum() <= params.B_max * (1 + 1e-6)
        if a.any():
            part = np.flatnonzero(a)
            r = uplink_rate(B[p][part], data["h"][part], params)
            tau_com = data["gamma"][part] / r
            assert np.all(tau_com <= data["tau_rem"][part] * (1 + 1e-3))


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_property_jax_feasible_allocations_meet_constraints(seed):
    """Same property on the float32 jitted path — the BMIN_SAFETY margin must
    absorb float32 rounding so allocations stay strictly feasible."""
    from repro.wireless.solver import jaxsolver as sjax
    rng = np.random.default_rng(seed)
    K = 5
    params = WirelessParams(K=K)
    data = {
        "Q": rng.uniform(0.0, 2.0, K),
        "gamma": rng.uniform(3e5, 1.2e6, K),
        "h": 10 ** rng.uniform(-7, -4, K),
        "tau_rem": rng.uniform(0.004, 0.0095, K),
        "B_max": params.B_max, "p_tx": params.p_tx, "N0": params.N0,
    }
    A = rng.integers(0, 2, (8, K)).astype(bool)
    d32 = sjax.to_device(data)
    bmin, ok = sjax._bmin(d32["gamma"], d32["h"], d32["tau_rem"],
                          d32["B_max"], d32["p_tx"], d32["N0"], HP)
    B, feas = sjax.allocate_batch(A, bmin, ok, d32["Q"], d32["gamma"],
                                  d32["h"], d32["B_max"], d32["p_tx"],
                                  d32["N0"], HP)
    B, feas = np.asarray(B, np.float64), np.asarray(feas)
    for p in range(len(A)):
        a = A[p]
        if not feas[p] or not a.any():
            continue
        part = np.flatnonzero(a)
        assert B[p].sum() <= params.B_max * (1 + 1e-5)
        r = uplink_rate(B[p][part], data["h"][part], params)
        tau_com = data["gamma"][part] / r
        # strict host-side feasibility, as checked by the FL runtime
        assert np.all(tau_com <= data["tau_rem"][part] + 1e-12)


def test_solver_objective_accounts_empty_schedule():
    """The all-zeros antibody is always seeded, so J* is finite even when
    every non-empty candidate is infeasible."""
    data, *_ = _data(K=6, seed=9, tau_max=1e-6)
    seeds = np.zeros((2, 6), bool)
    a, J, B = solve_round(data, seeds, 7, HP_SMALL)
    assert not a.any()
    assert np.isfinite(J)
    assert (B == 0).all()
