"""Fused jitted JCSBA solver — the whole server-side decision layer (immune
search over antibodies × KKT bandwidth bisection × Theorem-1 bound) as one
JAX program per round.

Each generation evaluates its new rows — the clones/mutants and elites plus
the fresh random rows, which are drawn up front — as one population: J₂(a)
and the KKT bandwidths B come from one candidate-vmapped, participant-masked
fixed-iteration bisection stack (see ``common`` for the numerical
conventions), and the bound term from ``core.convergence.objective_batched``.
Every row's B is carried beside its J, so the winner's allocation is never
solved again: a solve runs G + 1 sequential κ-bisection chains.  Everything
runs under a single ``jax.jit`` with ``lax.fori_loop`` over generations.
Random draws come from ``make_draws`` (``jax.random``) so the float64 numpy
mirror in ``ref.py`` can consume the identical bits.

``solve_core`` is the pure jnp entry point — ``policies.JCSBAPolicy`` builds
its traced step on it and benchmark sweep drivers wrap it in their own
``vmap``/``scan`` (scenario grids × rounds); ``solve_round`` is the
standalone numpy-in/numpy-out per-solve call kept for the jax↔np parity
suite (tests/test_solver_parity.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...core.convergence import objective_batched
from .common import (B_CAP, B_LO, BMIN_SAFETY, KAPPA_TINY, PHI_SERIES_X,
                     TOL_B, SolverHyper)

LN2 = float(np.log(2.0))

_BOOL_KEYS = ("has",)


def to_device(data: dict) -> dict:
    """numpy solver-data dict (``common.build_solver_data``) → float32 jnp."""
    out = {}
    for k, v in data.items():
        out[k] = jnp.asarray(v) if k in _BOOL_KEYS else \
            jnp.asarray(v, jnp.float32)
    return out


# ---------------------------------------------------------------------------
# physics: rate / φ / B_min — fixed-bracket bisections (see common docstring)
# ---------------------------------------------------------------------------
def rate(B, h, p_tx, N0):
    """Shannon/FDMA uplink rate r(B) (Eq. 13), jnp.  Public: the fused round
    engine and the sweep drivers reuse it for post-solve latency/energy."""
    x = p_tx * h / (B * N0)
    return B * jnp.log1p(x) / LN2


_rate = rate        # internal alias used throughout the bisection stack


def _phi(B, Q, gamma, h, p_tx, N0):
    """φ = ∂J₃/∂B (Eq. 37), series-stabilised for small x."""
    x = p_tx * h / (B * N0)
    ln1x = jnp.log1p(x)
    exact = x / (1.0 + x) - ln1x
    series = x * x * (-0.5 + x * (2.0 / 3.0 - 0.75 * x))
    num = jnp.where(x < PHI_SERIES_X, series, exact)
    return Q * p_tx * gamma * LN2 * num / (B * B * ln1x * ln1x)


def _bmin(gamma, h, tau_rem, B_max, p_tx, N0, hp: SolverHyper):
    """Per-client B with r(B) = Γ/τ_rem (Eq. 41).  Returns (bmin [K], ok [K]).

    The bracket tops out at 2·B_max: a B_min beyond that (or a latency-
    infeasible client, which gets the B_CAP sentinel) kills any candidate via
    the Σ B_min ≤ B_max check, where only "> B_max" matters."""
    target = gamma / jnp.where(tau_rem > 0, tau_rem, 1.0)
    ceiling = p_tx * h / (N0 * LN2)
    ok = (tau_rem > 0) & (target < ceiling * (1 - 1e-12))
    lo = jnp.full_like(h, B_LO)
    hi = jnp.full_like(h, 2 * B_max)

    def body(_, lh):
        lo, hi = lh
        mid = 0.5 * (lo + hi)
        under = _rate(mid, h, p_tx, N0) < target
        return jnp.where(under, mid, lo), jnp.where(under, hi, mid)

    _, hi = lax.fori_loop(0, hp.n_bisect_b, body, (lo, hi))
    return jnp.where(ok, hi * (1 + BMIN_SAFETY), B_CAP), ok


def _phi_inv(kappa, bmin, phi_b, Q, gamma, h, B_max, p_tx, N0,
             hp: SolverHyper):
    """B ≥ B_min with φ(B) = κ for every (candidate, client).

    kappa: [P, 1]; per-client arrays [K].  Clients with φ(B_min) ≥ κ are
    pinned at B_min (E1/E2 in the paper's case analysis).  The bracket is
    [B_min, B_max]: every B_k ≤ B_max at the KKT point, so clamping there
    never moves the κ root and keeps the fixed iteration budget small."""
    pinned = phi_b >= kappa                               # [P, K]
    lo = jnp.broadcast_to(bmin, pinned.shape)
    hi = jnp.full(pinned.shape, B_max, bmin.dtype)

    def body(_, lh):
        lo, hi = lh
        mid = 0.5 * (lo + hi)
        under = _phi(mid, Q, gamma, h, p_tx, N0) < kappa
        return jnp.where(under, mid, lo), jnp.where(under, hi, mid)

    # unrolled: the loop runs inside every κ step, and on a TPU a trip of
    # these few-vreg arrays costs its loop overhead, not its arithmetic
    lo, hi = lax.fori_loop(0, hp.n_bisect_b, body, (lo, hi), unroll=True)
    return jnp.where(pinned, bmin, 0.5 * (lo + hi))


def allocate_batch(A, bmin, ok, Q, gamma, h, B_max, p_tx, N0,
                   hp: SolverHyper):
    """Solve P4.2' for a whole population A ∈ {0,1}^{P×K} at once.

    Returns (B [P, K], feasible [P]); infeasibility is a mask, not None —
    infeasible rows carry B = 0."""
    A = jnp.asarray(A, bool)
    Af = A.astype(bmin.dtype)
    U = Af.sum(-1)                                        # [P]
    total_min = (Af * bmin).sum(-1)
    feasible = (~(A & ~ok).any(-1)) & (total_min <= B_max + TOL_B)
    at_eq = total_min >= B_max - TOL_B                    # (42) with equality
    phi_b = _phi(bmin, Q, gamma, h, p_tx, N0)             # [K]
    active = A & (Q > 0)

    # κ* bisection in log(−κ) space: total Σ B_k(κ) is monotone increasing
    # in κ, and κ spans many decades, so geometric halving is required to
    # converge in a fixed budget.  u_a ↔ total < B_max, u_b ↔ total ≥ B_max.
    k_lo = jnp.min(jnp.where(active, phi_b, 0.0), axis=-1)
    k_lo = jnp.minimum(k_lo, -1e-35)      # keep log finite; dummy if ¬active
    u_a = jnp.log(-k_lo)
    u_b = jnp.full_like(u_a, float(np.log(KAPPA_TINY)))

    def kbody(_, uu):
        u_a, u_b = uu
        u_mid = 0.5 * (u_a + u_b)
        kap = -jnp.exp(u_mid)[:, None]
        t = (Af * _phi_inv(kap, bmin, phi_b, Q, gamma, h, B_max, p_tx, N0,
                           hp)).sum(-1)
        under = t < B_max
        return jnp.where(under, u_mid, u_a), jnp.where(under, u_b, u_mid)

    _, u_b = lax.fori_loop(0, hp.n_bisect_k, kbody, (u_a, u_b))
    B = _phi_inv(-jnp.exp(u_b)[:, None], bmin, phi_b, Q, gamma, h,
                 B_max, p_tx, N0, hp)
    B = jnp.where(A, B, 0.0)

    # distribute residual rounding slack (keeps Σ = B_max), as in the legacy
    # scalar path: over unpinned clients if any, else over all participants
    slack = B_max - B.sum(-1)                             # [P]
    freem = A & (B > bmin + TOL_B)
    nfree = freem.sum(-1)
    add = jnp.where((nfree > 0)[:, None],
                    freem * (slack / jnp.maximum(nfree, 1))[:, None],
                    Af * (slack / jnp.maximum(U, 1))[:, None])
    B_kkt = jnp.where(A, jnp.maximum(B + add, bmin), 0.0)

    B_eq = jnp.where(A, bmin, 0.0)
    # all-participants-Q≤0: objective flat, split the slack evenly
    B_q0 = jnp.where(
        A, bmin + ((B_max - total_min) / jnp.maximum(U, 1))[:, None], 0.0)
    B = jnp.where(at_eq[:, None], B_eq,
                  jnp.where(active.any(-1)[:, None], B_kkt, B_q0))
    return jnp.where(feasible[:, None], B, 0.0), feasible


# ---------------------------------------------------------------------------
# J₂(a) for a population, fusing bound + energy terms
# ---------------------------------------------------------------------------
def objective_batch(A, B, feasible, data):
    """J₂(a) = V·(Theorem-1 objective) + Σ_k a_k Q_k (e_com + e_cmp);
    infeasible rows → +inf."""
    A = jnp.asarray(A, bool)
    Af = A.astype(B.dtype)
    r = _rate(jnp.maximum(B, B_LO), data["h"], data["p_tx"], data["N0"])
    tcom = jnp.where(A, data["gamma"] / jnp.maximum(r, 1e-30), 0.0)
    energy = (Af * data["Q"] * (data["p_tx"] * tcom
                                + data["e_cmp"])).sum(-1)
    bound = objective_batched(Af, data["zeta2"], data["delta2"],
                              data["wbar"], data["has"], data["D"],
                              data["eta"], data["rho"])
    return jnp.where(feasible, data["V"] * bound + energy, jnp.inf)


def _affinity(vals, hp: SolverHyper):
    """Eq. 50 affinity: min-max normalised, sharpened; infeasible → 0."""
    finite = jnp.isfinite(vals)
    jmax = jnp.max(jnp.where(finite, vals, -jnp.inf))
    jmin = jnp.min(jnp.where(finite, vals, jnp.inf))
    span = jnp.maximum(jmax - jmin, 1e-12)
    base = jnp.maximum((jmax - vals) / span, 0.0) + 1e-6
    aff = jnp.where(finite, base ** hp.iota, 0.0)
    return jnp.where(finite.any(), aff, jnp.zeros_like(vals))


# ---------------------------------------------------------------------------
# immune search over the population (Algorithm 2), fully on device
# ---------------------------------------------------------------------------
def make_draws(key, K: int, hp: SolverHyper):
    """All random bits for one solve.  Called inside the jitted program and,
    eagerly, by the numpy reference — identical bits either way."""
    k1, k2, k3 = jax.random.split(key, 3)
    init = jax.random.bernoulli(k1, 0.5, (hp.S, K))
    mut = jax.random.bernoulli(k2, hp.z, (hp.G, hp.n_clones, K))
    fresh = jax.random.bernoulli(k3, 0.5, (hp.G, hp.n_fresh, K))
    return init, mut, fresh


def solve_core(data: dict, seeds, key, hp: SolverHyper):
    """One JCSBA solve: (a*, J*, B*) for one round's ``data`` (jnp, float32).

    ``seeds`` [2, K] bool: warm-start antibody rows written over the first
    population rows (row 1 is conventionally the all-zeros antibody, so an
    empty schedule is always evaluated and J* is always finite).

    Callers may inject a precomputed per-client bisection as ``data["bmin"]``
    / ``data["bmin_ok"]`` — the fused round engine computes ``_bmin`` shard-
    locally under a client-sharded mesh and ``all_gather``s the [K] result
    (the bisection is elementwise, so the injected values are bit-identical
    to the inline ones)."""
    K = data["Q"].shape[0]
    if "bmin" in data:
        bmin, ok = data["bmin"], data["bmin_ok"]
    else:
        bmin, ok = _bmin(data["gamma"], data["h"], data["tau_rem"],
                         data["B_max"], data["p_tx"], data["N0"], hp)

    def J_batch(A):
        B, feas = allocate_batch(A, bmin, ok, data["Q"], data["gamma"],
                                 data["h"], data["B_max"], data["p_tx"],
                                 data["N0"], hp)
        return objective_batch(A, B, feas, data), B

    def fold_best(pop, vals, Bs, best):
        best_a, best_J, best_B = best
        i = jnp.argmin(vals)
        better = vals[i] < best_J
        return (jnp.where(better, pop[i], best_a),
                jnp.where(better, vals[i], best_J),
                jnp.where(better, Bs[i], best_B))

    init, mut, fresh = make_draws(key, K, hp)
    seeds = jnp.asarray(seeds, bool)
    pop0 = init.at[0].set(seeds[0]).at[1].set(seeds[1])

    # J and B are purely row-wise, so the population's values and bandwidths
    # are carried across generations and only *new* genotypes are evaluated
    # — the batched analogue of the sequential path's memoisation.  The fresh
    # rows are drawn up front, so they share the candidates' bisection chain:
    # one chain a generation, not two.
    def gen(g, carry):
        pop, vals, Bs, best = carry
        best = fold_best(pop, vals, Bs, best)
        aff = _affinity(vals, hp)
        ham = (pop[:, None, :] ^ pop[None, :, :]).sum(-1)
        con = (ham <= hp.dis).astype(aff.dtype).mean(-1)      # Eq. 51-52
        inc = hp.eps1 * aff - hp.eps2 * con                   # Eq. 53
        elites = pop[jnp.argsort(-inc)[:hp.n_elite]]
        clones = jnp.repeat(elites, hp.mu, axis=0)            # μ-fold cloning
        mutants = clones ^ mut[g]
        rows = jnp.concatenate([mutants, elites, fresh[g]], axis=0)
        new_vals, new_B = J_batch(rows)
        cand_aff = _affinity(new_vals[:hp.n_cand], hp)
        order = jnp.argsort(-cand_aff)[:hp.n_keep]
        keep = jnp.concatenate([order, hp.n_cand + jnp.arange(hp.n_fresh)])
        return rows[keep], new_vals[keep], new_B[keep], best

    # no row beats +inf → the all-zeros antibody, whose allocation is zeros
    best0 = (jnp.zeros(K, bool), jnp.asarray(jnp.inf, jnp.float32),
             jnp.zeros(K, bmin.dtype))
    pop, vals, Bs, best = lax.fori_loop(0, hp.G, gen,
                                        (pop0, *J_batch(pop0), best0))
    return fold_best(pop, vals, Bs, best)                     # final gen check


@partial(jax.jit, static_argnames="hp")
def _solve_jit(data, seeds, key, hp: SolverHyper):
    return solve_core(data, seeds, key, hp)


def solve_round(data: dict, seeds: np.ndarray, seed_int: int,
                hp: SolverHyper):
    """Host-facing per-round solve: numpy in, numpy out.

    Compiles once per (K, M, hp) signature; subsequent rounds re-use the
    cached executable."""
    key = jax.random.PRNGKey(seed_int)
    a, J, B = _solve_jit(to_device(data), jnp.asarray(seeds, bool), key, hp)
    return np.asarray(a), float(J), np.asarray(B, np.float64)
