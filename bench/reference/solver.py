"""The server's decision for one round, written plainly in numpy.

JCSBA (Algorithm 2 with the P4.2' bandwidth split and the Theorem-1 bound):

* B_min per client: the bandwidth at which the Shannon rate meets Gamma/tau
  (Eq. 41), by a fixed 30-step bisection on [1e-3, 2 B_max], inflated by
  1e-4; a client that cannot meet its latency budget gets 1e12;
* the KKT split of B_max over a candidate set: every client at
  phi^-1(kappa) or pinned at B_min, kappa found by a 40-step bisection in
  log(-kappa), residual slack spread over the unpinned clients;
* J(a) = V * (eta rho sqrt(A1 + A2) - (2 eta - eta^2)/2 * sum of covered
  zeta^2) + sum_k a_k Q_k (p tau_com + e_cmp); infeasible sets are +inf;
* the immune search: 20 antibodies, 10 generations, 5-fold cloning of the
  4 elites ranked by affinity minus concentration, mutation and fresh rows
  drawn with ``jax.random.bernoulli`` from the round's policy seed, the
  previous winner and the empty set written over the first two rows.

The arithmetic takes the dtype of the solver data: float64 for the
reference, float32 for its control.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

LN2 = float(np.log(2.0))
TOL_B, B_LO, B_CAP, BMIN_SAFETY = 1.0, 1e-3, 1e12, 1e-4
KAPPA_TINY, PHI_SERIES_X = 1e-30, 0.02


@dataclasses.dataclass(frozen=True)
class Hyper:
    S: int = 20
    G: int = 10
    mu: int = 5
    z: float = 0.175
    iota: float = 4.0
    dis: int = 2
    eps1: float = 1.0
    eps2: float = 0.15
    n_bisect_b: int = 30
    n_bisect_k: int = 40

    @property
    def n_elite(self):
        return max(self.S // self.mu, 1)

    @property
    def n_keep(self):
        return min(self.S - self.n_elite, self.n_elite * self.mu
                   + self.n_elite)


def rate(B, h, p_tx, N0):
    return B * np.log1p(p_tx * h / (B * N0)) / LN2


def _phi(B, Q, gamma, h, p_tx, N0):
    x = p_tx * h / (B * N0)
    ln1x = np.log1p(x)
    series = x * x * (-0.5 + x * (2.0 / 3.0 - 0.75 * x))
    num = np.where(x < PHI_SERIES_X, series, x / (1.0 + x) - ln1x)
    return Q * p_tx * gamma * LN2 * num / (B * B * ln1x * ln1x)


def bmin(d, hp: Hyper):
    target = d["gamma"] / np.where(d["tau_rem"] > 0, d["tau_rem"], 1.0)
    ok = (d["tau_rem"] > 0) & (
        target < d["p_tx"] * d["h"] / (d["N0"] * LN2) * (1 - 1e-12))
    lo = np.full_like(d["h"], B_LO)
    hi = np.full_like(d["h"], 2 * d["B_max"])
    for _ in range(hp.n_bisect_b):
        mid = 0.5 * (lo + hi)
        under = rate(mid, d["h"], d["p_tx"], d["N0"]) < target
        lo, hi = np.where(under, mid, lo), np.where(under, hi, mid)
    return np.where(ok, hi * (1 + BMIN_SAFETY), B_CAP), ok


def _phi_inv(kappa, bm, phi_b, d, hp):
    pinned = phi_b >= kappa
    lo = np.broadcast_to(bm, pinned.shape).copy()
    hi = np.full(pinned.shape, d["B_max"], bm.dtype)
    for _ in range(hp.n_bisect_b):
        mid = 0.5 * (lo + hi)
        under = _phi(mid, d["Q"], d["gamma"], d["h"], d["p_tx"],
                     d["N0"]) < kappa
        lo, hi = np.where(under, mid, lo), np.where(under, hi, mid)
    return np.where(pinned, bm, 0.5 * (lo + hi))


def allocate(A, bm, ok, d, hp: Hyper):
    """(B [P, K], feasible [P]) for candidate sets A [P, K]."""
    A = np.asarray(A, bool)
    Af = A.astype(bm.dtype)
    B_max = d["B_max"]
    U = Af.sum(-1)
    total_min = (Af * bm).sum(-1)
    feasible = (~(A & ~ok).any(-1)) & (total_min <= B_max + TOL_B)
    at_eq = total_min >= B_max - TOL_B
    phi_b = _phi(bm, d["Q"], d["gamma"], d["h"], d["p_tx"], d["N0"])
    active = A & (d["Q"] > 0)
    k_lo = np.minimum(np.min(np.where(active, phi_b, 0.0), axis=-1),
                      -1e-35)
    u_a = np.log(-k_lo)
    u_b = np.full_like(u_a, np.log(KAPPA_TINY))
    for _ in range(hp.n_bisect_k):
        u_mid = 0.5 * (u_a + u_b)
        t = (Af * _phi_inv(-np.exp(u_mid)[:, None], bm, phi_b, d,
                           hp)).sum(-1)
        under = t < B_max
        u_a, u_b = np.where(under, u_mid, u_a), np.where(under, u_b, u_mid)
    B = np.where(A, _phi_inv(-np.exp(u_b)[:, None], bm, phi_b, d, hp), 0.0)
    slack = B_max - B.sum(-1)
    free = A & (B > bm + TOL_B)
    nfree = free.sum(-1)
    add = np.where((nfree > 0)[:, None],
                   free * (slack / np.maximum(nfree, 1))[:, None],
                   Af * (slack / np.maximum(U, 1))[:, None])
    B_kkt = np.where(A, np.maximum(B + add, bm), 0.0)
    B_eq = np.where(A, bm, 0.0)
    B_q0 = np.where(A, bm + ((B_max - total_min) / np.maximum(U, 1))[:, None],
                    0.0)
    B = np.where(at_eq[:, None], B_eq,
                 np.where(active.any(-1)[:, None], B_kkt, B_q0))
    return np.where(feasible[:, None], B, 0.0), feasible


def bound(A, d):
    """Theorem-1 term with the descent credit of covered modalities, [P]."""
    Af = np.asarray(A, bool).astype(d["D"].dtype)
    part = d["has"][None] & (Af[:, None, :] > 0.5)
    sched = part.any(-1)
    A1 = ((~sched) * d["zeta2"]).sum(-1)
    wt_raw = np.where(part, d["D"], 0.0)
    denom = wt_raw.sum(-1, keepdims=True)
    wt = np.where(denom > 0, wt_raw / np.maximum(denom, 1e-30), 0.0)
    cover = (Af[:, None, :] * d["wbar"]).sum(-1)
    coeff = wt + d["wbar"] - 2.0 * Af[:, None, :] * d["wbar"]
    A2 = np.maximum((sched * 2.0 * (1.0 - cover)
                     * (coeff * d["delta2"]).sum(-1)).sum(-1), 0.0)
    eta, rho = d["eta"], d["rho"]
    credit = (2 * eta - eta ** 2) / 2.0 * (sched * d["zeta2"]).sum(-1)
    return eta * rho * np.sqrt(A1 + A2) - credit


def objective(A, B, feasible, d):
    A = np.asarray(A, bool)
    r = rate(np.maximum(B, B_LO), d["h"], d["p_tx"], d["N0"])
    tcom = np.where(A, d["gamma"] / np.maximum(r, 1e-30), 0.0)
    energy = (A * d["Q"] * (d["p_tx"] * tcom + d["e_cmp"])).sum(-1)
    return np.where(feasible, d["V"] * bound(A, d) + energy, np.inf)


def _affinity(vals, hp):
    finite = np.isfinite(vals)
    if not finite.any():
        return np.zeros_like(vals)
    jmax = np.max(vals[finite])
    jmin = np.min(vals[finite])
    base = np.maximum((jmax - vals) / max(jmax - jmin, 1e-12), 0.0) + 1e-6
    return np.where(finite, base ** hp.iota, 0.0)


def draws(seed: int, K: int, hp: Hyper):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(np.uint32(seed)), 3)
    n_clones = hp.n_elite * hp.mu
    return (np.asarray(jax.random.bernoulli(k1, 0.5, (hp.S, K))),
            np.asarray(jax.random.bernoulli(k2, hp.z, (hp.G, n_clones, K))),
            np.asarray(jax.random.bernoulli(
                k3, 0.5, (hp.G, hp.S - hp.n_keep, K))))


def jcsba(d, warm, seed: int, hp: Hyper = Hyper()):
    """(a* [K] bool, J* , B* [K]) of one round's immune search."""
    K = len(d["Q"])
    bm, ok = bmin(d, hp)

    def J(A):
        B, feas = allocate(A, bm, ok, d, hp)
        return objective(A, B, feas, d)

    init, mut, fresh = draws(seed, K, hp)
    pop = init.copy()
    pop[0], pop[1] = np.asarray(warm, bool), False
    vals = J(pop)
    best_a, best_J = np.zeros(K, bool), np.inf
    for g in range(hp.G + 1):
        i = int(np.argmin(vals))
        if vals[i] < best_J:
            best_a, best_J = pop[i].copy(), vals[i]
        if g == hp.G:
            break
        aff = _affinity(vals, hp)
        con = ((pop[:, None, :] ^ pop[None, :, :]).sum(-1)
               <= hp.dis).mean(-1)
        elites = pop[np.argsort(-(hp.eps1 * aff - hp.eps2 * con),
                                kind="stable")[:hp.n_elite]]
        cand = np.concatenate([np.repeat(elites, hp.mu, axis=0) ^ mut[g],
                               elites])
        cand_vals = J(cand)
        order = np.argsort(-_affinity(cand_vals, hp),
                           kind="stable")[:hp.n_keep]
        pop = np.concatenate([cand[order], fresh[g]])
        vals = np.concatenate([cand_vals[order], J(fresh[g])])
    return best_a, float(best_J), allocate(best_a[None], bm, ok, d, hp)[0][0]


def evaluate_sets(d, A, hp: Hyper = Hyper()):
    """(J [P], B of the first set) of given sets A [P, K] under the KKT
    split."""
    A = np.asarray(A, bool)
    bm, ok = bmin(d, hp)
    B, feas = allocate(A, bm, ok, d, hp)
    return objective(A, B, feas, d), B[0]


def evaluate_set(d, a, hp: Hyper = Hyper()):
    """(J(a), B(a)) of one given set under the KKT split."""
    J, B = evaluate_sets(d, np.asarray(a, bool)[None], hp)
    return float(J[0]), B
