"""One call's worth of rounds of wireless multimodal FL, written plainly.

Per round (Algorithm 1 of the paper): draw the channel, decide the schedule
and bandwidth, mark scheduled clients that miss tau_max as failures, run one
full-batch gradient step of each participant's loss on its own modalities
(dropout seeded by the client's seed), aggregate each modality over its
uploaders with weights D_k / sum D (Eq. 12), refresh the trackers (zeta_m =
norm of the aggregated gradient, delta_km = distance of an uploader's
gradient to it, other owners decayed 0.9 toward the fresh mean), step the
Lyapunov queues by the energy spent, and evaluate the fresh global model on
the held-out split.

``walk`` runs these rounds in one of two roles:

* following the program: each round it makes its own decision, scores the
  program's decision against it, and then acts on the program's decision
  (the decision is an answer checked by what it says: a JCSBA schedule by
  its objective under the reference's own state);
  every other quantity is its own;
* as a stand-in for the program, acting on its own decisions, for the
  control (matmuls one precision step lower, the solver in float32) and
  for the planted faults.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import inputs as I
from . import model as Mo
from . import solver as S


@dataclasses.dataclass
class Record:
    """What one call of the program shows about its rounds, and its state
    after them."""
    a: np.ndarray                      # [R, K] scheduled
    ok: np.ndarray                     # [R, K] participated
    J: np.ndarray                      # [R] solver objective (NaN: none)
    weights: Dict[str, np.ndarray]     # {m: [R, K]} Eq. 12 weights
    energy: np.ndarray                 # [R] cumulative energy
    metrics: List[Dict[str, float]]    # per round eval metrics
    params0: dict                      # global params before the call
    params: dict                       # after it
    Q: np.ndarray                      # [K]
    zeta: np.ndarray                   # [M]
    delta: np.ndarray                  # [M, K]


FAULTS = ("unchanged", "half_batch", "altered")
#: rounds in which the reference runs its own immune search to score the
#: program's schedule against (the search is most of the reference's time)
SEARCH_ROUNDS = 3


def _worst(cur: float, x: float) -> float:
    """The larger reading; one that is not a number counts as infinite."""
    x = float(x)
    return float("inf") if x != x else max(cur, x)


def _flat(tree, np_dtype):
    return np.concatenate([np.asarray(x, np_dtype).ravel()
                           for x in jax.tree.leaves(tree)])


def walk(cfg: dict, traffic: dict, seed: int, rounds: int,
         prog: Optional[Record] = None, precision: str = "highest",
         np_dtype=np.float64, fault: Optional[str] = None):
    """Run ``rounds`` rounds from the seed.  With ``prog`` the walk follows
    the program's decisions and returns (own record, readings); without it
    it acts on its own (with ``fault`` planted, if given) and returns
    (record, None)."""
    cfg_key = json.dumps(cfg, sort_keys=True)
    inp = I.make_inputs(cfg, seed)
    W = I.WIRELESS
    K, mods = cfg["K"], inp.mods
    M = len(mods)
    p_tx, N0 = I.p_tx(), I.n0()
    eta = cfg["eta"]
    D = inp.sizes.astype(np_dtype)
    wbar = np.stack([np.where(inp.has[i], D, 0.0) / (inp.has[i] * D).sum()
                     for i in range(M)])
    params0 = Mo.init_params(cfg, seed)
    theta = params0
    Q = np.zeros(K, np_dtype)
    spent = np.zeros(K, np_dtype)
    zeta = np.full(M, 1.0, np_dtype)
    delta = np.full((M, K), 0.3, np_dtype)
    warm = np.zeros(K, bool)
    test_feats = {m: jnp.asarray(x) for m, x in inp.test.features.items()}
    test_labels = jnp.asarray(inp.test.labels)
    # every client's rows padded to one length and every modality present
    # (zeros where the client lacks it), so one gradient program serves all
    n_max = max(c.size for c in inp.clients)

    def padded(c, m, shape):
        if m not in c.modalities:
            return np.zeros((n_max,) + shape, np.float32)
        x = c.data.features[m]
        return np.pad(x, [(0, n_max - len(x))] + [(0, 0)] * len(shape))

    client_feats = [{m: jnp.asarray(padded(c, m, x.shape[1:]))
                     for m, x in inp.test.features.items()}
                    for c in inp.clients]
    client_labels = [jnp.asarray(np.pad(c.data.labels, (0, n_max - c.size)))
                     for c in inp.clients]
    client_n = [c.size for c in inp.clients]
    if traffic["scheduler"] != "jcsba":
        raise ValueError(f"the reference has no {traffic['scheduler']!r} "
                         f"policy; it follows JCSBA only")

    rec = dict(a=[], ok=[], J=[], energy=[], metrics=[],
               weights={m: [] for m in mods})
    first = None            # the first round in which anyone uploaded
    rd = dict(sched_excess=0.0, J_gap=0.0, J_gap_first=0.0,
              part_mismatch=0, weights_gap=0.0,
              energy_gap=0.0, loss_gap=0.0, loss_gap_first=0.0, acc_gap=0.0)
    for t in range(rounds):
        h, dseed, cseeds = I.draw_round(inp)
        d = {"Q": Q, "h": h.astype(np_dtype),
             "gamma": inp.gamma.astype(np_dtype),
             "tau_rem": (W["tau_max"] - inp.tau_cmp).astype(np_dtype),
             "e_cmp": inp.e_cmp.astype(np_dtype), "B_max": W["B_max"],
             "p_tx": p_tx, "N0": N0, "V": cfg["V"], "eta": eta, "rho": 1.0,
             "zeta2": zeta ** 2, "delta2": delta ** 2, "wbar": wbar,
             "has": inp.has, "D": D}
        # the decision: the reference's own, then the one acted on
        if prog is None or t < SEARCH_ROUNDS:
            a_own, J_own, B_own = S.jcsba(d, warm, dseed)
        else:
            a_own = J_own = B_own = None
        if prog is None:
            a, B, J_rep = a_own, B_own, J_own
            if fault == "altered":
                a = a.copy()
                a[0] = ~a[0]
                B = S.evaluate_set(d, a)[1]
        else:
            a = np.asarray(prog.a[t], bool)
            (J_a, J_empty), B = S.evaluate_sets(
                d, np.stack([a, np.zeros(K, bool)]))
            # the scale: J of the empty schedule, V eta rho sqrt(sum
            # zeta^2), the size of the bound the search trades against
            scale = abs(J_empty)
            if J_own is not None:
                rd["sched_excess"] = _worst(rd["sched_excess"],
                                            (J_a - J_own) / scale)
            gap = abs(float(prog.J[t]) - J_a) / scale
            rd["J_gap"] = _worst(rd["J_gap"], gap)
            if first is not None and t == first + 1:
                # reads the trackers the first uploads left
                rd["J_gap_first"] = _worst(0.0, gap)
            J_rep = float("nan")
        warm = a.copy()
        r = S.rate(np.maximum(B, S.B_LO), d["h"], p_tx, N0)
        tcom = np.where(a, d["gamma"] / np.maximum(r, 1e-30), 0.0)
        ok = a & (tcom + inp.tau_cmp <= W["tau_max"] + 1e-12)
        if prog is not None:
            rd["part_mismatch"] += int(not np.array_equal(ok, prog.ok[t]))
        if first is None and ok.any():
            first = t

        # local steps of the participants, then Eq. 12 and the trackers
        up = np.stack([ok & inp.has[i] for i in range(M)])
        w = np.stack([np.where(up[i], D, 0.0) / max((up[i] * D).sum(), 1e-30)
                      for i in range(M)])
        new_k, grad_k = {}, {}
        for k in np.flatnonzero(ok):
            n = client_n[k] // 2 if fault == "half_batch" else client_n[k]
            valid = jnp.asarray(np.arange(n_max) < n, jnp.float32)
            avail = jnp.asarray([m in inp.clients[k].modalities
                                 for m in mods], jnp.float32)
            g = Mo.grads(cfg_key, precision, theta, client_feats[k],
                         client_labels[k], valid, avail, int(cseeds[k]))
            grad_k[k] = g
            new_k[k] = jax.tree.map(
                lambda p, gg: p - eta * gg, theta, g)
        theta_next = dict(theta)
        for i, m in enumerate(mods):
            ks = np.flatnonzero(up[i])
            if not ks.size:
                continue
            theta_next[m] = jax.tree.map(
                lambda *xs: sum(jnp.float32(w[i, k]) * x
                                for k, x in zip(ks, xs)),
                *[new_k[k][m] for k in ks])
            gs = np.stack([_flat(grad_k[k][m], np_dtype) for k in ks])
            gbar = (w[i, ks][:, None].astype(np_dtype) * gs).sum(0)
            norms = np.sqrt(((gs - gbar) ** 2).sum(-1))
            zeta[i] = np.sqrt((gbar ** 2).sum())
            mean_d = norms.mean()
            stale = inp.has[i] & ~up[i]
            delta[i] = np.where(stale, 0.9 * delta[i] + 0.1 * mean_d,
                                delta[i])
            delta[i, ks] = norms
        if fault != "unchanged":
            theta = theta_next
        used = a * (p_tx * tcom + inp.e_cmp)
        Qn = Q - (W["E_add"] - used)
        Q = (Qn * (Qn > 0)).astype(np_dtype)
        spent = spent + used
        met = Mo.evaluate(cfg_key, precision, theta_next, test_feats,
                          test_labels)

        rec["a"].append(a)
        rec["ok"].append(ok)
        rec["J"].append(J_rep)
        rec["energy"].append(float(spent.sum()))
        rec["metrics"].append(met)
        for i, m in enumerate(mods):
            rec["weights"][m].append(w[i])
        if prog is not None:
            rd["weights_gap"] = _worst(rd["weights_gap"], max(
                float(np.max(np.abs(np.asarray(prog.weights[m][t]) - w[i])))
                for i, m in enumerate(mods)))
            # over the round's allowance where nobody has spent anything yet
            rd["energy_gap"] = _worst(rd["energy_gap"], abs(
                float(prog.energy[t]) - float(spent.sum()))
                / max(float(spent.sum()), W["E_add"]))
            pm = prog.metrics[t]
            gap = abs(pm["loss"] - met["loss"]) / met["loss"]
            rd["loss_gap"] = _worst(rd["loss_gap"], gap)
            if t == first:      # the globals after the first uploads
                rd["loss_gap_first"] = _worst(0.0, gap)
            rd["acc_gap"] = _worst(rd["acc_gap"], max(
                abs(pm[k] - met[k]) for k in met if k != "loss"))

    if fault == "unchanged":
        Q = np.zeros(K, np_dtype)
        zeta = np.full(M, 1.0, np_dtype)
        delta = np.full((M, K), 0.3, np_dtype)
    own = Record(np.array(rec["a"]), np.array(rec["ok"]), np.array(rec["J"]),
                 {m: np.array(v) for m, v in rec["weights"].items()},
                 np.array(rec["energy"]), rec["metrics"],
                 jax.tree.map(np.asarray, params0),
                 jax.tree.map(np.asarray, theta), np.asarray(Q),
                 zeta.copy(), delta.copy())
    if prog is None:
        return own, None
    rd.update(final_readings(prog, own))
    return own, rd


def final_readings(prog: Record, ref: Record) -> Dict[str, float]:
    """Readings of the state after the call: each leaf's change, the
    queues and the trackers."""
    out = {}
    flat_p = jax.tree_util.tree_flatten_with_path(prog.params)[0]
    p0 = jax.tree.leaves(prog.params0)
    r1 = jax.tree.leaves(ref.params)
    r0 = jax.tree.leaves(ref.params0)
    dprog = np.array([np.linalg.norm(np.asarray(a, np.float64)
                                     - np.asarray(b, np.float64))
                      for (_, a), b in zip(flat_p, p0)])
    dref = np.array([np.linalg.norm(np.asarray(a, np.float64)
                                    - np.asarray(b, np.float64))
                     for a, b in zip(r1, r0)])
    med = float(np.median(dref))
    moved = dref >= 1e-3 * med
    gap = np.abs(dprog - dref) / np.maximum(dref, med)
    out["dparam_gap"] = float(np.max(gap[moved]))
    out["leaves_left_out"] = int((~moved).sum())
    worst = int(np.argmax(np.where(moved, gap, -1.0)))
    out["dparam_worst_leaf"] = jax.tree_util.keystr(flat_p[worst][0])
    out["dparam_median_leaf_gap"] = float(np.median(gap[moved]))
    qs = max(float(np.max(ref.Q)), I.WIRELESS["E_add"])
    out["queue_gap"] = float(np.max(np.abs(prog.Q - ref.Q)) / qs)
    tz = np.abs(prog.zeta - ref.zeta) / np.maximum(ref.zeta, 1e-30)
    td = (np.abs(prog.delta - ref.delta).max(-1)
          / np.maximum(ref.delta.max(-1), 1e-30))
    out["tracker_gap"] = float(max(tz.max(), td.max()))
    return out
