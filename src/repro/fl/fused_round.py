"""Fully on-device MFL rounds — schedule → cohort gather → local updates →
Eq. 12 aggregation → queue/tracker update as ONE jitted program per round.

PR 1 batched the client fan-out (fl/client.py) and PR 2 batched the server
decision layer (wireless/solver/), but the runtime still hopped to host
between them every round: solver jit → host decode → client jit → host
aggregation → host trackers.  This module chains all four stages inside a
single ``round_step(carry, xs) -> (carry, aux)`` whose carry packs the entire
evolving experiment state, so ``lax.scan`` can drive whole experiments (and,
vmapped, dense V/τ scenario grids — benchmarks/fused_round.py) without
leaving the device.

Cohort gather — the BGD hot path is O(J), not O(K)
--------------------------------------------------
Originally the round ran the masked BGD update over the *whole* dense client
stack: every round touched K × max_batch × d features even though only a
handful of clients are ever scheduled.  Policies now emit a static-size,
duplicate-free cohort index vector (``wireless.policies.cohort_indices`` —
the sixth ``step_full`` output), and the round body *gathers* exactly those
J rows from a device-resident ``data.partition.ClientStore`` before the BGD
stage:

* single device — ``store.take(idx)`` (``jnp.take`` over the client axis);
* client-sharded 2-D mesh — a masked cross-shard reduction (``_gather_rows``):
  each shard contributes the cohort rows it owns, ``lax.psum`` over the
  ``"clients"`` axis reassembles them bit-exactly.

Everything model-sized downstream — the vmapped BGD, the Eq. 12 contraction
(``core.aggregation``), the ζ/δ divergence norms (Gram-form
``core.convergence.tracker_update_gram``) — runs on [J]-leading stacks;
cohort-local results are scattered back to dense [K] rows through the index
vector (a ``segment_sum``, exact because the indices are duplicate-free).
Only O(K) *vector* physics stays dense: channel rates, latency feasibility,
Lyapunov queues — cheap at any K.  Per-round latency and peak memory
therefore scale with the cohort, not the population
(benchmarks/population_scale.py: K = 50 → 100 000 at J ≈ 10).

Carry layout (``FusedCarry``, a pytree):

* ``params``      — the global multimodal model {modality: subtree};
* ``policy``      — the scheduling policy's own state dict
  (``wireless.policies``: JCSBA's warm-start antibody, Round-Robin's cursor,
  empty for Random/Selection) — the engine is policy-generic: any scheduler
  exposing a traced ``SchedulePolicy`` core runs fused;
* ``Q`` / ``spent`` — Lyapunov virtual energy queues + cumulative energy;
* ``zeta`` / ``delta`` — the Theorem-1 ζ_m / δ_{k,m} trackers as dense
  [M] / [M, K] arrays (modality order = ``BoundState.mods``);
* ``model_dist``  — ‖θ_k − θ⁰‖ bookkeeping (read by the Selection policy).

Per-round inputs (``RoundXs``) are the only randomness the loop consumes:
channel gains, the immune-search PRNG seed and per-client dropout seeds —
plus the (deterministic) ``eval_flag`` marking rounds on the ``eval_every``
grid.  They are pregenerated on host by ``draw_round_xs`` in exactly the
order the host loop consumes its ``np.random.Generator`` stream (channel
draws → solver seed → K client seeds — see
``MFLExperiment._draw_client_seeds``), which is what makes the fused path
draw-for-draw equivalent to the host reference: with identical experiment
seeds, participant sets match exactly and params / queues / trackers match
to float32 reduction-order tolerance (tests/test_fused_round.py locks this
contract).

Two per-round decision surfaces ride along since PR 5:

* **modality dropout** — policies whose ``step_full`` emits a drop mask
  ([28]'s baseline, ``wireless.policies.DropoutPolicy``) thread it into the
  Eq. 12 upload masks (``core.aggregation.upload_masks_traced``), so the
  last host-only scheduler now scans on device and the full Table-3
  five-policy comparison is one fused program;
* **device-resident eval** — rounds flagged by ``xs.eval_flag`` evaluate the
  freshly aggregated globals on the held-out split inside the scan
  (``fl.eval.eval_metrics`` behind ``lax.cond``; skipped rounds emit NaN
  fillers gated by ``RoundAux.eval_mask``), so ``run_scanned`` and
  ``scan_v_grid`` produce multimodal + unimodal accuracy *curves* with zero
  host eval calls.

Equivalence caveats (all covered by the tests' tolerances): the host loop
keeps queues/trackers in float64 numpy between the f32 jitted stages, while
the fused carry stays f32 end-to-end — per-round drift is ~1e-7 relative and
does not move the solver's argmin on the tested configs.  The cohort path
adds no new caveat: cohort rows appear in ascending client order (stable
argsort), so reductions see the same nonzero terms in the same order as the
dense masked path, and interleaved exact zeros do not move f32 sums
(property-tested in tests/test_cohort_gather.py).
"""
from __future__ import annotations

import functools
import time
import warnings
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core import aggregation as agg
from ..core.convergence import grad_gram, tracker_update_gram
from .eval import device_test_set, eval_metrics, nan_metrics
from ..launch.mesh import make_sweep_mesh
from ..launch.sharding import (logical_pspec, pad_leading_axis,
                               population_shard_map, scenario_shard_map,
                               slice_leading_axis)
from ..wireless.lyapunov import queue_update
from ..wireless.solver import build_solver_data
from ..wireless.solver.common import B_LO
from ..wireless.solver.jaxsolver import _bmin, rate, to_device


class FusedCarry(NamedTuple):
    """Whole-experiment state threaded through ``lax.scan``."""
    params: Dict[str, Any]
    policy: Dict[str, jax.Array]    # SchedulePolicy state (may be empty)
    Q: jax.Array                # [K]
    spent: jax.Array            # [K]
    zeta: jax.Array             # [M]
    delta: jax.Array            # [M, K]
    model_dist: jax.Array       # [K]


class RoundXs(NamedTuple):
    """Pregenerated per-round randomness (stack leading axis to scan)."""
    h: jax.Array                # [K] channel gains (float32)
    draw_seed: jax.Array        # scalar uint32 — immune-search key seed
    client_seeds: jax.Array     # [K] uint32 — per-client dropout seeds
    eval_flag: jax.Array        # scalar bool — evaluate this round's globals


class RoundAux(NamedTuple):
    """Per-round outputs — the traced stand-in for ScheduleDecision +
    RoundRecord, decoded on host by ``MFLExperiment._decode_fused_round``."""
    a: jax.Array                # [K] bool — scheduled (incl. failures)
    ok: jax.Array               # [K] bool — participated
    J: jax.Array                # scalar solver objective J₂(a*)
    weights: Dict[str, jax.Array]   # Eq. 12 weights w^t_{k,m}
    energy_total: jax.Array     # scalar Σ_k cumulative energy after round
    drop: Dict[str, jax.Array]  # {m: [K] bool} — modality dropped this round
    metrics: Dict[str, jax.Array]   # test metrics (NaN when not evaluated)
    eval_mask: jax.Array        # scalar bool — ``metrics`` is real


def draw_round_xs(exp, rounds: int, eval_every: Optional[int] = None,
                  include_final: bool = False) -> RoundXs:
    """Consume ``rounds`` rounds of the experiment's host randomness in the
    canonical order — one host-loop round exactly: K channel draws
    (``Channel.draw``), one policy seed (the single ``rng.integers(2 ** 31)``
    every policy-backed scheduler draws per round, whatever the policy), then
    the per-client dropout seeds via the experiment's own
    ``_draw_client_seeds`` so that contract stays single-sourced.  A fused
    experiment and a host-loop experiment sharing the same seed therefore
    walk the identical ``np.random`` stream.

    ``eval_flag`` is deterministic, not random: round t is flagged exactly
    when the host loop would evaluate it (``(exp._round + t) %
    exp.eval_every == 0``).  ``include_final`` additionally flags the last
    round — sweep drivers use it so every scenario's curve ends with the
    final model's metrics whatever the cadence.

    ``eval_every`` is deprecated: the cadence is the *experiment's* setting,
    duplicated here it silently desynchronised host-loop and fused curves.
    Pass ``MFLExperiment(eval_every=...)`` instead."""
    if eval_every is not None:
        warnings.warn(
            "draw_round_xs(eval_every=...) is deprecated; the eval cadence "
            "comes from the experiment — construct "
            "MFLExperiment(eval_every=...) instead",
            DeprecationWarning, stacklevel=2)
    K = exp.params.K
    ee = int(exp.eval_every if eval_every is None else eval_every)
    h = np.empty((rounds, K), np.float32)
    draw = np.empty(rounds, np.uint32)
    cseed = np.empty((rounds, K), np.uint32)
    flags = np.zeros(rounds, bool)
    for t in range(rounds):
        h[t] = exp.channel.draw()
        draw[t] = exp.rng.integers(2 ** 31)
        cseed[t] = exp._draw_client_seeds()
        flags[t] = (exp._round + t) % ee == 0
    if include_final and rounds:
        flags[-1] = True
    return RoundXs(jnp.asarray(h), jnp.asarray(draw), jnp.asarray(cseed),
                   jnp.asarray(flags))


def draw_population_xs(channel, rng, K: int, rounds: int,
                       eval_every: int = 0,
                       include_final: bool = False) -> RoundXs:
    """``draw_round_xs`` for ``from_store`` engines (no ``MFLExperiment``):
    one host-loop round of randomness per scanned round — K channel draws,
    one policy seed, K client seeds — from an explicit ``Channel`` + numpy
    generator.  ``eval_every <= 0`` disables the eval cadence entirely
    (``include_final`` can still flag the last round, the scenario-zoo
    convention so every curve ends with the final model's metrics)."""
    h = np.empty((rounds, K), np.float32)
    draw = np.empty(rounds, np.uint32)
    cseed = np.empty((rounds, K), np.uint32)
    flags = np.zeros(rounds, bool)
    for t in range(rounds):
        h[t] = channel.draw()
        draw[t] = rng.integers(2 ** 31)
        cseed[t] = rng.integers(2 ** 31, size=K, dtype=np.uint32)
        flags[t] = eval_every > 0 and t % eval_every == 0
    if include_final and rounds:
        flags[-1] = True
    return RoundXs(jnp.asarray(h), jnp.asarray(draw), jnp.asarray(cseed),
                   jnp.asarray(flags))


def _gather_rows(x, idx, axis_name: str):
    """Cross-shard cohort gather under a client-sharded mesh.

    ``x`` is this shard's [K_loc, ...] slice of a client-axis leaf; ``idx``
    [J] holds *global* client indices (replicated).  Each shard contributes
    the rows it owns (others zeroed), and ``lax.psum`` over the mesh axis
    reassembles the full cohort — exact for every dtype here: each output
    element receives exactly one nonzero contribution."""
    K_loc = x.shape[0]
    off = lax.axis_index(axis_name) * K_loc
    local = idx - off
    mine = (local >= 0) & (local < K_loc)
    rows = jnp.take(x, jnp.clip(local, 0, K_loc - 1), axis=0)
    orig = rows.dtype
    if orig == jnp.bool_:
        rows = rows.astype(jnp.int32)
    shape = (idx.shape[0],) + (1,) * (rows.ndim - 1)
    rows = jnp.where(mine.reshape(shape), rows, 0)
    out = lax.psum(rows, axis_name)
    return out.astype(orig) if orig == jnp.bool_ else out


class FusedRoundEngine:
    """Per-experiment compiler/runner for the fused round program.

    Built lazily by ``MFLExperiment`` (engine="fused").  Holds the static,
    device-resident context — the ``ClientStore`` population, per-client
    costs, solver template, tracker constants, the held-out test split for
    the in-scan eval — and exposes:

    * ``step(carry, xs)``  — one jitted round;
    * ``scan(carry, xs)``  — R rounds under one ``lax.scan`` (xs stacked);
    * ``init_carry()`` / ``export_carry()`` — host-state ↔ carry conversion.

    ``from_store`` builds an engine straight from a ``ClientStore`` +
    ``WirelessParams`` + policy — no ``MFLExperiment`` (whose per-client
    Python loops are prohibitive at K = 10⁵); benchmarks/population_scale.py
    drives cohort rounds at population scale through it.

    ``trace_count`` increments each time the round body is *traced* — the
    zero-host-round-trips contract is asserted as "many rounds, one trace"
    in tests/test_fused_round.py.
    """

    def __init__(self, exp):
        exp.scheduler.bind(exp.params.K, exp.client_mods)
        self.policy = exp.scheduler.policy
        if self.policy is None:
            raise ValueError(
                f"fused rounds require a traced scheduling policy "
                f"(wireless.policies); scheduler {exp.scheduler.name!r} "
                f"runs host-side only")
        self.exp = exp
        self.K = exp.params.K
        self.mods = list(exp.bound.mods)
        self.V = getattr(exp.scheduler, "V", 1.0)
        self.staleness = float(exp.bound.staleness)
        self.trace_count = 0

        # solver-data template: static entries live on device once; Q/h and
        # the ζ²/δ² snapshot are overwritten from the carry every round
        tmpl = build_solver_data(np.zeros(self.K), np.zeros(self.K),
                                 exp.cost, exp.params, exp.bound, self.V)
        # tau_cmp rides in the template (not a baked engine static) so
        # scenario grids can override it per scenario like every other
        # per-client cost vector
        tmpl["tau_cmp"] = np.asarray(exp.cost.tau_cmp, np.float64)
        self._solver_tmpl = to_device(tmpl)
        p = exp.params
        self._tau_max = float(p.tau_max)
        self._E_add = float(p.E_add)
        self._p_tx = float(p.p_tx)
        self._N0 = float(p.N0)

        self._store = exp._get_store()
        self._init_params = jax.tree.map(jnp.asarray, exp.init_params)
        self._cohort = exp.adapter.cohort_step(tuple(self.mods))
        # the adapter's deterministic forward backs the in-scan eval, so the
        # fused curve matches adapter.evaluate for every model family
        self._eval_logits = exp.adapter.eval_logits

        # device-resident eval context: the held-out split lives on device
        # for the engine's lifetime; rounds flagged by xs.eval_flag run the
        # shared fl.eval.eval_metrics program on the fresh globals.  It enters
        # every program as an argument, never as a baked-in constant, so the
        # compiled code (and its cache key) does not depend on the data.
        self._test_set = device_test_set(exp.test_ds)
        self._compile()

    @classmethod
    def from_store(cls, store, params, policy, adapter, *, V: float = 1.0,
                   eta: float = 0.05, rho: float = 1.0,
                   staleness: float = 0.9, init_zeta: float = 1.0,
                   init_delta: float = 0.3, seed: int = 0):
        """Engine straight from a ``ClientStore`` — the population-scale
        entry point.  The solver template is assembled from the store's
        vectorized cost/ownership arrays (the same fields
        ``build_solver_data`` derives from ``ClientCost``/``BoundState``,
        whose per-client Python loops this path exists to avoid); tracker
        initials mirror ``BoundState``'s cold-start values.  Use
        ``fresh_carry()`` for the matching initial carry."""
        self = cls.__new__(cls)
        self.exp = None
        self.policy = policy
        self.K = store.K
        self.mods = list(store.modalities)
        self.V = float(V)
        self.staleness = float(staleness)
        self.trace_count = 0
        self._init_zeta, self._init_delta = float(init_zeta), float(init_delta)

        has = np.stack([np.asarray(store.has_modality[m], bool)
                        for m in self.mods])
        sizes = np.asarray(store.sizes, np.float64)
        wbar = agg.stacked_weights(sizes, {m: has[i] for i, m in
                                           enumerate(self.mods)})
        tmpl = {
            "Q": np.zeros(self.K),
            "gamma": np.asarray(store.gamma_bits, np.float64),
            "h": np.zeros(self.K),
            "tau_rem": params.tau_max - np.asarray(store.tau_cmp, np.float64),
            "tau_cmp": np.asarray(store.tau_cmp, np.float64),
            "e_cmp": np.asarray(store.e_cmp, np.float64),
            "B_max": float(params.B_max),
            "p_tx": float(params.p_tx),
            "N0": float(params.N0),
            "V": float(V), "eta": float(eta), "rho": float(rho),
            "zeta2": np.full(len(self.mods), init_zeta ** 2),
            "delta2": np.full((len(self.mods), self.K), init_delta ** 2),
            "wbar": np.stack([wbar[m] for m in self.mods]),
            "has": has,
            "D": sizes,
        }
        self._solver_tmpl = to_device(tmpl)
        self._tau_max = float(params.tau_max)
        self._E_add = float(params.E_add)
        self._p_tx = float(params.p_tx)
        self._N0 = float(params.N0)

        self._store = jax.tree.map(jnp.asarray, store)
        gp = adapter.init_global(jax.random.key(seed))
        self._global_params0 = gp
        self._init_params = jax.tree.map(jnp.asarray, gp)
        self._cohort = adapter.cohort_step(tuple(self.mods))
        self._eval_logits = adapter.eval_logits
        # eval context: client 0's shard stands in as the held-out split —
        # population benches never flag an eval round, but lax.cond still
        # traces both branches, so the program needs *some* test tensors
        self._test_set = ({m: self._store.features[m][0] for m in self.mods},
                          self._store.labels[0])
        self._compile()
        return self

    def _compile(self):
        # drop-mask row -> engine modality index, for policies with dropout
        # (step_full's mask rows follow policy.drop_mods; empty otherwise)
        self._drop_rows = {m: i for i, m in
                           enumerate(getattr(self.policy, "drop_mods", ()))}
        self._jit_step = jax.jit(self._round_step)
        self._jit_scan = jax.jit(self._scan_steps)
        self._sharded_vsweep_cache = {}     # cache key -> jitted sweep

    # ------------------------------------------------------------------
    # host state ↔ carry
    # ------------------------------------------------------------------
    def init_carry(self) -> FusedCarry:
        exp = self.exp
        f32 = lambda x: jnp.asarray(x, jnp.float32)     # noqa: E731
        return FusedCarry(
            params=jax.tree.map(jnp.asarray, exp.global_params),
            policy={k: jnp.asarray(v)
                    for k, v in exp.scheduler.state().items()},
            Q=f32(exp.queues.Q), spent=f32(exp.queues.spent),
            zeta=f32([exp.bound.zeta[m] for m in self.mods]),
            delta=f32(np.stack([exp.bound.delta[m] for m in self.mods])),
            model_dist=f32(exp.model_dist))

    def fresh_carry(self) -> FusedCarry:
        """Cold-start carry for a ``from_store`` engine (no host experiment
        to mirror): fresh globals, empty queues, ``BoundState``-style tracker
        initials."""
        M = len(self.mods)
        f32 = lambda x: jnp.asarray(x, jnp.float32)     # noqa: E731
        return FusedCarry(
            params=jax.tree.map(jnp.asarray, self._global_params0),
            policy={k: jnp.asarray(v)
                    for k, v in self.policy.init_state().items()},
            Q=f32(np.zeros(self.K)), spent=f32(np.zeros(self.K)),
            zeta=f32(np.full(M, self._init_zeta)),
            delta=f32(np.full((M, self.K), self._init_delta)),
            model_dist=f32(np.zeros(self.K)))

    def round_params(self, carry: FusedCarry):
        """Round-boundary params export for live serving: the carry's global
        fusion params, straight off the device chain — no host mirror write
        (cf. ``export_carry``), so a serving process can hot-swap them into
        its donated buffer tree (``launch/continuous.py``) without waiting
        on the queue/tracker decode."""
        return carry.params

    def export_carry(self, carry: FusedCarry) -> None:
        """Write the carry back into the host-side mirrors (checkpointing,
        final_metrics, interop with the non-fused paths)."""
        exp = self.exp
        exp.global_params = carry.params
        exp.queues.Q = np.asarray(carry.Q, np.float64)
        exp.queues.spent = np.asarray(carry.spent, np.float64)
        exp.queues.t = exp._round
        for i, m in enumerate(self.mods):
            exp.bound.zeta[m] = float(carry.zeta[i])
            exp.bound.delta[m] = np.asarray(carry.delta[i], np.float64)
        exp.model_dist = np.asarray(carry.model_dist, np.float64)
        exp.scheduler.load_state(
            {k: np.asarray(v) for k, v in carry.policy.items()})

    # ------------------------------------------------------------------
    # the fused program
    # ------------------------------------------------------------------
    def _round_step(self, carry: FusedCarry, xs: RoundXs, store, test_set,
                    overrides=None, axis_name: Optional[str] = None):
        """One round.  ``store`` is the (possibly shard-local)
        ``ClientStore``; ``axis_name`` names the mesh axis the store and the
        per-client xs leaves are sharded over (None = single device /
        replicated).  Cohort compute is replicated across the client axis —
        only the O(K·N·d) store and the O(R·K) randomness shard.

        ``overrides`` replaces solver-template entries for this round (a
        vmapped V — or, for scenario grids, any per-scenario context:
        gamma/tau_rem/tau_cmp/e_cmp/has/D/wbar...); ``test_set`` is the
        ``(features, labels)`` held-out split the round evaluates on — the
        engine's own, or a scenario's in a scenario grid."""
        self.trace_count += 1
        tf, tl = test_set

        # 0. under a client-sharded mesh the *vector* physics stays dense +
        # replicated: reassemble the full channel draw from the shards
        h = xs.h if axis_name is None else \
            lax.all_gather(xs.h, axis_name, tiled=True)

        # 1. server decision: the scheduler's traced policy core (JCSBA's
        # population-batched solve, or a baseline's traced schedule) — the
        # policy state (warm start / cursor / ...) threads through the carry
        data = dict(self._solver_tmpl)
        if overrides:
            data.update(overrides)      # e.g. a vmapped V for scenario sweeps
        data["Q"], data["h"] = carry.Q, h
        data["zeta2"] = jnp.square(carry.zeta)
        data["delta2"] = jnp.square(carry.delta)
        if axis_name is not None and hasattr(self.policy, "hp"):
            # the KKT B_min bisection is the solver's only per-client
            # *compute* (30 fixed iterations × K): run it shard-locally on
            # this shard's slice and all_gather — elementwise, so bit-exact
            K_loc = xs.h.shape[0]
            off = lax.axis_index(axis_name) * K_loc
            sl = lambda x: lax.dynamic_slice_in_dim(x, off, K_loc)  # noqa: E731
            bl, okl = _bmin(sl(data["gamma"]), xs.h, sl(data["tau_rem"]),
                            data["B_max"], data["p_tx"], data["N0"],
                            self.policy.hp)
            data["bmin"] = lax.all_gather(bl, axis_name, tiled=True)
            data["bmin_ok"] = lax.all_gather(okl, axis_name, tiled=True)
        pstate, a, B, J, drop_rows, idx = self.policy.step_full(
            carry.policy, data, carry.model_dist,
            jax.random.PRNGKey(xs.draw_seed))

        # 2. latency feasibility (C4): scheduled-but-late ⇒ failure — energy
        # is spent, nothing is uploaded
        r = rate(jnp.maximum(B, B_LO), h, self._p_tx, self._N0)
        tcom = jnp.where(a, data["gamma"] / jnp.maximum(r, 1e-30), 0.0)
        ok = a & (tcom + data["tau_cmp"] <= self._tau_max + 1e-12)

        # 3. cohort gather + masked BGD updates (Eq. 7) on the [J] stack.
        # The policy's index vector lists scheduled clients first (ascending)
        # with unscheduled padding; ``ok_c`` masks failures and padding alike,
        # so a padding slot contributes exact zeros everywhere downstream.
        if axis_name is None:
            cohort = store.take(idx)
            seeds_c = jnp.take(xs.client_seeds, idx)
        else:
            cohort = jax.tree.map(
                lambda x: _gather_rows(x, idx, axis_name), store)
            seeds_c = _gather_rows(xs.client_seeds, idx, axis_name)
        Jc = idx.shape[0]
        ok_c = jnp.take(ok, idx)
        drop = {m: drop_rows[i] for m, i in self._drop_rows.items()
                if m in self.mods}       # empty for policies without dropout
        drop_c = {m: jnp.take(d, idx) for m, d in drop.items()}
        upload_c = agg.upload_masks_traced(ok_c, cohort.has_modality, drop_c)
        avail_c = {m: upload_c[m].astype(jnp.float32) for m in self.mods}

        def run_cohort(args):
            params, avail, seeds = args
            newp, grads, _totals, dist_sq = self._cohort(
                params, self._init_params, cohort.features, cohort.labels,
                cohort.sample_mask, avail, seeds)
            return newp, grads, dist_sq

        def skip_cohort(args):
            params, _avail, _seeds = args
            newp = jax.tree.map(
                lambda p: jnp.broadcast_to(p, (Jc,) + p.shape), params)
            return (newp, jax.tree.map(jnp.zeros_like, newp),
                    {m: jnp.zeros(Jc, jnp.float32) for m in self.mods})

        newp_c, grads_c, dist_sq_c = lax.cond(
            ok.any(), run_cohort, skip_cohort,
            (carry.params, avail_c, seeds_c))

        # 4. Eq. 12 aggregation on the cohort stack + ζ/δ tracker refresh.
        # Every contributor is in the cohort by construction, so the weight
        # renormalisation over J equals the dense one over K; the dense [K]
        # weight rows the aux records keep are the segment-sum scatter.
        # The trackers consume the per-modality gradient Gram matrix
        # G = Σ_leaves X Xᵀ [J, J]: ζ² = wᵀGw and δ_j² = G_jj − 2(Gw)_j +
        # wᵀGw, so the refresh needs no aggregated-gradient pytree and no
        # second O(J·|θ|) reduction pass over the gradient stack.
        w_c = agg.stacked_weights_traced(cohort.sizes, upload_c)
        new_params = agg.aggregate_stacked_traced(carry.params, newp_c, w_c)
        w = agg.cohort_weights_dense(w_c, idx, self.K)
        zs, ds = [], []
        for i, m in enumerate(self.mods):
            z_m, d_m = tracker_update_gram(
                carry.zeta[i], carry.delta[i], grad_gram(grads_c[m]),
                w_c[m], upload_c[m], idx, data["has"][i], self.staleness)
            zs.append(z_m)
            ds.append(d_m)

        # 5. Lyapunov queue recursion (§V-A) + energy accounting
        used = a.astype(jnp.float32) * (self._p_tx * tcom + data["e_cmp"])
        Qn = queue_update(carry.Q, used, self._E_add)
        spent = carry.spent + used

        # 6. ‖θ_k − θ⁰‖ for participants (Selection-scheduler bookkeeping):
        # cohort-local distances scattered back to the dense row
        d_sq_c = sum(dist_sq_c[m] * avail_c[m] for m in self.mods)
        dist_k = agg.scatter_cohort_rows(
            jnp.where(ok_c, jnp.sqrt(d_sq_c), 0.0), idx, self.K)
        model_dist = jnp.where(ok, dist_k, carry.model_dist)

        # 7. device-resident eval of the fresh globals on the held-out split
        # (the host loop's adapter.evaluate, fused behind the cadence flag —
        # only the branch that actually runs costs anything at runtime)
        metrics = lax.cond(
            xs.eval_flag,
            lambda p: eval_metrics(p, tf, tl, logits_fn=self._eval_logits),
            lambda p: nan_metrics(tf),
            new_params)

        new_carry = FusedCarry(new_params, pstate, Qn, spent,
                               jnp.stack(zs), jnp.stack(ds), model_dist)
        aux = RoundAux(a, ok, J, w, spent.sum(), drop, metrics, xs.eval_flag)
        return new_carry, aux

    def _scan_steps(self, carry: FusedCarry, xs: RoundXs, store, test_set):
        def body(c, x):
            return self._round_step(c, x, store, test_set)
        return lax.scan(body, carry, xs)

    # ------------------------------------------------------------------
    def step(self, carry: FusedCarry, xs: RoundXs):
        return self._jit_step(carry, xs, self._store, self._test_set)

    def scan(self, carry: FusedCarry, xs: RoundXs):
        """R rounds in one program; xs leaves carry a leading [R] axis.
        Compiles once per distinct R (then cached)."""
        return self._jit_scan(carry, xs, self._store, self._test_set)

    def lower(self, carry: FusedCarry, xs: RoundXs, scanned: bool = True):
        """The ``jax.stages.Lowered`` round program ``scan`` (or ``step``)
        runs — for memory analysis and for inspecting the compiled text."""
        fn = self._jit_scan if scanned else self._jit_step
        return fn.lower(carry, xs, self._store, self._test_set)

    def _scan_one_v(self, V, carry: FusedCarry, xs: RoundXs, store,
                    test_set, axis_name: Optional[str] = None):
        return self._scan_one_scenario({"V": V}, store, test_set, carry, xs,
                                       axis_name=axis_name)

    def _scan_one_scenario(self, overrides, store, test_set,
                           carry: FusedCarry, xs: RoundXs,
                           axis_name: Optional[str] = None):
        """One scenario's whole experiment: R rounds under ``lax.scan`` with
        this scenario's solver-data overrides / store / test split.  The unit
        ``scan_scenario_grid`` vmaps and shards."""
        def body(c, x):
            return self._round_step(c, x, store, test_set,
                                    overrides=overrides, axis_name=axis_name)
        return lax.scan(body, carry, xs)

    def scan_scenario_grid(self, overrides, carry: FusedCarry, xs: RoundXs,
                           stores=None, test_sets=None, mesh="auto"):
        """Whole experiments over an arbitrary *scenario* grid — the
        generalization of ``scan_v_grid`` from a V-line to a zoo.

        ``overrides`` is a dict of stacked solver-data entries, every value
        carrying a leading [S] scenario axis over the per-round shapes
        (``V`` → [S], ``gamma``/``tau_rem``/``tau_cmp``/``e_cmp``/``D`` →
        [S, K], ``has``/``wbar`` → [S, M, K]); each scenario's row replaces
        the engine's solver template for its entire experiment
        (``data/scenarios.py::stack_scenarios`` assembles exactly this dict
        from ``ScenarioSpec``s).  ``stores`` optionally stacks per-scenario
        ``ClientStore``s ([S]-leading leaves — scenarios must share K, N and
        the modality set; None = every scenario reads the engine's resident
        store) and ``test_sets`` an ``(features, labels)`` pair with
        [S]-leading leaves for per-scenario eval.  All scenarios share the
        initial carry and the per-round randomness ``xs`` — the controlled-
        comparison convention ``scan_v_grid`` established.

        Runs as one ``jit(vmap(scan))``; on a multi-device 1-D
        ``("scenario",)`` mesh the scenario axis (grid rows, stores, test
        sets alike) shards over devices via ``shard_map`` — bit-exact vs the
        single-device vmap (tests/test_scenarios.py).  The 2-D
        ``("scenario", "clients")`` population mesh is V-grid-only: a
        client-sharded store cannot also carry a scenario axis — use
        ``scan_v_grid`` there."""
        ovr = to_device(dict(overrides))
        n_S = next(iter(ovr.values())).shape[0]
        for k, v in ovr.items():
            if v.shape[0] != n_S:
                raise ValueError(
                    f"override {k!r} has scenario axis {v.shape[0]}, "
                    f"expected {n_S}")
        store_arg = self._store if stores is None else \
            jax.tree.map(jnp.asarray, stores)
        ts_arg = self._test_set if test_sets is None else \
            jax.tree.map(jnp.asarray, test_sets)
        if mesh == "auto":
            mesh = make_sweep_mesh()
        key = ("scenario", None if mesh is None else mesh,
               tuple(sorted(ovr)), stores is None, test_sets is None)
        if mesh is None or mesh.devices.size <= 1:
            fn = self._sharded_vsweep_cache.get(key)
            if fn is None:
                fn = jax.jit(jax.vmap(
                    self._scan_one_scenario,
                    in_axes=(0, None if stores is None else 0,
                             None if test_sets is None else 0, None, None)))
                self._sharded_vsweep_cache[key] = fn
            return fn(ovr, store_arg, ts_arg, carry, xs)
        if "clients" in mesh.axis_names:
            raise ValueError(
                "scan_scenario_grid supports 1-D ('scenario',) meshes only; "
                "the 2-D ('scenario', 'clients') population mesh shards the "
                "client store itself — run V-only grids there via "
                "scan_v_grid")
        n_dev = mesh.devices.size
        ovr = pad_leading_axis(ovr, n_dev)
        sharded = [0]
        if stores is not None:
            store_arg = pad_leading_axis(store_arg, n_dev)
            sharded.append(1)
        if test_sets is not None:
            ts_arg = pad_leading_axis(ts_arg, n_dev)
            sharded.append(2)
        fn = self._sharded_vsweep_cache.get(key)
        if fn is None:
            vm = jax.vmap(
                self._scan_one_scenario,
                in_axes=(0, None if stores is None else 0,
                         None if test_sets is None else 0, None, None))
            fn = jax.jit(scenario_shard_map(vm, mesh, n_args=5,
                                            sharded_args=tuple(sharded)))
            self._sharded_vsweep_cache[key] = fn
        carries, auxs = fn(ovr, store_arg, ts_arg, carry, xs)
        return (slice_leading_axis(carries, n_S),
                slice_leading_axis(auxs, n_S))

    def scan_v_grid(self, V_grid, carry: FusedCarry, xs: RoundXs,
                    mesh="auto"):
        """Whole *experiments* over a drift-penalty grid: every V in
        ``V_grid`` runs the full R-round experiment (same initial carry, same
        channel/dropout randomness — the paper's Fig.-4 controlled V study)
        under one ``jit(vmap(scan))``.  Returns (final carries, auxs) with a
        leading [len(V_grid)] axis.  This is the dense V-frontier workload
        the split pipeline cannot express without n_V × R host round-trips.

        Meshes: ``mesh="auto"`` builds a 1-D ``("scenario",)`` mesh over all
        local devices (``launch.mesh.make_sweep_mesh``), ``mesh=None`` forces
        the single-device vmap, or pass an explicit mesh.  A 1-D mesh shards
        the scenario axis only — pure SPMD fan-out (``scenario_shard_map``).
        A 2-D ``("scenario", "clients")`` mesh
        (``launch.mesh.make_population_mesh``) additionally partitions the
        client store and the per-client randomness over the ``"clients"``
        axis (specs from ``launch.sharding.logical_pspec``): each shard holds
        K/n_clients rows of every O(K·N·d) leaf, the round body gathers
        cohorts via masked psums and keeps cohort compute replicated.  Grids
        that don't divide the scenario axis are padded by repeating the last
        V and sliced back; K must divide the clients axis.  Sharded and
        single-device runs produce the same results
        (tests/test_sharded_sweep.py, tests/test_cohort_gather.py)."""
        V = jnp.asarray(V_grid, jnp.float32)
        if mesh == "auto":
            mesh = make_sweep_mesh()
        if mesh is None or mesh.devices.size <= 1 or \
                "clients" not in mesh.axis_names:
            # V is just the simplest scenario grid — one overridden solver
            # entry, engine store and test split shared by every row
            return self.scan_scenario_grid({"V": V}, carry, xs, mesh=mesh)
        fn, args = self._population_sweep(V, carry, xs, mesh)
        carries, auxs = fn(*args)
        return (slice_leading_axis(carries, V.shape[0]),
                slice_leading_axis(auxs, V.shape[0]))

    def lower_v_grid(self, V_grid, carry: FusedCarry, xs: RoundXs, mesh):
        """The ``jax.stages.Lowered`` program ``scan_v_grid`` runs on a 2-D
        ``("scenario", "clients")`` mesh; its compiled ``input_shardings``
        show which device holds which slice of the store and of the
        per-client randomness."""
        fn, args = self._population_sweep(
            jnp.asarray(V_grid, jnp.float32), carry, xs, mesh)
        return fn.lower(*args)

    def _population_sweep(self, V, carry: FusedCarry, xs: RoundXs, mesh):
        """The jitted 2-D-mesh sweep and its arguments (V padded to the
        scenario axis)."""
        n_cl = int(mesh.shape["clients"])
        if self.K % n_cl:
            raise ValueError(
                f"K={self.K} must divide the mesh's clients axis "
                f"({n_cl} shards)")
        Vp = pad_leading_axis(V, int(mesh.shape["scenario"]))
        fn = self._sharded_vsweep_cache.get(mesh)
        if fn is None:
            vm = jax.vmap(
                functools.partial(self._scan_one_v, axis_name="clients"),
                in_axes=(0, None, None, None, None))
            xs_spec = RoundXs(
                h=logical_pspec(("rounds", "clients"), mesh),
                draw_seed=logical_pspec(("rounds",), mesh),
                client_seeds=logical_pspec(("rounds", "clients"), mesh),
                eval_flag=logical_pspec(("rounds",), mesh))
            fn = jax.jit(population_shard_map(
                vm, mesh,
                in_specs=(logical_pspec(("scenario",), mesh), P(),
                          xs_spec, logical_pspec(("clients",), mesh), P()),
                out_specs=logical_pspec(("scenario",), mesh)))
            self._sharded_vsweep_cache[mesh] = fn
        return fn, (Vp, carry, xs, self._store, self._test_set)

    # ------------------------------------------------------------------
    def run(self, carry: FusedCarry, xs: RoundXs, scanned: bool):
        """Execute and time; returns (carry, aux-on-host, wall seconds)."""
        t0 = time.perf_counter()
        if scanned:
            carry, aux = self.scan(carry, xs)
        else:
            carry, aux = self.step(carry, xs)
        aux = jax.tree.map(np.asarray, jax.block_until_ready(aux))
        return carry, aux, time.perf_counter() - t0
