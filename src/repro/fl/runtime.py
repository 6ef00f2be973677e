"""The wireless MFL loop — Algorithm 1 of the paper.

Per communication round t:
  1. redraw channel gains h_k;
  2. the server solves the scheduling/bandwidth problem (JCSBA or a baseline).
     JCSBA runs on the population-batched solver (``wireless.solver``) — one
     fused jitted program per round evaluating the whole immune population;
     the engine spec's backend suffix (``engine="batched:np"`` /
     ``"seq:seq"``) selects its float64 numpy mirror or the original
     sequential scalar path (see ``schedulers.JCSBAScheduler``);
  3. scheduled clients run the local update (one BGD epoch, Eq. 7) — clients
     whose latency constraint is violated under the chosen bandwidth are
     *transmission failures*: they consume energy but contribute no update
     (this is what punishes the naive equal-bandwidth baselines);
  4. per-modality aggregation with participated weights (Eq. 12);
  5. Lyapunov queues and the Theorem-1 ζ/δ trackers are updated;
  6. test metrics (multimodal + per-modality accuracy) are recorded.

Round engines (``engine=`` — "seq" | "batched" | "fused")
---------------------------------------------------------
One kwarg selects how rounds execute; a ``":<backend>"`` suffix picks the
JCSBA solver backend for parity studies (``"batched:np"`` — float64 numpy
mirror, ``"seq:seq"`` — the original scalar path; default jax).

Batched round engine (default, ``engine="batched"``)
----------------------------------------------------
Step 3 historically re-entered JAX once per scheduled client.  The batched
engine instead executes *all* K clients' one-epoch BGD updates as a single
jitted ``jax.vmap`` over a dense, device-resident client stack, making the
round — not the client — the unit of compute:

* **Padding.** At experiment init the cohort is stacked into a
  ``data.partition.StackedClients``: every modality is materialised for every
  client at a fixed ``max_batch`` (the largest shard), ragged shards are
  zero-padded, and a ``sample_mask`` [K, N] marks real samples.  Shapes are
  round-invariant, so the step compiles exactly once.
* **Masking.** A per-modality 0/1 *upload mask* [K] (scheduled ∧ no
  transmission failure ∧ owns the modality ∧ did not drop it) replaces the
  sequential path's skip-the-dict-key convention: a masked-out modality
  contributes exactly zero to the fused loss (core.fusion), hence exactly
  zero gradient, and is excluded from Eq. 12 by the same mask
  (core.aggregation.stacked_weights / aggregate_stacked).  Dropout draws
  per-sample keys (models.paper_models), so padding never perturbs the
  masks of real samples.
* **Equivalence.** With the same seed and schedule, the batched and
  sequential paths produce identical Eq. 12 weights and globally aggregated
  params up to float32 reduction order (tests/test_batched_equivalence.py).
  The sequential loop is kept behind ``engine="seq"`` for exactly this A/B.

Fused round engine (``engine="fused"``)
---------------------------------------
The batched engine still hops to host between the jitted solver and the
jitted client stage every round.  ``engine="fused"`` runs the *whole* round —
steps 1-6 above, test metrics included via the device-resident ``fl.eval``
pass — as one jitted program (fl/fused_round.py) for every scheduler with a
traced policy core (jcsba / random / round_robin / selection / dropout —
see ``wireless.policies``; only the np/seq JCSBA parity backends are
excluded): ``run_round`` becomes a thin host wrapper that pregenerates the
round's randomness, calls the fused step and decodes the traced schedule /
drop-mask / metric arrays into a JSON-safe RoundRecord; ``run_scanned(R)``
drives R rounds under a single ``lax.scan``.  Per-round host rng consumption
is static (see ``_draw_client_seeds``; every policy draws exactly one solver
seed per round), so all engines consume the identical stream and stay
equivalent round by round (tests/test_fused_round.py, parametrized over all
five policies).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Dict, List, Optional

import jax
import numpy as np

from ..core import aggregation as agg
from ..core.convergence import BoundState
from ..data import synthetic
from ..data.partition import partition, train_test_split
from ..wireless import cost as wcost
from ..wireless.channel import Channel
from ..wireless.lyapunov import EnergyQueues
from ..wireless.params import MODALITY_PROFILES, WirelessParams
from ..wireless.schedulers import (ScheduleContext, Scheduler, make_scheduler)
from .client import make_adapter


def _span(name: str, round0: int):
    """A host phase of a fused call as a profiler span (free unless a
    ``jax.profiler.trace`` is active), tagged with the call's first round."""
    return jax.profiler.TraceAnnotation(name, round0=round0)


def jnp_or_np(x):
    """Record/JSON-boundary normalizer: accepts jnp OR np values (e.g. fields
    produced under jit) and returns plain Python objects — 0-d arrays become
    scalars, 1-d+ arrays become lists, containers recurse.  Every
    ``RoundRecord`` is built through this so device arrays never leak into
    ``json.dump`` of histories or checkpoint manifests (regression test in
    tests/test_fused_round.py)."""
    if isinstance(x, dict):
        return {k: jnp_or_np(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [jnp_or_np(v) for v in x]
    if hasattr(x, "ndim"):
        x = np.asarray(x)
        return x.item() if x.ndim == 0 else x.tolist()
    return x


@dataclasses.dataclass
class RoundRecord:
    round: int
    participants: List[int]
    failures: List[int]
    energy_total: float
    metrics: Dict[str, float]
    #: host scheduler wall time; NaN on the fused path, which has no
    #: scheduler time of its own (the ``mfl.*`` profiler spans of
    #: ``run_scanned`` / ``run_round`` time its host phases instead)
    sched_time_s: float
    #: modality -> sorted clients that dropped it this round ([28]'s
    #: modality-dropout baseline; empty for every other policy)
    dropped: Dict[str, List[int]] = dataclasses.field(default_factory=dict)

    @classmethod
    def make(cls, round, participants, failures, energy_total, metrics,
             sched_time_s, dropped=None) -> "RoundRecord":
        """The one constructor both round engines use — normalizes every
        field through ``jnp_or_np`` so records are always JSON-safe."""
        return cls(int(jnp_or_np(round)),
                   [int(v) for v in jnp_or_np(list(participants))],
                   [int(v) for v in jnp_or_np(list(failures))],
                   float(jnp_or_np(energy_total)),
                   {k: float(jnp_or_np(v)) for k, v in metrics.items()},
                   float(jnp_or_np(sched_time_s)),
                   {str(m): sorted(int(k) for k in ks)
                    for m, ks in (dropped or {}).items()})


#: valid ``engine=`` loop names, in increasing fusion order
ENGINE_LOOPS = ("seq", "batched", "fused")

#: valid "+"-joined engine-spec backend tokens after the ":" — JCSBA solver
#: parity backends ('np'/'seq'), the Pallas hot path ('pallas': custom-VJP
#: fusion loss, plus kernel-backed mixers for the backbone adapters) and
#: per-client activation checkpointing ('remat')
ENGINE_TOKENS = ("jax", "np", "seq", "pallas", "remat")


def parse_engine(engine: str):
    """``"<loop>[:<token>[+<token>...]]"`` → (loop, solver_backend,
    loss_backend, remat, use_kernels, canonical spec).

    Examples: ``"fused"``, ``"batched:np"``, ``"fused:pallas"``,
    ``"fused:remat"``, ``"fused:pallas+remat"``."""
    loop, _, rest = engine.partition(":")
    if loop not in ENGINE_LOOPS:
        raise ValueError(
            f"unknown engine {engine!r}; expected "
            f"'seq' | 'batched' | 'fused' with an optional "
            f"':<token>[+<token>...]' suffix from {ENGINE_TOKENS} "
            f"(a jcsba solver backend 'np'/'seq', 'pallas' for the "
            f"kernel-backed hot path, 'remat' for per-client "
            f"activation checkpointing)")
    tokens = [t for t in rest.split("+") if t] if rest else []
    for t in tokens:
        if t not in ENGINE_TOKENS:
            raise ValueError(
                f"unknown engine token {t!r} in {engine!r}; "
                f"choose from {ENGINE_TOKENS}")
    solver = [t for t in tokens if t in ("np", "seq")]
    if len(solver) > 1:
        raise ValueError(f"conflicting solver backends in {engine!r}")
    solver_backend = solver[0] if solver else "jax"
    loss_backend = "pallas" if "pallas" in tokens else "xla"
    remat = "remat" in tokens
    canon = loop + (":" + "+".join(tokens) if tokens else ":jax")
    return loop, solver_backend, loss_backend, remat, "pallas" in tokens, canon


class MFLExperiment:
    def __init__(self, dataset: str = "crema_d", scheduler: str = "jcsba",
                 K: int = 10, omega: float = 0.3, n_samples: int = 1200,
                 dirichlet_alpha: float = 0.0,
                 eta: float = 0.05, V: float = 1.0, seed: int = 0,
                 params: Optional[WirelessParams] = None,
                 scheduler_kwargs: Optional[dict] = None,
                 eval_every: int = 1, engine: str = "batched",
                 arch: str = "lstm-cnn"):
        # engine-spec token routing: 'pallas' selects the custom-VJP Pallas
        # fusion-loss on the client BGD hot path (kernels/fusion_loss) —
        # and, for the backbone adapters, the kernel-backed mixers too —
        # leaving the JCSBA solver on its traced 'jax' core; 'np'/'seq'
        # remain the host-side JCSBA parity solvers on the XLA loss; 'remat'
        # activation-checkpoints each client's loss in the cohort step.
        (loop, solver_backend, loss_backend, remat, use_kernels,
         self.engine) = parse_engine(engine)
        self.rng = np.random.default_rng(seed)
        self.params = params or WirelessParams(K=K)
        self.eval_every = eval_every
        self.batched = loop == "batched"
        self.fused = loop == "fused"
        self._fused_engine = None           # built lazily (fl/fused_round.py)
        self._carry = None                  # FusedCarry when fused
        self._stacked_dev = None            # device-resident client stack
        self._stacked_src = None            # cohort it was built from
        self._store_dev = None              # device-resident ClientStore
        self._store_src = None              # cohort it was built from

        full = synthetic.DATASETS[dataset](seed=seed, n=n_samples)
        self.train_ds, self.test_ds = train_test_split(full, 0.2, seed)
        self.clients = partition(self.train_ds, K, omega, seed,
                                 dirichlet_alpha=dirichlet_alpha)
        self.all_mods = sorted(full.features.keys())
        self.client_mods = [c.modalities for c in self.clients]
        self.data_sizes = [c.size for c in self.clients]
        self.profile = MODALITY_PROFILES[dataset]

        # the model-family axis: 'lstm-cnn' (the paper's submodels) or a
        # transformer/SSD encoder stack (fl/client.py::make_adapter)
        self.arch = arch
        self.adapter = make_adapter(dataset, arch, eta=eta,
                                    loss_backend=loss_backend, remat=remat,
                                    use_kernels=use_kernels)
        self.global_params = self.adapter.init_global(jax.random.key(seed))
        self.init_params = jax.tree.map(lambda x: x, self.global_params)

        self.cost = wcost.client_costs(self.data_sizes, self.client_mods,
                                       self.profile, self.params)
        self.channel = Channel(self.params, self.rng)
        self.queues = EnergyQueues(K)
        w_bar = agg.unified_weights(self.data_sizes, self.client_mods,
                                    self.all_mods)
        self.bound = BoundState(K, self.all_mods, self.client_mods, w_bar,
                                self.data_sizes, eta=eta)
        self.w_bar = w_bar
        kw = dict(scheduler_kwargs or {})
        if scheduler == "jcsba":
            kw.setdefault("V", V)
            kw.setdefault("solver", solver_backend)
        self.scheduler: Scheduler = make_scheduler(scheduler, self.rng, **kw)
        self.scheduler.bind(K, self.client_mods)
        if self.fused and self.scheduler.policy is None:
            raise ValueError(
                f"engine='fused' requires a traced scheduling policy; "
                f"scheduler={scheduler!r} with backend={solver_backend!r} runs "
                f"host-side only (every scheduler has a traced core — "
                f"jcsba/random/round_robin/selection/dropout — except "
                f"JCSBA's np/seq parity backends)")
        self.model_dist = np.zeros(K)
        self.history: List[RoundRecord] = []
        self._round = 0

    # ------------------------------------------------------------------
    def run_round(self) -> RoundRecord:
        if self.fused:
            return self._run_round_fused()
        t = self._round
        K = self.params.K
        h = self.channel.draw()
        ctx = ScheduleContext(h=h, Q=self.queues.Q, cost=self.cost,
                              params=self.params, bound=self.bound,
                              round_idx=t, model_dist=self.model_dist,
                              client_modalities=self.client_mods)
        t0 = time.perf_counter()
        dec = self.scheduler.schedule(ctx)
        sched_time = time.perf_counter() - t0

        tcom = wcost.com_latency(dec.B, h, self.cost.gamma_bits, self.params)
        ecom = wcost.com_energy(tcom, self.params)
        ok = dec.a & (tcom + self.cost.tau_cmp <= self.params.tau_max + 1e-12)
        failures = sorted(np.flatnonzero(dec.a & ~ok))
        participants = sorted(np.flatnonzero(ok))

        # --- local updates + aggregation (Eq. 12) + trackers ---
        seeds = self._draw_client_seeds()
        if self.batched:
            w_t = self._round_batched(dec, participants, seeds)
        else:
            w_t = self._round_sequential(dec, participants, seeds)
        self.last_weights = w_t
        self.queues.step(dec.a.astype(float), ecom, self.cost.e_cmp,
                         self.params.E_add)

        metrics = {}
        if t % self.eval_every == 0:
            metrics = self.adapter.evaluate(self.global_params, self.test_ds)
        dropped: Dict[str, List[int]] = {}
        if dec.dropout_modality:
            for k, m in enumerate(dec.dropout_modality):
                if m is not None:
                    dropped.setdefault(m, []).append(k)
        rec = RoundRecord.make(t, participants, failures,
                               self.queues.spent.sum(), metrics, sched_time,
                               dropped)
        self.history.append(rec)
        self._round += 1
        return rec

    # ------------------------------------------------------------------
    # fused engine (fl/fused_round.py): the whole round as one jitted program
    # ------------------------------------------------------------------
    def _get_fused_engine(self):
        if self._fused_engine is None:
            from .fused_round import FusedRoundEngine
            self._fused_engine = FusedRoundEngine(self)
        if self._carry is None:
            self._carry = self._fused_engine.init_carry()
        return self._fused_engine

    def _decode_fused_round(self, t: int, aux) -> RoundRecord:
        """Host-side decoder: traced schedule/energy/eval arrays →
        RoundRecord.  Metrics come from the device-resident eval — real only
        on rounds the cadence flagged (``aux.eval_mask``); the NaN fillers of
        skipped rounds never reach a record.  ``sched_time_s`` is NaN: no
        scheduler time exists on the fused path."""
        a = np.asarray(aux.a, bool)
        ok = np.asarray(aux.ok, bool)
        self.last_weights = {m: np.asarray(aux.weights[m])
                             for m in self.all_mods}
        metrics = {}
        if bool(aux.eval_mask):
            metrics = {k: float(v) for k, v in aux.metrics.items()}
        dropped = {m: np.flatnonzero(np.asarray(d, bool))
                   for m, d in aux.drop.items()}
        return RoundRecord.make(t, sorted(np.flatnonzero(ok)),
                                sorted(np.flatnonzero(a & ~ok)),
                                aux.energy_total, metrics, float("nan"),
                                {m: ks for m, ks in dropped.items()
                                 if len(ks)})

    def _run_round_fused(self) -> RoundRecord:
        from .fused_round import draw_round_xs
        eng = self._get_fused_engine()
        t = self._round
        with _span("mfl.call", t):
            with _span("mfl.draw_xs", t):
                xs = jax.tree.map(lambda x: x[0], draw_round_xs(self, 1))
            self._carry, aux, _ = eng.run(self._carry, xs, scanned=False)
            with _span("mfl.decode", t):
                rec = self._decode_fused_round(t, aux)
                self.history.append(rec)
                self._round += 1
            # keep the public host-side mirrors (global_params, queues,
            # bound, model_dist) live — a device->host copy, not a
            # round-trip: the carry stays the compute chain's source of truth
            with _span("mfl.export", t):
                eng.export_carry(self._carry)
        return rec

    def run_scanned(self, rounds: int) -> List[RoundRecord]:
        """R rounds under a single ``lax.scan`` — one device program for the
        whole stretch.  Per-round randomness is pregenerated in the canonical
        stream order, so the result is identical to R ``run_round()`` calls
        (asserted bit-for-bit in tests/test_system.py).  Test metrics are
        evaluated *inside* the scan on every round of the ``eval_every`` grid
        (the device-resident ``fl.eval`` pass — intermediate global params
        still never materialise on host), so one scan yields the full
        accuracy curve.

        Under ``jax.profiler.trace`` each call leaves an ``mfl.call`` span
        holding, in order, ``mfl.draw_xs`` (the rounds' randomness),
        ``mfl.dispatch`` and ``mfl.fetch`` (``FusedRoundEngine.run``),
        ``mfl.decode`` (the records) and ``mfl.export`` (the host mirrors),
        all tagged ``round0`` = the call's first round."""
        if not self.fused:
            raise RuntimeError("run_scanned requires engine='fused'")
        from .fused_round import draw_round_xs
        eng = self._get_fused_engine()
        start = self._round
        with _span("mfl.call", start):
            with _span("mfl.draw_xs", start):
                xs = draw_round_xs(self, rounds)
            self._carry, auxs, _ = eng.run(self._carry, xs, scanned=True)
            with _span("mfl.decode", start):
                recs = [self._decode_fused_round(
                            start + i, jax.tree.map(lambda x: x[i], auxs))
                        for i in range(rounds)]
                self.history.extend(recs)
                self._round += rounds
            with _span("mfl.export", start):
                eng.export_carry(self._carry)   # host mirrors stay live
        return recs

    # ------------------------------------------------------------------
    # local-update fan-out: sequential (reference) vs batched (default)
    # ------------------------------------------------------------------
    def _draw_client_seeds(self) -> np.ndarray:
        """One dropout seed per client, every round, scheduled or not.

        The consumption pattern is *static* (K scalar draws per round), so the
        np-rng stream is independent of the schedule outcome — which lets the
        fused round engine (fl/fused_round.py) pregenerate the whole stream
        for a ``lax.scan`` over rounds while staying draw-for-draw identical
        to the host loop.  Client k always uses ``seeds[k]``."""
        return np.array([self.rng.integers(2 ** 31)
                         for _ in range(self.params.K)], np.uint32)

    def _round_sequential(self, dec, participants,
                          seeds: np.ndarray) -> Dict[str, np.ndarray]:
        """Reference path: one JAX re-entry per scheduled client."""
        K = self.params.K
        client_params: List[Optional[dict]] = [None] * K
        client_grads: List[Optional[dict]] = [None] * K
        for k in participants:
            drop = (dec.dropout_modality[k]
                    if dec.dropout_modality is not None else None)
            rng = jax.random.key(int(seeds[k]))
            newp, grads, _ = self.adapter.local_update(
                self.global_params, self.clients[k], rng, drop)
            client_params[k] = newp
            client_grads[k] = grads
            self.model_dist[k] = float(np.sqrt(sum(
                float(np.vdot(a - b, a - b).real)
                for a, b in zip(jax.tree.leaves(newp),
                                jax.tree.leaves({m: self.init_params[m]
                                                 for m in newp})))))

        # participated weights (Eq. 12), renormalised over what was actually
        # uploaded (a dropped modality is absent from the client's upload).
        w_t = agg.weights_from_uploads(self.data_sizes, client_params,
                                       self.all_mods)
        self.global_params = agg.aggregate(self.global_params, client_params,
                                           w_t)
        agg_grads = agg.aggregate_gradients(client_grads, w_t)
        self.bound.update(client_grads, agg_grads)
        return w_t

    def _round_batched(self, dec, participants,
                       seeds: np.ndarray) -> Dict[str, np.ndarray]:
        """Batched path: the whole cohort's updates in one jitted vmap."""
        K = self.params.K
        upload = {m: np.zeros(K, bool) for m in self.all_mods}
        for k in participants:
            drop = (dec.dropout_modality[k]
                    if dec.dropout_modality is not None else None)
            mods = tuple(m for m in self.client_mods[k] if m != drop)
            if not mods:
                mods = tuple(self.client_mods[k])
            for m in mods:
                upload[m][k] = True
        if not len(participants):
            return agg.stacked_weights(self.data_sizes, upload)

        feats, labels, smask = self._get_stacked()
        newp, grads, _totals, dist_sq = self.adapter.batched_local_update(
            self.global_params, self.init_params, feats, labels, smask,
            upload, seeds)

        w_t = agg.stacked_weights(self.data_sizes, upload)
        self.global_params = agg.aggregate_stacked(self.global_params, newp,
                                                   w_t)
        agg_grads = agg.aggregate_gradients_stacked(grads, w_t)
        self.bound.update_stacked(grads, upload, agg_grads)

        d_sq = np.zeros(K)
        for m in self.all_mods:
            d_sq += np.asarray(dist_sq[m]) * upload[m]
        part = np.asarray(participants, int)
        self.model_dist[part] = np.sqrt(d_sq[part])
        return w_t

    def _get_stacked(self):
        """Device-resident padded client stack, rebuilt if the cohort is
        swapped out (e.g. a non-IID repartition after init).  Keyed on the
        identities of the ClientData objects, so replacing the list *or*
        individual entries invalidates the cache; mutating a client's
        dataset arrays in place does not and is unsupported."""
        src = tuple(map(id, self.clients))
        if self._stacked_dev is None or self._stacked_src != src:
            import jax.numpy as jnp
            from ..data.partition import stack_clients
            sc = stack_clients(self.clients, self.all_mods)
            self._stacked_dev = (
                {m: jnp.asarray(x) for m, x in sc.features.items()},
                jnp.asarray(sc.labels), jnp.asarray(sc.sample_mask))
            self._stacked_src = src
        return self._stacked_dev

    def _get_store(self):
        """Device-resident ``ClientStore`` (the fused engine's population
        store; data/partition.py) — same cohort-identity invalidation
        contract as ``_get_stacked``."""
        src = tuple(map(id, self.clients))
        if self._store_dev is None or self._store_src != src:
            import jax.numpy as jnp
            from ..data.partition import build_client_store, stack_clients
            sc = stack_clients(self.clients, self.all_mods)
            store = build_client_store(sc, self.cost.gamma_bits,
                                       self.cost.tau_cmp, self.cost.e_cmp)
            self._store_dev = jax.tree.map(jnp.asarray, store)
            self._store_src = src
        return self._store_dev

    def run(self, rounds: int, verbose: bool = False) -> List[RoundRecord]:
        for _ in range(rounds):
            rec = self.run_round()
            if verbose and rec.metrics:
                acc = rec.metrics.get("multimodal", float("nan"))
                sched = ("" if np.isnan(rec.sched_time_s) else
                         f"sched={rec.sched_time_s * 1e3:.1f}ms ")
                print(f"[{self.scheduler.name}] round {rec.round:4d} "
                      f"acc={acc:.4f} E={rec.energy_total:.3f}J "
                      f"{sched}part={rec.participants}")
        return self.history

    # ------------------------------------------------------------------
    # checkpoint / resume (server state: global model + queues + trackers)
    # ------------------------------------------------------------------
    def save(self, path: str) -> str:
        from ..checkpoint import save_checkpoint
        if self.fused and self._carry is not None:
            # the carry is authoritative mid-fused-experiment: mirror it back
            # into the host-side state the checkpoint schema reads
            self._fused_engine.export_carry(self._carry)
        state = {
            "global_params": self.global_params,
            "queues_Q": self.queues.Q,
            "queues_spent": self.queues.spent,
            "delta": {m: self.bound.delta[m] for m in self.all_mods},
            "model_dist": self.model_dist,
            # the policy's own evolving state (JCSBA warm-start antibody,
            # Round-Robin cursor, ...) via the explicit checkpoint API
            "policy": self.scheduler.state(),
        }
        meta = {"round": self._round,
                "zeta": {m: float(self.bound.zeta[m]) for m in self.all_mods},
                "queues_t": self.queues.t}
        return save_checkpoint(path, state, step=self._round, metadata=meta)

    def restore(self, path: str) -> int:
        import jax.numpy as jnp
        from ..checkpoint import load_checkpoint
        state, manifest = load_checkpoint(path)
        self.global_params = jax.tree.map(
            jnp.asarray, state["global_params"])
        self.queues.Q = np.asarray(state["queues_Q"])
        self.queues.spent = np.asarray(state["queues_spent"])
        self.queues.t = manifest["metadata"]["queues_t"]
        for m in self.all_mods:
            self.bound.delta[m] = np.asarray(state["delta"][m])
            self.bound.zeta[m] = manifest["metadata"]["zeta"][m]
        self.model_dist = np.asarray(state["model_dist"])
        # policy state via the explicit API; stateless policies saved nothing
        # (the empty dict flattens away).  Pre-policy checkpoints stored the
        # JCSBA warm start as a top-level "warm_a" blob — still restored, but
        # deprecated: save() has written only the policy/ state dict since
        # the traced-policy layer landed, so re-saving migrates in place.
        pol = state.get("policy")
        if pol is None and "warm_a" in state:
            warnings.warn(
                "checkpoint uses the legacy top-level 'warm_a' warm-start "
                "blob; restored this time — re-save the experiment to "
                "migrate to the policy/ state-dict format (see README "
                "'Checkpoint migration')",
                DeprecationWarning, stacklevel=2)
            pol = {"warm_a": state["warm_a"]}
        if pol:
            self.scheduler.load_state(pol)
        self._round = manifest["step"]
        if self.fused:
            # rebuild the fused carry from the restored host state
            self._carry = None
            self._get_fused_engine()
        return self._round

    # ------------------------------------------------------------------
    def final_metrics(self) -> Dict[str, float]:
        for rec in reversed(self.history):
            if rec.metrics:
                out = dict(rec.metrics)
                out["energy_total"] = self.history[-1].energy_total
                sched = [r.sched_time_s for r in self.history
                         if np.isfinite(r.sched_time_s)]
                if sched:       # host engines only: fused records hold NaN
                    out["mean_sched_time_s"] = float(np.mean(sched))
                return out
        return {}
