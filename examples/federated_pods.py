"""Pods-as-clients: the paper's JCSBA scheduler driving LM-scale federated
training — the technique as a first-class feature of the distributed runtime
(DESIGN.md §4, hardware adaptation).

8 simulated "pods" (FL clients) each hold a shard of the token stream and a
reduced qwen3-0.6b replica.  Each round: the wireless layer simulates the
inter-site links (gains redrawn per round), JCSBA picks the pods and their
bandwidth under the latency/energy budget, the chosen pods take a local
AdamW step, and per-parameter federated averaging aggregates.  This is M=1
in the paper's notation — the unimodal degenerate case the bound still
covers (A2 only).

  PYTHONPATH=src python examples/federated_pods.py --rounds 12
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.aggregation import unified_weights
from repro.core.convergence import BoundState
from repro.data.tokens import TokenStream
from repro.launch import steps
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adamw
from repro.wireless import cost as wcost
from repro.wireless.channel import Channel
from repro.wireless.lyapunov import EnergyQueues
from repro.wireless.params import WirelessParams
from repro.wireless.schedulers import ScheduleContext, JCSBAScheduler


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--pods", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config("qwen3-0.6b").reduced()
    K = args.pods
    rng = np.random.default_rng(0)

    # model upload size: a pod pushes its delta every round
    params = steps.init_fn(cfg)(jax.random.key(0))
    n_params = steps.param_count(params)
    model_bits = n_params * 16                       # bf16 on the wire

    # wireless layer: inter-site links; τ budget scaled to the model size
    P = WirelessParams(K=K, tau_max=2.0, B_max=200e6, E_add=5.0,
                       extra_gain_db=60.0)
    mods = [("lm",)] * K
    profile = {"lm": (float(model_bits), 5e5)}
    sizes = [args.batch * args.seq] * K
    cc = wcost.client_costs(sizes, mods, profile, P)
    ch = Channel(P, rng)
    queues = EnergyQueues(K)
    w = unified_weights(sizes, mods, ["lm"])
    bound = BoundState(K, ["lm"], mods, w, sizes)
    sched = JCSBAScheduler(rng, V=1.0)

    opt = adamw(3e-4)
    opt_state = opt.init(params)
    step_fn = jax.jit(steps.make_train_step(cfg, opt, attn_chunk=64))
    streams = [TokenStream(cfg.vocab_size, seed=k) for k in range(K)]

    for t in range(args.rounds):
        h = ch.draw()
        ctx = ScheduleContext(h=h, Q=queues.Q, cost=cc, params=P,
                              bound=bound, round_idx=t,
                              model_dist=np.zeros(K),
                              client_modalities=mods)
        dec = sched.schedule(ctx)
        part = np.flatnonzero(dec.a)
        tcom = wcost.com_latency(dec.B, h, cc.gamma_bits, P)
        ecom = wcost.com_energy(tcom, P)

        # each scheduled pod takes a local step from the global params;
        # aggregation = data-size-weighted average of the updated replicas
        grads_by_pod = []
        new_params_acc = None
        wsum = 0.0
        loss_round = []
        for k in part:
            b = streams[k].batch(args.batch, args.seq)
            batch = {kk: jnp.asarray(v) for kk, v in b.items()}
            newp, _, loss = step_fn(params, opt_state, batch)
            loss_round.append(float(loss))
            wk = sizes[k]
            wsum += wk
            contrib = jax.tree.map(lambda x: wk * x.astype(jnp.float32), newp)
            new_params_acc = contrib if new_params_acc is None else \
                jax.tree.map(jnp.add, new_params_acc, contrib)
            gk = jax.tree.map(lambda a_, b_: (a_ - b_), newp, params)
            grads_by_pod.append({"lm": gk})
        if new_params_acc is not None:
            params = jax.tree.map(
                lambda acc, old: (acc / wsum).astype(old.dtype),
                new_params_acc, params)
            agg = {"lm": jax.tree.map(
                lambda *g: sum(g) / len(g),
                *[gb["lm"] for gb in grads_by_pod])}
            full = [({"lm": gb["lm"]} if i < len(grads_by_pod) else None)
                    for i, gb in enumerate(grads_by_pod)]
            bound.update(full + [None] * (K - len(full)), agg)
        queues.step(dec.a.astype(float), ecom, cc.e_cmp, P.E_add)
        print(f"round {t:3d} pods={part.tolist()} "
              f"loss={np.mean(loss_round) if loss_round else float('nan'):.4f} "
              f"E={queues.spent.sum():.2f}J")
    print("done — JCSBA scheduled pods under link/energy budgets (M=1 case)")


if __name__ == "__main__":
    main()
