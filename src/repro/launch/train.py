"""Training driver.

Modes:
* ``standard`` — data-parallel LM training of any registered arch.  On this
  CPU container use ``--reduced`` (2-block, tiny-dim variant of the same
  family); on a real TPU slice drop the flag and the production mesh +
  shardings from the dry-run path are used unchanged.
* ``federated`` — the paper's wireless-MFL loop (Algorithm 1) with
  pods-as-clients semantics: each FL client holds a shard of the token stream
  and the JCSBA scheduler decides which "pods" participate each round under
  the simulated wireless constraints.  (The faithful paper experiment with
  the LSTM/CNN models lives in examples/wireless_mfl.py.)

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \\
      --steps 50 --batch 8 --seq 256
  PYTHONPATH=src python -m repro.launch.train --mode federated --rounds 40
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..data.tokens import TokenStream, vlm_batch
from ..optim import warmup_cosine, adamw
from . import steps as S
from .compile_cache import enable_compile_cache


def train_standard(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"[train] arch={cfg.name} reduced={args.reduced} "
          f"layers={cfg.n_layers} d_model={cfg.d_model}")
    params = S.init_fn(cfg)(jax.random.key(args.seed))
    n_params = S.param_count(params)
    print(f"[train] params: {n_params/1e6:.2f}M")
    opt = adamw(warmup_cosine(args.lr, 10, args.steps))
    opt_state = opt.init(params)
    step_fn = jax.jit(S.make_train_step(cfg, opt, n_groups=1,
                                        attn_chunk=min(256, args.seq)))
    stream = TokenStream(cfg.vocab_size, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    losses = []
    for i in range(args.steps):
        if cfg.arch_type == "vlm":
            batch = vlm_batch(rng, args.batch, args.seq, 16,
                              cfg.frontend_dims[0], cfg.vocab_size)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
        else:
            b = stream.batch(args.batch, args.seq)
            batch = {k: jnp.asarray(v) for k, v in b.items()}
            if cfg.arch_type == "audio":
                batch["src_embeds"] = jnp.asarray(rng.normal(
                    size=(args.batch, 64, cfg.d_model)).astype(np.float32))
        t0 = time.time()
        params, opt_state, loss = step_fn(params, opt_state, batch)
        losses.append(float(loss))
        if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
            print(f"[train] step {i:4d} loss={float(loss):.4f} "
                  f"({time.time() - t0:.2f}s)")
    assert np.isfinite(losses).all(), "NaN loss"
    print(f"[train] first->last loss: {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses


def train_federated(args):
    from ..fl.runtime import MFLExperiment
    exp = MFLExperiment(dataset=args.dataset, scheduler=args.scheduler,
                        n_samples=args.n_samples, seed=args.seed, V=args.V)
    exp.run(args.rounds, verbose=True)
    print("[federated] final:", exp.final_metrics())
    return exp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="standard",
                    choices=["standard", "federated"])
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    # federated
    ap.add_argument("--dataset", default="crema_d")
    ap.add_argument("--scheduler", default="jcsba")
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--n-samples", type=int, default=800)
    ap.add_argument("--V", type=float, default=1.0)
    args = ap.parse_args()
    enable_compile_cache()
    if args.mode == "federated":
        train_federated(args)
    else:
        train_standard(args)


if __name__ == "__main__":
    main()
