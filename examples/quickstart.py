"""Quickstart: 10 rounds of wireless multimodal FL with JCSBA + one
LM-architecture forward pass through the public API.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.runtime import MFLExperiment
from repro.configs import get_config
from repro.launch import steps
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    # --- the paper's system: decision-fusion MFL over a simulated cell ---
    exp = MFLExperiment(dataset="crema_d", scheduler="jcsba",
                        n_samples=400, seed=0)
    exp.run(10, verbose=True)
    print("final:", exp.final_metrics())

    # --- the model zoo: any assigned arch, reduced for CPU ---
    cfg = get_config("qwen3-4b").reduced()
    params = steps.init_fn(cfg)(jax.random.key(0))
    loss_fn = jax.jit(steps.make_loss_fn(cfg, attn_chunk=64))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 128)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 128)),
                                   jnp.int32)}
    print(f"{cfg.name} (reduced) loss:", float(loss_fn(params, batch)))


if __name__ == "__main__":
    main()
