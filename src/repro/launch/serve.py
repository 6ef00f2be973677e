"""Batched serving driver: prefill a prompt batch, then greedy-decode.

On this CPU container use ``--reduced``; the production path is the same code
under the dry-run mesh/shardings.  For VLM archs the vision decision head's
logit bias is computed once at prefill and added at the sampling layer —
per-step decode is the backbone only (see steps.make_serve_step docstring).

Prefill runs as ONE bulk pass that fills the KV cache and exports it
(``steps.make_bulk_prefill``); ``--teacher-forced`` keeps the legacy
token-by-token path for A/B (``benchmarks/serving.py`` commits the ratio).
Audio archs precompute all layers' cross-K/V in one stacked einsum
(``encdec.cross_kv``).  For round-boundary params hot-swap under live MFL
training, see ``launch/continuous.py``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..models import transformer as T, encdec
from . import steps as S
from .compile_cache import enable_compile_cache


def teacher_forced_prefill(serve_step, params, cache, prompts):
    """Legacy prefill: teacher-force the prompt one token at a time through
    decode steps.  Kept as the bulk path's A/B baseline — it fills the cache
    identically (tests/test_decode_consistency.py) at S times the
    dispatches."""
    prompt_len = prompts.shape[1]
    for i in range(prompt_len):
        nxt, cache = serve_step(params, cache, prompts[:, i:i + 1],
                                jnp.int32(i))
    return nxt, cache


def serve(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rng = np.random.default_rng(args.seed)
    params = S.init_fn(cfg)(jax.random.key(args.seed))
    B = args.batch
    prompt_len = args.prompt_len
    max_len = prompt_len + args.gen_len
    prompts = jnp.asarray(rng.integers(
        0, min(cfg.vocab_size, 1000), (B, prompt_len)), jnp.int32)

    serve_step = jax.jit(S.make_serve_step(cfg), donate_argnums=(1,))

    enc = None
    if cfg.arch_type == "audio":
        src = jnp.asarray(rng.normal(size=(B, 64, cfg.d_model)),
                          cfg.param_dtype)
        enc = encdec.encode(params, src, cfg, attn_chunk=64)
        cache = encdec.init_dec_cache(cfg, B, max_len, src.shape[1],
                                      cfg.param_dtype)
        # cross K/V from the encoder output: one stacked einsum, all layers
        ck, cv = encdec.cross_kv(params, enc, cfg)
        cache["cross_k"] = ck.astype(cache["cross_k"].dtype)
        cache["cross_v"] = cv.astype(cache["cross_v"].dtype)
    else:
        cache = T.init_cache(cfg, B, max_len, cfg.param_dtype)

    t0 = time.time()
    if args.teacher_forced:
        nxt, cache = teacher_forced_prefill(serve_step, params, cache,
                                            prompts)
    else:
        bulk = jax.jit(S.make_bulk_prefill(cfg, attn_chunk=args.attn_chunk),
                       donate_argnums=(3,) if enc is not None else (2,))
        if enc is not None:
            nxt, cache = bulk(params, prompts, enc, cache)
        else:
            nxt, cache = bulk(params, prompts, cache)
    generated = [nxt]
    for i in range(args.gen_len - 1):
        nxt, cache = serve_step(params, cache, generated[-1],
                                jnp.int32(prompt_len + i))
        generated.append(nxt)
    dt = time.time() - t0
    out = jnp.concatenate(generated, axis=1)
    toks = B * (prompt_len + args.gen_len - 1)
    mode = "teacher-forced" if args.teacher_forced else "bulk"
    print(f"[serve] arch={cfg.name} batch={B} prefill={mode} steps={toks} "
          f"{toks / dt:.1f} tok/s wall={dt:.2f}s")
    print("[serve] sample:", np.asarray(out[0])[:16].tolist())
    assert out.shape == (B, args.gen_len)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--attn-chunk", type=int, default=64)
    ap.add_argument("--teacher-forced", action="store_true",
                    help="legacy per-token prefill (A/B baseline)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    serve(args)


if __name__ == "__main__":
    main()
