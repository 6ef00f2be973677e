"""Continuous serving under live MFL training: round-boundary params
hot-swap into a zero-recompile decode loop.

The "serve what you train" story (ROADMAP) made concrete.  A
``ContinuousServer`` holds the whole serving tree —

* ``lm``:       the static decode backbone (e.g. reduced qwen3-0.6b),
* ``fusion``:   the MFL global fusion params the training rounds refresh,
* ``coupling``: a fixed seeded [C, V] matrix projecting fused class logits
                into vocab space —

behind ONE flat donated buffer per dtype (``launch/parambuf``).  Decode
steps unpack params from the buffers inside the jitted step (static slices
XLA folds into views), and the per-request multimodal context enters as a
constant logit bias added at the sampling layer — the same decision-head
convention the VLM serve path documents (``steps.make_serve_step``): fused
class logits from the request's modality features, projected through
``coupling``.  Per-step decode is the backbone only.

A hot-swap (``swap``) is one donated device copy — ``parambuf.make_swap``
writes the fresh round's params into the old allocation — plus a bias
recompute; token/cache shapes never change, so the decode jit cache stays
warm across swaps: zero recompiles, by construction and by assertion
(``run_continuous`` counts traces before/after, the repo's
``FusedRoundEngine.trace_count`` idiom).

``run_continuous`` interleaves fused ``round_step`` scans with decode-step
batches, swapping at every round boundary and timing each decode step, so
``benchmarks/serving.py`` can report the p99 swap-induced spike against a
no-swap baseline.
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import fusion
from ..models import paper_models, transformer as T
from ..models.config import ModelConfig
from . import parambuf
from . import steps as S
from .compile_cache import enable_compile_cache


class ContinuousServer:
    """Decode-serving engine whose params live behind flat donated buffers.

    ``request_feats`` is the batch's multimodal context (modality ->
    [B, ...] features, e.g. a slice of the experiment's held-out split) —
    it determines the per-request fusion bias and the serving batch size.
    """

    def __init__(self, cfg: ModelConfig, lm_params, fusion_params,
                 request_feats: Dict[str, jax.Array], *, max_len: int,
                 bias_scale: float = 0.1, coupling_seed: int = 0,
                 n_groups: int = 1, attn_chunk: int = 64, mesh=None):
        if cfg.arch_type == "audio":
            raise NotImplementedError(
                "audio archs serve through launch.serve (encoder-side cross "
                "K/V); the continuous harness drives T.decode_step backbones")
        self.cfg = cfg
        self.max_len = max_len
        self.feats = {m: jnp.asarray(x) for m, x in request_feats.items()}
        self.batch = next(iter(self.feats.values())).shape[0]

        n_classes = jax.eval_shape(
            lambda p, f: fusion.fuse_logits(paper_models.modal_logits(p, f)),
            fusion_params, self.feats).shape[-1]
        coupling = (jax.random.normal(jax.random.key(coupling_seed),
                                      (n_classes, cfg.vocab_size),
                                      jnp.float32) * bias_scale)
        # host-side refs for rebuilding the serving tree at swap time (the
        # hot path reads only the packed buffers)
        self._lm = jax.tree.map(jnp.asarray, lm_params)
        self._coupling = coupling
        tree = {"lm": self._lm, "fusion": fusion_params,
                "coupling": coupling}
        self.spec = parambuf.spec_of(tree)
        self.bufs = parambuf.pack(tree, self.spec)
        if mesh is not None:
            from .sharding import serving_buffer_shardings
            self.bufs = jax.device_put(
                self.bufs, serving_buffer_shardings(self.bufs, mesh))
        self._swap_fn = parambuf.make_swap(self.spec)

        # trace counters: incremented each time a body is *traced* — the
        # zero-recompile contract is "many steps/swaps, one trace each"
        self.decode_traces = 0
        self.prefill_traces = 0
        self.bias_traces = 0
        spec = self.spec

        def _decode(bufs, cache, token, index, bias):
            self.decode_traces += 1
            params = parambuf.unpack(bufs, spec)
            logits, cache = T.decode_step(params["lm"], cache, token, index,
                                          cfg)
            logits = logits.astype(jnp.float32) + bias[:, None, :]
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        self._decode = jax.jit(_decode, donate_argnums=(1,))
        bulk = S.make_bulk_prefill(cfg, n_groups=n_groups,
                                   attn_chunk=attn_chunk)

        def _prefill(bufs, tokens, cache):
            self.prefill_traces += 1
            params = parambuf.unpack(bufs, spec)
            return bulk(params["lm"], tokens, cache)

        self._prefill = jax.jit(_prefill, donate_argnums=(2,))

        def _bias(bufs, feats):
            self.bias_traces += 1
            params = parambuf.unpack(bufs, spec)
            modal = paper_models.modal_logits(params["fusion"], feats)
            return fusion.fuse_logits(modal) @ params["coupling"]

        self._bias_fn = jax.jit(_bias)
        self.bias = self._bias_fn(self.bufs, self.feats)
        self.cache = None
        self.token = None
        self.index = 0

    # ------------------------------------------------------------------
    def start(self, prompts: jax.Array) -> None:
        """Bulk-prefill the prompt batch [B, S] and arm the decode loop."""
        B, S = prompts.shape
        assert B == self.batch, (B, self.batch)
        cache = T.init_cache(self.cfg, B, self.max_len, self.cfg.param_dtype)
        self.token, self.cache = self._prefill(
            self.bufs, jnp.asarray(prompts, jnp.int32), cache)
        self.index = S
        jax.block_until_ready(self.token)

    def decode_step(self) -> float:
        """One greedy decode step for the whole batch; returns seconds."""
        t0 = time.perf_counter()
        self.token, self.cache = self._decode(
            self.bufs, self.cache, self.token, jnp.int32(self.index),
            self.bias)
        jax.block_until_ready(self.token)
        self.index += 1
        return time.perf_counter() - t0

    def decode_batch(self, n: int) -> list:
        return [self.decode_step() for _ in range(n)]

    def swap(self, new_fusion_params) -> float:
        """Hot-swap fresh global fusion params: one donated device copy into
        the old buffer allocation + a bias recompute.  Returns seconds."""
        t0 = time.perf_counter()
        self.bufs = self._swap_fn(
            self.bufs, {"lm": self._lm, "fusion": new_fusion_params,
                        "coupling": self._coupling})
        self.bias = self._bias_fn(self.bufs, self.feats)
        jax.block_until_ready(self.bias)
        return time.perf_counter() - t0

    # ------------------------------------------------------------------
    def state(self):
        """Snapshot (cache, token, index) — decode steps donate the cache,
        so the snapshot copies it."""
        return (jax.tree.map(jnp.copy, self.cache), jnp.copy(self.token),
                self.index)

    def load_state(self, st) -> None:
        cache, token, index = st
        self.cache = jax.tree.map(jnp.copy, cache)
        self.token = jnp.copy(token)
        self.index = index

    def compile_counts(self) -> Dict[str, int]:
        """Python trace counters + jit cache sizes for every hot-path
        function — the quantities the zero-recompile assertion compares."""
        out = {"decode_traces": self.decode_traces,
               "prefill_traces": self.prefill_traces,
               "bias_traces": self.bias_traces,
               "swap_traces": _cache_size(self._swap_fn)}
        for name, fn in (("decode", self._decode),
                         ("prefill", self._prefill),
                         ("bias", self._bias_fn)):
            n = _cache_size(fn)
            if n is not None:
                out[f"{name}_cache"] = n
        return {k: v for k, v in out.items() if v is not None}


def _cache_size(jitted) -> Optional[int]:
    return jitted._cache_size() if hasattr(jitted, "_cache_size") else None


# ---------------------------------------------------------------------------
# the interleaved driver
# ---------------------------------------------------------------------------
def run_continuous(exp, server: ContinuousServer, prompts, *, rounds: int,
                   steps_per_round: int, warmup_steps: int = 4) -> dict:
    """Interleave fused MFL training rounds with decode-step batches,
    hot-swapping the round's fresh global params at every boundary.

    Warmup compiles every jitted path (prefill, decode, a same-params swap,
    bias); after it the jit caches must be stable — ``recompiles`` in the
    returned report counts any post-warmup trace, and the tests /
    CI smoke assert it is all-zero.  Per-decode-step wall times are split
    into ``post_swap`` (the first step after a swap — where a swap-induced
    spike would land) and ``steady`` so the bench can compare p99s.
    """
    if not getattr(exp, "fused", False):
        raise ValueError("run_continuous requires an MFLExperiment with "
                         "engine='fused' (the scanned round_step path)")
    eng = exp._get_fused_engine()
    server.start(jnp.asarray(prompts, jnp.int32))
    for _ in range(max(warmup_steps, 1)):
        server.decode_step()
    server.swap(jax.tree.map(jnp.asarray, exp.global_params))
    server.decode_step()
    baseline = server.compile_counts()

    steady, post_swap, swap_walls, round_walls = [], [], [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        exp.run_scanned(1)
        round_walls.append(time.perf_counter() - t0)
        swap_walls.append(server.swap(eng.round_params(exp._carry)))
        for s in range(steps_per_round):
            (post_swap if s == 0 else steady).append(server.decode_step())
    post = server.compile_counts()
    recompiles = {k: post[k] - baseline.get(k, 0) for k in post}
    tokens = server.batch * (rounds * steps_per_round)
    decode_wall = sum(steady) + sum(post_swap)
    return {
        "rounds": rounds, "steps_per_round": steps_per_round,
        "batch": server.batch, "tokens_decoded": tokens,
        "tokens_per_s": tokens / decode_wall if decode_wall else 0.0,
        "steady_latencies_s": steady,
        "post_swap_latencies_s": post_swap,
        "swap_walls_s": swap_walls,
        "round_walls_s": round_walls,
        "compile_counts": post,
        "recompiles": recompiles,
    }


# ---------------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser(
        description="continuous serving demo: decode stream + fused MFL "
                    "rounds with round-boundary hot-swap")
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--dataset", default="iemocap")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--steps-per-round", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--K", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    from ..configs import get_config
    from ..fl.runtime import MFLExperiment
    cfg = get_config(args.arch).reduced()
    exp = MFLExperiment(dataset=args.dataset, scheduler="jcsba", K=args.K,
                        n_samples=120, seed=args.seed, eval_every=10 ** 9,
                        engine="fused")
    feats = {m: jnp.asarray(x[:args.batch])
             for m, x in sorted(exp.test_ds.features.items())}
    lm = S.init_fn(cfg)(jax.random.key(args.seed))
    server = ContinuousServer(
        cfg, lm, exp.global_params, feats,
        max_len=args.prompt_len + args.rounds * args.steps_per_round + 8)
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, min(cfg.vocab_size, 1000),
                           (args.batch, args.prompt_len))
    rep = run_continuous(exp, server, prompts, rounds=args.rounds,
                         steps_per_round=args.steps_per_round)
    lat = np.array(rep["steady_latencies_s"]) * 1e3
    print(f"[continuous] arch={cfg.name} {rep['tokens_decoded']} tokens "
          f"@ {rep['tokens_per_s']:.1f} tok/s | decode p50={np.percentile(lat, 50):.2f}ms "
          f"p99={np.percentile(lat, 99):.2f}ms | swap "
          f"{np.mean(rep['swap_walls_s']) * 1e3:.2f}ms | "
          f"recompiles={sum(rep['recompiles'].values())}")
    assert sum(rep["recompiles"].values()) == 0, rep["recompiles"]
    return rep


if __name__ == "__main__":
    main()
