"""The paper's submodels, written plainly in ``jax.numpy``.

Decision-level fusion of per-modality classifiers (the paper's section VI):
a two-layer LSTM (dropout between the layers while training, last hidden
state, a ReLU hidden layer, the class layer) for sequences, and a CNN of
three SAME 5x5 convolutions, each followed by ReLU and a SAME 5x5 max-pool
of stride 3, then two ReLU hidden layers and the class layer, for images.
The loss of Eqs. 1-4 is the cross-entropy of the mean of the available
modalities' logits plus each modality's own cross-entropy weighted by v_m.

Widths come from the configuration file.  The weights are drawn from the
seed with the initialisation the paper's code uses (uniform +-1/sqrt(H) LSTM
gates, scaled normal layers); gradients come from ``jax.grad`` of a plain
``lax.scan`` and ``reduce_window``, no hand-written backward.  The reference
computes in float32 with matmuls and convolutions at ``highest`` precision;
its control one step lower, at ``high`` (three bfloat16 passes).
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

#: the dropout stream of modality m folds in its index in this order
MODALITY_ORDER = ("audio", "image", "text")


def _init_lstm(key, spec, n_classes):
    d_in, H = spec["d_in"], spec["hidden"]
    s = 1.0 / math.sqrt(H)

    def layer(k, di):
        k1, k2 = jax.random.split(k)
        return {"wi": jax.random.uniform(k1, (di, 4 * H), minval=-s,
                                         maxval=s),
                "wh": jax.random.uniform(k2, (H, 4 * H), minval=-s,
                                         maxval=s),
                "b": jnp.zeros((4 * H,))}

    ks = jax.random.split(key, 4)
    return {"lstm0": layer(ks[0], d_in), "lstm1": layer(ks[1], H),
            "fc": {"w": jax.random.normal(ks[2], (H, H)) / math.sqrt(H),
                   "b": jnp.zeros((H,))},
            "out": {"w": jax.random.normal(ks[3], (H, n_classes))
                    / math.sqrt(H),
                    "b": jnp.zeros((n_classes,))}}


def _init_cnn(key, spec, n_classes):
    ks = jax.random.split(key, 6)
    k, ch, scale = spec["kernel"], spec["channels"], spec["conv_scale"]

    def conv(kk, ci):
        return (jax.random.normal(kk, (k, k, ci, ch))
                * math.sqrt(2.0 / (k * k * ci)) * scale)

    f0, f1 = spec["fc"]
    return {"c0": conv(ks[0], spec["in_ch"]), "c1": conv(ks[1], ch),
            "c2": conv(ks[2], ch),
            "fc0": {"w": jax.random.normal(ks[3], (spec["flat"], f0)) / 8.0,
                    "b": jnp.zeros((f0,))},
            "fc1": {"w": jax.random.normal(ks[4], (f0, f1)) / 8.0,
                    "b": jnp.zeros((f1,))},
            "out": {"w": jax.random.normal(ks[5], (f1, n_classes))
                    / math.sqrt(f1),
                    "b": jnp.zeros((n_classes,))}}


def init_params(cfg: dict, seed: int) -> Dict[str, dict]:
    """Global weights from the seed: one key per modality, in sorted
    modality order."""
    mods = sorted(cfg["models"])
    keys = jax.random.split(jax.random.key(seed), len(mods))
    out = {}
    for m, k in zip(mods, keys):
        spec = cfg["models"][m]
        init = _init_lstm if spec["kind"] == "lstm" else _init_cnn
        out[m] = init(k, spec, cfg["n_classes"])
    return out


def _lstm_layer(p, x, unroll=1):
    """x [B, T, d] -> hidden states [B, T, H]."""
    B, H = x.shape[0], p["wh"].shape[0]

    def cell(carry, x_t):
        h, c = carry
        a = x_t @ p["wi"] + h @ p["wh"] + p["b"]
        i, f, g, o = jnp.split(a, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    zero = jnp.zeros((B, H), x.dtype)
    _, hs = lax.scan(cell, (zero, zero), jnp.swapaxes(x, 0, 1),
                     unroll=unroll)
    return jnp.swapaxes(hs, 0, 1)


def lstm_logits(p, x, spec, rng=None, unroll=1):
    h = _lstm_layer(p["lstm0"], x, unroll)
    if rng is not None:
        keep_p = 1.0 - spec["dropout"]
        keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
            jnp.arange(h.shape[0]))
        keep = jax.vmap(lambda k: jax.random.bernoulli(
            k, keep_p, h.shape[1:]))(keys)
        h = jnp.where(keep, h / keep_p, 0.0).astype(h.dtype)
    h = _lstm_layer(p["lstm1"], h, unroll)[:, -1]
    h = jax.nn.relu(h @ p["fc"]["w"] + p["fc"]["b"])
    return h @ p["out"]["w"] + p["out"]["b"]


def cnn_logits(p, x, spec, rng=None):
    w, s = spec["pool"]
    y = x
    for name in ("c0", "c1", "c2"):
        y = lax.conv_general_dilated(
            y, p[name], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        y = jax.nn.relu(y)
        y = lax.reduce_window(y, -jnp.inf, lax.max, (1, w, w, 1),
                              (1, s, s, 1), "SAME")
    y = y.reshape(y.shape[0], -1)
    y = jax.nn.relu(y @ p["fc0"]["w"] + p["fc0"]["b"])
    y = jax.nn.relu(y @ p["fc1"]["w"] + p["fc1"]["b"])
    return y @ p["out"]["w"] + p["out"]["b"]


def modal_logits(cfg, params, feats, seed=None):
    """{m: [B, C]} for the modalities in ``feats``; ``seed`` (a client's
    dropout seed) switches training-time dropout on."""
    out = {}
    for m in sorted(feats):
        spec = cfg["models"][m]
        rng = None
        if seed is not None:
            rng = jax.random.fold_in(jax.random.key(seed),
                                     MODALITY_ORDER.index(m))
        fn = lstm_logits if spec["kind"] == "lstm" else cnn_logits
        out[m] = fn(params[m], feats[m], spec, rng)
    return out


def _xent_rows(logits, labels):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return lse - gold


def _xent(logits, labels):
    return jnp.mean(_xent_rows(logits, labels))


def loss(cfg, params, feats, labels, valid, avail, seed):
    """H_k = F_k + sum_m v_m G_m: the mean over the client's ``valid``
    samples, Eq. 1's fused logits averaging the modalities with ``avail``
    1 (the client's own), each unimodal term weighted by ``avail`` too."""
    logits = modal_logits(cfg, params, feats, seed)
    mods = sorted(logits)
    n = valid.sum()
    fused = sum(avail[i] * logits[m] for i, m in enumerate(mods)) \
        / avail.sum()
    total = (_xent_rows(fused, labels) * valid).sum() / n
    for i, m in enumerate(mods):
        total = total + avail[i] * cfg["v_weights"][m] * (
            _xent_rows(logits[m], labels) * valid).sum() / n
    return total


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_key: str, precision: str):
    import json
    cfg = json.loads(cfg_key)

    @jax.jit
    def fn(params, feats, labels, valid, avail, seed):
        with jax.default_matmul_precision(precision):
            return jax.grad(lambda p: loss(cfg, p, feats, labels, valid,
                                           avail, seed))(params)
    return fn


@functools.lru_cache(maxsize=None)
def _eval_fn(cfg_key: str, precision: str):
    import json
    cfg = json.loads(cfg_key)

    @jax.jit
    def fn(params, feats, labels):
        with jax.default_matmul_precision(precision):
            logits = modal_logits(cfg, params, feats)
            fused = sum(lg.astype(jnp.float32) for lg in logits.values()) \
                / len(logits)
            out = {"multimodal": jnp.mean(jnp.argmax(fused, -1) == labels),
                   "loss": _xent(fused, labels)}
            for m, lg in logits.items():
                out[m] = jnp.mean(jnp.argmax(lg, -1) == labels)
            return out
    return fn


def grads(cfg_key: str, precision: str, params, feats, labels, valid,
          avail, seed: int):
    """Gradient of one client's loss at ``params``.  Every client's rows
    are padded to one length (``valid`` marks its own) and every modality
    is present (``avail`` marks its own; the others' features are zeros and
    get exactly zero gradient), so one program serves all clients."""
    return _grad_fn(cfg_key, precision)(params, feats, labels, valid, avail,
                                        jnp.uint32(seed))


def evaluate(cfg_key: str, precision: str, params, feats, labels
             ) -> Dict[str, float]:
    out = _eval_fn(cfg_key, precision)(params, feats, labels)
    return {k: float(v) for k, v in out.items()}


