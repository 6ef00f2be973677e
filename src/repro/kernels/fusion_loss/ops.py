"""Differentiable public wrappers around the fusion-loss kernels.

``fusion_loss`` is the stacked [M, T, V] entry point; it carries a
``jax.custom_vjp`` whose forward saves the online-softmax residuals
(per-row max + log-sum-exp for the fused mixture and each unimodal head) and
whose backward is the one-pass blocked Pallas kernel — softmax probabilities
are never materialised, and ``avail``-masked modalities / zero-cotangent
(sample-mask-padded) rows get exact-zero gradients.  ``fusion_loss_grads``
exposes the same backward with its ζ/δ partials (gsq/gdot) as a public op.

``fused_multimodal_loss`` is the dict front-end with the same
(v_weights, avail, sample_mask) semantics as ``core.fusion.multimodal_loss``
— the training hot path (fl/client.py, ``loss_backend="pallas"``) calls it
per client under the cohort vmap.  Per-modality logits feed the kernel as
separate operands (no [M, B·S, V] stack copy); a broadcast head
(e.g. vision [B, 1, V]) stays its compact [B, V] self via the kernel's
tile→batch-row index map.  ``avail`` entries must be scalars (the per-client
0/1 availability the cohort path uses) — vector per-sample availability
changes the G_m weighting semantics and stays on the XLA path.

Non-divisible ``block_t``/``block_v`` tiles are handled by padding: token
rows pad with avail = 0 (exact-zero loss and gradient), vocab columns pad
with a large-negative logit (exactly zero probability mass).  Off a TPU both
directions run in interpret mode (``kernels.interpret_mode``).  Metrics
omit ``fused_logits`` (the kernel never forms the fused logits tensor — use
the XLA path when you need it for accuracy).
"""
from __future__ import annotations

import functools
import math
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import interpret_mode
from .kernel import (fusion_loss_bwd_pallas, fusion_loss_fwd_pallas,
                     fusion_loss_pallas)

__all__ = ["fusion_loss", "fusion_loss_grads", "fused_multimodal_loss",
           "fusion_loss_pallas"]


# ---------------------------------------------------------------------------
# tile planning + padding.  cfg = (block_t, block_v, interpret, seg) is the
# custom_vjp's static (nondiff) argument: seg[m] = 0 for a full [T, V]
# operand, or S for a compact broadcast head [B, V] (T = B·S).
# ---------------------------------------------------------------------------
def _plan(cfg, T: int, V: int):
    block_t, block_v, _, seg = cfg
    bt = min(block_t, T)
    for s in seg:
        if s:            # tiles must not straddle a broadcast head's rows
            bt = math.gcd(bt, s)
    bv = min(block_v, V)
    return bt, bv, -(-T // bt) * bt, -(-V // bv) * bv


def _neg_big(dtype):
    """Vocab-padding logit: large-negative but summable across M modalities
    without overflowing to inf (0·inf in the mixture einsum would be NaN)."""
    return jnp.asarray(jnp.finfo(dtype).min / 8, dtype)


def _pad_operand(lg, s: int, T: int, V: int, Tp: int, Vp: int):
    if Vp > V:
        lg = jnp.pad(lg, ((0, 0), (0, Vp - V)),
                     constant_values=_neg_big(lg.dtype))
    if not s and Tp > T:
        lg = jnp.pad(lg, ((0, Tp - T), (0, 0)))
    return lg


def _pad_inputs(cfg, logits, labels, avail):
    T = labels.shape[0]
    V = logits[0].shape[-1]
    bt, bv, Tp, Vp = _plan(cfg, T, V)
    seg = cfg[3]
    lg_p = tuple(_pad_operand(lg, s, T, V, Tp, Vp)
                 for lg, s in zip(logits, seg))
    lab_p = jnp.pad(labels, (0, Tp - T)) if Tp > T else labels
    av_p = (jnp.pad(avail, ((0, 0), (0, Tp - T))) if Tp > T else avail)
    return lg_p, lab_p, av_p, (bt, bv, T, V)


# ---------------------------------------------------------------------------
# custom-VJP core
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fusion_core(cfg, logits, labels, avail):
    out, _ = _fusion_core_fwd(cfg, logits, labels, avail)
    return out


def _fusion_core_fwd(cfg, logits, labels, avail):
    lg_p, lab_p, av_p, (bt, bv, T, V) = _pad_inputs(cfg, logits, labels,
                                                    avail)
    f_nll, m_nll, f_max, f_lse, m_max, m_lse = fusion_loss_fwd_pallas(
        lg_p, lab_p, av_p, block_t=bt, block_v=bv, v_real=V, seg=cfg[3],
        save_residuals=True, interpret=cfg[2])
    res = (logits, labels, avail,
           f_max[:T], f_lse[:T], m_max[:, :T], m_lse[:, :T])
    return (f_nll[:T], m_nll[:, :T]), res


def _bwd_call(cfg, logits, labels, avail, f_lse, m_lse, d_fused, d_modal):
    """Shared padded backward: returns (per-modality dlogits in the
    operands' own layouts/dtypes, gsq [M], gdot [M])."""
    seg = cfg[3]
    lg_p, lab_p, av_p, (bt, bv, T, V) = _pad_inputs(cfg, logits, labels,
                                                    avail)
    Tp = lab_p.shape[0]
    if Tp > T:
        d_fused = jnp.pad(d_fused, (0, Tp - T))
        d_modal = jnp.pad(d_modal, ((0, 0), (0, Tp - T)))
        f_lse = jnp.pad(f_lse, (0, Tp - T))
        m_lse = jnp.pad(m_lse, ((0, 0), (0, Tp - T)))
    dl_p, gsq, gdot = fusion_loss_bwd_pallas(
        lg_p, lab_p, av_p, d_fused, d_modal, f_lse, m_lse,
        block_t=bt, block_v=bv, v_real=V, seg=seg, interpret=cfg[2])
    dl = []
    for lg, s, d in zip(logits, seg, dl_p):
        d = d[:T, :V]
        if s:            # broadcast head: fold the token grid back to [B, V]
            d = d.reshape(-1, s, V).sum(1)
        dl.append(d.astype(lg.dtype))
    return tuple(dl), gsq, gdot


def _fusion_core_bwd(cfg, res, ct):
    logits, labels, avail, _f_max, f_lse, _m_max, m_lse = res
    d_fused, d_modal = ct
    dl, _gsq, _gdot = _bwd_call(cfg, logits, labels, avail, f_lse, m_lse,
                                d_fused, d_modal)
    # labels are integral (float0 cotangent); avail is a mask, not a
    # differentiation surface — its cotangent is defined as zero.
    d_labels = np.zeros(np.shape(labels), jax.dtypes.float0)
    return dl, d_labels, jnp.zeros_like(avail)


_fusion_core.defvjp(_fusion_core_fwd, _fusion_core_bwd)


# ---------------------------------------------------------------------------
# public ops
# ---------------------------------------------------------------------------
def fusion_loss(logits, labels, avail=None, *, block_t: int = 128,
                block_v: int = 2048, interpret: Optional[bool] = None):
    """Differentiable one-pass loss: logits [M,T,V]; labels [T]; avail [M,T]
    (default all-available).  Returns (fused_nll [T], modal_nll [M,T]);
    gradients w.r.t. ``logits`` flow through the blocked backward kernel."""
    M, T, V = logits.shape
    if avail is None:
        avail = jnp.ones((M, T), jnp.float32)
    cfg = (block_t, block_v, interpret_mode(interpret), (0,) * M)
    return _fusion_core(cfg, tuple(logits[i] for i in range(M)),
                        labels.astype(jnp.int32),
                        avail.astype(jnp.float32))


def fusion_loss_grads(logits, labels, avail, d_fused, d_modal, *,
                      block_t: int = 128, block_v: int = 2048,
                      interpret: Optional[bool] = None):
    """Backward pass as a public op, partials included.

    Given the loss cotangents ``d_fused`` [T] / ``d_modal`` [M, T], returns
    (dlogits [M, T, V], gsq [M], gdot [M]) where gsq_m = ‖dlogits_m‖² and
    gdot_m = ⟨dlogits_m, g_fused⟩ (g_fused = the fused-CE term of the
    gradient) — the Theorem-1 ζ/δ norm partials in logits space, accumulated
    tile-by-tile inside the same single pass that emits the gradient
    (float64-oracle parity in tests/test_fusion_vjp.py)."""
    M, T, V = logits.shape
    cfg = (block_t, block_v, interpret_mode(interpret), (0,) * M)
    lg = tuple(logits[i] for i in range(M))
    labels = labels.astype(jnp.int32)
    avail = avail.astype(jnp.float32)
    _, (_, _, _, _f_max, f_lse, _m_max, m_lse) = _fusion_core_fwd(
        cfg, lg, labels, avail)
    dl, gsq, gdot = _bwd_call(cfg, lg, labels, avail, f_lse, m_lse,
                              jnp.asarray(d_fused, jnp.float32),
                              jnp.asarray(d_modal, jnp.float32))
    return jnp.stack(dl), gsq, gdot


def fused_multimodal_loss(modal_logits: Mapping[str, jax.Array],
                          labels: jax.Array,
                          v_weights: Optional[Mapping[str, float]] = None,
                          avail: Optional[Mapping[str, jax.Array]] = None,
                          sample_mask: Optional[jax.Array] = None, *,
                          block_t: int = 128, block_v: int = 2048,
                          interpret: Optional[bool] = None):
    """Dict front-end matching ``core.fusion.multimodal_loss`` semantics.

    H = F + Σ_m v_m·mean(a_m)·G_m over the sample-masked mean, computed from
    the kernel's per-token (fused_nll, modal_nll) — differentiable end to
    end (the masked means contribute the cotangents; the kernel backward
    does the rest).  Returns (total, {"F", "G_<m>", "G"}).
    """
    names = sorted(modal_logits.keys())
    V = modal_logits[names[0]].shape[-1]
    lab = labels.reshape(-1).astype(jnp.int32)
    T = lab.shape[0]
    lgs, seg = [], []
    for m in names:
        lg = modal_logits[m]
        if lg.shape[:-1] == labels.shape:
            lgs.append(lg.reshape(T, V))
            seg.append(0)
        else:               # broadcast head, e.g. [B, 1, V] vs labels [B, S]
            lgs.append(lg.reshape(-1, V))
            seg.append(int(labels.shape[-1]))
    avs = []
    for m in names:
        a = jnp.asarray(1.0 if avail is None else avail[m], jnp.float32)
        if jnp.ndim(a) != 0:
            raise NotImplementedError(
                "fused_multimodal_loss takes scalar per-modality avail "
                "(the cohort path's 0/1 availability); per-sample vectors "
                "stay on core.fusion.multimodal_loss")
        avs.append(a)
    a_full = jnp.broadcast_to(jnp.stack(avs)[:, None], (len(names), T))

    cfg = (block_t, block_v, interpret_mode(interpret), tuple(seg))
    f_nll, m_nll = _fusion_core(cfg, tuple(lgs), lab, a_full)

    if sample_mask is None:
        w = jnp.ones((T,), jnp.float32)
    else:
        w = jnp.broadcast_to(jnp.asarray(sample_mask, jnp.float32),
                             labels.shape).reshape(-1)
    wsum = jnp.maximum(w.sum(), 1e-9)
    F = (f_nll * w).sum() / wsum
    metrics = {"F": F}
    G = jnp.zeros((), jnp.float32)
    for i, m in enumerate(names):
        v = 1.0 if v_weights is None else float(v_weights.get(m, 1.0))
        g = v * (m_nll[i] * w).sum() / wsum
        metrics[f"G_{m}"] = g
        G = G + g
    metrics["G"] = G
    return F + G, metrics
