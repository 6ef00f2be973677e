"""Pure-jnp oracle for the fused decision-fusion loss kernel, fwd + bwd.

Inputs
  logits: [M, T, V]   stacked per-modality logits (any float dtype)
  labels: [T] int32
  avail:  [M, T] float — 0/1 availability of modality m for token t
Outputs
  fused_nll: [T] f32   — CE of the availability-averaged logits (Eq. 1)
  modal_nll: [M, T] f32 — per-modality CE (Eq. 3), zero where unavailable

The ``*_f64`` twins run the same math in float64 (when jax x64 is enabled —
tests wrap them in ``jax.enable_x64(True)``) and serve as the gradient
oracle for the custom-VJP Pallas backward: ``fusion_loss_ref_grads`` emits
the logits cotangent and the ζ/δ partials (gsq = ‖dx_m‖², gdot = ⟨dx_m,
g_fused⟩) by materialising the softmax probabilities the kernel never does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _f64_or_f32():
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def _fusion_loss_impl(logits, labels, avail, dt):
    lg = logits.astype(dt)
    a = avail.astype(dt)
    denom = jnp.maximum(a.sum(0), 1e-9)                    # [T]
    fused = jnp.einsum("mtv,mt->tv", lg, a) / denom[:, None]

    def nll(x, y):
        lse = jax.nn.logsumexp(x, axis=-1)
        gold = jnp.take_along_axis(x, y[..., None], axis=-1)[..., 0]
        return lse - gold

    fused_nll = nll(fused, labels)
    modal_nll = jax.vmap(lambda x: nll(x, labels))(lg) * a
    return fused_nll, modal_nll


def fusion_loss_ref(logits: jax.Array, labels: jax.Array, avail: jax.Array):
    return _fusion_loss_impl(logits, labels, avail, jnp.float32)


def fusion_loss_ref_f64(logits, labels, avail):
    """Float64 forward twin (f32 when x64 is disabled)."""
    return _fusion_loss_impl(logits, labels, avail, _f64_or_f32())


def fusion_loss_ref_grads(logits, labels, avail, d_fused, d_modal):
    """Backward oracle: (dlogits [M, T, V], gsq [M], gdot [M]).

    ``d_fused`` [T] / ``d_modal`` [M, T] are the cotangents of
    (fused_nll, modal_nll).  Runs in float64 when x64 is enabled.  The
    partials are defined on the token grid: for a broadcast head the kernel
    path reduces the [T, V] gradient to the compact operand *after* these
    sums, so the oracle matches the kernel's accumulators exactly."""
    dt = _f64_or_f32()
    lg = logits.astype(dt)
    a = avail.astype(dt)
    df = d_fused.astype(dt)
    dm = d_modal.astype(dt)
    M, T, V = lg.shape
    denom = jnp.maximum(a.sum(0), 1e-9)                    # [T]
    fused = jnp.einsum("mtv,mt->tv", lg, a) / denom[:, None]
    p_f = jax.nn.softmax(fused, axis=-1)                   # [T, V]
    p_m = jax.nn.softmax(lg, axis=-1)                      # [M, T, V]
    onehot = jax.nn.one_hot(labels, V, dtype=dt)           # [T, V]
    base = df[:, None] * (p_f - onehot)                    # [T, V]
    d = ((a / denom)[..., None] * base[None]
         + (dm * a)[..., None] * (p_m - onehot[None]))     # [M, T, V]
    gsq = (d * d).sum((1, 2))
    gdot = (d * base[None]).sum((1, 2))
    return d, gsq, gdot
