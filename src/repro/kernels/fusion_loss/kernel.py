"""Pallas TPU kernels: fused decision-level-fusion + softmax-CE, fwd + bwd.

The paper's claim (§II) is that adding the unimodal losses is computationally
free because the unimodal logits already exist.  At LM scale the *loss itself*
becomes the bottleneck: materialising M softmaxes over a 151k-262k vocab is
HBM-bound.  The forward kernel tiles the vocab axis into VMEM blocks and
computes the fused log-sum-exp and all M per-modality CEs in ONE pass over the
logits — each logit element is read exactly once from HBM.  With
``save_residuals=True`` it additionally emits the online-softmax residuals
(per-row max and log-sum-exp for the fused mixture and every unimodal head),
which is everything the backward needs besides the logits themselves.

The backward kernel (``fusion_loss_bwd_pallas``) re-reads the logits once and
emits ``dlogits`` per modality in a single blocked pass — softmax
probabilities exist only tile-at-a-time in VMEM, never materialised:

    d x[m,t,v] = gf[t]·(avail[m,t]/denom[t])·(p_f[t,v] − 1{v=y_t})
               + gm[m,t]·avail[m,t]·(p_m[m,t,v] − 1{v=y_t})

where p_f/p_m are reconstructed from the saved residuals.  ``avail``
multiplies every term, so masked modalities and padded rows get *exact-zero*
gradients.  As free by-products the backward accumulates, across all tiles,
the per-modality squared norm ‖dx_m‖² and the dot ⟨dx_m, g_fused⟩ of the
logits gradient (``gsq``/``gdot`` — the Theorem-1 ζ/δ partials in logits
space; see core.convergence for the param-space twin).

Grid: (T/Tb, V/Vb), vocab innermost; streaming state lives in VMEM scratch
across vocab tiles.  Per-modality logits arrive as separate refs (variadic),
so callers never materialise an [M, T, V] stack in HBM; a broadcast head
(e.g. vision [B, 1, V] against labels [B, S]) is fed as its compact
[B, 1, V] array with a tile→batch-row index map (``seg[m] = S``, requires
Tb | S).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _load_tiles(logit_refs, bt: int):
    """The per-modality tiles as f32 [Tb, Vb] values.  A broadcast head's
    tile is [1, Vb] and broadcasts over the token rows.  Every value stays
    2-D (rows on sublanes, vocab on lanes): Mosaic lays out no 1-D vectors
    and no [M, Tb, Vb] stacks."""
    return [jnp.broadcast_to(r[...].astype(jnp.float32), (bt, r.shape[-1]))
            for r in logit_refs]


def _gold_pick(labels, iv, block_v: int):
    """Bool [Tb, Vb]: True where this vocab tile holds the gold column of
    the row (``labels`` is [Tb, 1])."""
    col = jax.lax.broadcasted_iota(jnp.int32, (labels.shape[0], block_v), 1)
    return col + iv * block_v == labels


def _fused_tile(xs, avail, iv, block_v: int, v_real: int):
    """Availability-averaged mixture tile with padded vocab columns pinned to
    NEG_INF (keeps the fused LSE independent of vocab padding even on rows
    where every modality is unavailable and the mixture degenerates to 0).
    ``xs``/``avail``: per-modality [Tb, Vb] tiles and [Tb, 1] weights."""
    denom = jnp.maximum(sum(avail), 1e-9)                   # [Tb, 1]
    fused = sum(x * a for x, a in zip(xs, avail)) / denom   # [Tb, Vb]
    col = (jax.lax.broadcasted_iota(jnp.int32, fused.shape, 1)
           + iv * block_v)
    return jnp.where(col < v_real, fused, NEG_INF), denom


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(labels_ref, avail_ref, *refs, n_mod: int, block_v: int,
                v_real: int, save_residuals: bool):
    iv = pl.program_id(1)
    nv = pl.num_programs(1)
    logit_refs = refs[:n_mod]
    n_out = 6 if save_residuals else 2
    outs = refs[n_mod:n_mod + n_out]
    mf, sf, gf, mm, sm, gm = refs[n_mod + n_out:]

    @pl.when(iv == 0)
    def _init():
        mf[...] = jnp.full_like(mf, NEG_INF)
        sf[...] = jnp.zeros_like(sf)
        gf[...] = jnp.zeros_like(gf)
        mm[...] = jnp.full_like(mm, NEG_INF)
        sm[...] = jnp.zeros_like(sm)
        gm[...] = jnp.zeros_like(gm)

    bt = labels_ref.shape[0]
    xs = _load_tiles(logit_refs, bt)                        # M × [Tb, Vb]
    avail = [avail_ref[i].astype(jnp.float32) for i in range(n_mod)]
    pick = _gold_pick(labels_ref[...], iv, block_v)
    fused, _ = _fused_tile(xs, avail, iv, block_v, v_real)

    # streaming logsumexp + gold logit: fused mixture, then each modality
    m_new = jnp.maximum(mf[...], fused.max(axis=-1, keepdims=True))
    sf[...] = (sf[...] * jnp.exp(mf[...] - m_new)
               + jnp.exp(fused - m_new).sum(-1, keepdims=True))
    mf[...] = m_new
    gf[...] = gf[...] + jnp.where(pick, fused, 0.0).sum(-1, keepdims=True)
    for i, x in enumerate(xs):
        mi_new = jnp.maximum(mm[i], x.max(axis=-1, keepdims=True))
        sm[i] = (sm[i] * jnp.exp(mm[i] - mi_new)
                 + jnp.exp(x - mi_new).sum(-1, keepdims=True))
        mm[i] = mi_new
        gm[i] = gm[i] + jnp.where(pick, x, 0.0).sum(-1, keepdims=True)

    @pl.when(iv == nv - 1)
    def _finalize():
        f_lse = mf[...] + jnp.log(sf[...])
        outs[0][...] = f_lse - gf[...]
        for i in range(n_mod):
            m_lse = mm[i] + jnp.log(sm[i])
            outs[1][i] = (m_lse - gm[i]) * avail[i]
            if save_residuals:
                outs[4][i] = mm[i]
                outs[5][i] = m_lse
        if save_residuals:
            outs[2][...] = mf[...]
            outs[3][...] = f_lse


def _row_specs(M: int, block_t: int):
    """BlockSpecs of a per-row [T, 1] and a per-(modality, row) [M, T, 1]
    operand: a trailing unit lane dim keeps every block 2-D and tileable,
    under the cohort vmap's extra leading axis too."""
    return (pl.BlockSpec((block_t, 1), lambda it, iv: (it, 0)),
            pl.BlockSpec((M, block_t, 1), lambda it, iv: (0, it, 0)))


def _logit_specs(seg, block_t: int, block_v: int):
    """Per-modality input BlockSpecs.  ``seg[m] == 0`` → full [T, V] operand
    tiled (Tb, Vb); ``seg[m] == S`` → compact [B, 1, V] operand whose token
    tile maps onto one batch row (requires Tb | S so tiles never straddle
    rows)."""
    specs = []
    for s in seg:
        if s:
            assert s % block_t == 0, (s, block_t)
            specs.append(pl.BlockSpec(
                (pl.squeezed, 1, block_v),
                functools.partial(_seg_map, bt=block_t, S=s)))
        else:
            specs.append(pl.BlockSpec((block_t, block_v),
                                      lambda it, iv: (it, iv)))
    return specs


def _seg_map(it, iv, *, bt: int, S: int):
    return ((it * bt) // S, 0, iv)


def _logit_operands(logits, seg):
    """Broadcast heads [B, V] enter the kernel as [B, 1, V]."""
    return [lg[:, None, :] if s else lg for lg, s in zip(logits, seg)]


@functools.partial(jax.jit, static_argnames=(
    "block_t", "block_v", "v_real", "seg", "save_residuals", "interpret"))
def fusion_loss_fwd_pallas(logits, labels, avail, *, block_t: int,
                           block_v: int, v_real: int, seg,
                           save_residuals: bool = False,
                           interpret: bool = False):
    """Variadic forward.  ``logits`` is a tuple of per-modality arrays —
    [T, V], or [B, V] when ``seg[m] = S`` marks a broadcast head (T = B·S);
    labels [T] int32; avail [M, T].  Shapes must tile exactly (the
    differentiable ops.py wrapper pads); ``v_real`` ≤ V marks real vocab
    columns.  Returns (fused_nll [T], modal_nll [M, T]) plus, with
    ``save_residuals``, (fused_max [T], fused_lse [T], modal_max [M, T],
    modal_lse [M, T])."""
    M = len(logits)
    T = labels.shape[0]
    V = logits[0].shape[-1]
    assert T % block_t == 0 and V % block_v == 0, (T, V, block_t, block_v)
    grid = (T // block_t, V // block_v)
    row, mrow = _row_specs(M, block_t)
    out_specs = [row, mrow]
    out_shape = [jax.ShapeDtypeStruct((T, 1), jnp.float32),
                 jax.ShapeDtypeStruct((M, T, 1), jnp.float32)]
    if save_residuals:
        out_specs += [row, row, mrow, mrow]
        out_shape += out_shape[:1] * 2 + out_shape[1:] * 2

    kern = functools.partial(_fwd_kernel, n_mod=M, block_v=block_v,
                             v_real=v_real, save_residuals=save_residuals)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[row, mrow] + _logit_specs(seg, block_t, block_v),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_t, 1), jnp.float32)] * 3
                       + [pltpu.VMEM((M, block_t, 1), jnp.float32)] * 3,
        interpret=interpret,
    )(labels[:, None], avail[..., None], *_logit_operands(logits, seg))
    return tuple(o[..., 0] for o in out)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_kernel(labels_ref, avail_ref, df_ref, dm_ref, flse_ref, mlse_ref,
                *refs, n_mod: int, block_v: int, v_real: int):
    iv = pl.program_id(1)
    nv = pl.num_programs(1)
    logit_refs = refs[:n_mod]
    dl_refs = refs[n_mod:2 * n_mod]
    gsq_ref, gdot_ref, sq_acc, dot_acc = refs[2 * n_mod:]

    @pl.when(iv == 0)
    def _init():
        sq_acc[...] = jnp.zeros_like(sq_acc)
        dot_acc[...] = jnp.zeros_like(dot_acc)

    bt = labels_ref.shape[0]
    xs = _load_tiles(logit_refs, bt)                        # M × [Tb, Vb]
    avail = [avail_ref[i].astype(jnp.float32) for i in range(n_mod)]
    fused, denom = _fused_tile(xs, avail, iv, block_v, v_real)

    # probabilities from the saved residuals, one tile at a time
    p_f = jnp.exp(fused - flse_ref[...])                    # [Tb, Vb]
    pick = _gold_pick(labels_ref[...], iv, block_v).astype(jnp.float32)
    base = df_ref[...] * (p_f - pick)                       # [Tb, Vb]
    for i, (x, r) in enumerate(zip(xs, dl_refs)):
        p_m = jnp.exp(x - mlse_ref[i])
        d = ((avail[i] / denom) * base
             + (dm_ref[i] * avail[i]) * (p_m - pick))       # [Tb, Vb]
        r[...] = d.astype(r.dtype)
        sq_acc[i] = sq_acc[i] + (d * d).sum(-1, keepdims=True)
        dot_acc[i] = dot_acc[i] + (d * base).sum(-1, keepdims=True)

    @pl.when(iv == nv - 1)
    def _finalize():
        gsq_ref[...] = sq_acc[...]
        gdot_ref[...] = dot_acc[...]


@functools.partial(jax.jit, static_argnames=(
    "block_t", "block_v", "v_real", "seg", "interpret"))
def fusion_loss_bwd_pallas(logits, labels, avail, d_fused, d_modal,
                           fused_lse, modal_lse, *, block_t: int,
                           block_v: int, v_real: int, seg,
                           interpret: bool = False):
    """One blocked pass emitting the logits gradient + ζ/δ partials.

    Inputs mirror the forward (same variadic ``logits``/``seg`` layout) plus
    the loss cotangents ``d_fused`` [T] / ``d_modal`` [M, T] and the saved
    LSE residuals.  Returns (dlogits — one [T, V] f32 array per modality,
    broadcast heads included; gsq [M] = Σ dx_m²; gdot [M] = Σ dx_m·g_fused).
    The kernel accumulates the partials per row across vocab tiles; the
    row sum happens here.
    """
    M = len(logits)
    T = labels.shape[0]
    V = logits[0].shape[-1]
    assert T % block_t == 0 and V % block_v == 0, (T, V, block_t, block_v)
    grid = (T // block_t, V // block_v)
    row, mrow = _row_specs(M, block_t)
    kern = functools.partial(_bwd_kernel, n_mod=M, block_v=block_v,
                             v_real=v_real)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[row, mrow, row, mrow, row, mrow]
                 + _logit_specs(seg, block_t, block_v),
        out_specs=[pl.BlockSpec((block_t, block_v),
                                lambda it, iv: (it, iv))] * M + [mrow] * 2,
        out_shape=[jax.ShapeDtypeStruct((T, V), jnp.float32)] * M
                  + [jax.ShapeDtypeStruct((M, T, 1), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((M, block_t, 1), jnp.float32)] * 2,
        interpret=interpret,
    )(labels[:, None], avail[..., None], d_fused[:, None],
      d_modal[..., None], fused_lse[:, None], modal_lse[..., None],
      *_logit_operands(logits, seg))
    return tuple(out[:M]), out[M].sum((1, 2)), out[M + 1].sum((1, 2))


# ---------------------------------------------------------------------------
# stacked-operand compatibility wrapper (forward only, shapes must tile)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("block_t", "block_v", "interpret"))
def fusion_loss_pallas(logits: jax.Array, labels: jax.Array,
                       avail: jax.Array, *, block_t: int = 128,
                       block_v: int = 2048, interpret: bool = False):
    """logits [M,T,V], labels [T] int32, avail [M,T] -> (fused_nll [T],
    modal_nll [M,T]), both f32.  For the differentiable, padding-aware entry
    point use ``ops.fusion_loss``."""
    M, T, V = logits.shape
    block_t = min(block_t, T)
    block_v = min(block_v, V)
    assert T % block_t == 0 and V % block_v == 0, (T, V, block_t, block_v)
    out = fusion_loss_fwd_pallas(
        tuple(logits[i] for i in range(M)), labels, avail,
        block_t=block_t, block_v=block_v, v_real=V, seg=(0,) * M,
        save_residuals=False, interpret=interpret)
    return out[0], out[1]
