"""Pallas TPU kernel: Mamba2 SSD intra-chunk contraction (arXiv:2405.21060).

The chunked SSD algorithm splits the sequence into chunks of Q tokens; the
intra-chunk (block-diagonal) term is an attention-like contraction masked by
the decay matrix L[t,s] = exp(cum_t − cum_s), and the per-chunk summary state
feeds the O(S/Q) inter-chunk recurrence (kept in ``ops.py`` as a lax.scan).

This kernel fuses, per (batch, chunk, head):   decay-matrix construction,
C·Bᵀ scores, masking, the [Q,Q]x[Q,hp] matmul, AND the chunk-state
[N,Q]x[Q,hp] matmul — one VMEM round trip for x/B/C instead of five HBM
passes in the XLA path.  Q=chunk defaults to 128/256 (MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, cumc_ref, cumr_ref, bt_ref, c_ref, y_ref, st_ref):
    x = x_ref[...].astype(jnp.float32)                     # [Q, hp]
    cum_c = cumc_ref[...].astype(jnp.float32)              # [Q, 1]
    cum_r = cumr_ref[...].astype(jnp.float32)              # [1, Q]
    Bt = bt_ref[...].astype(jnp.float32)                   # [N, Q]
    Cm = c_ref[...].astype(jnp.float32)                    # [Q, N]
    Q = x.shape[0]

    tri = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    # mask the exponent: upper-tri diffs overflow exp (cf. mamba2.py note)
    L = jnp.exp(jnp.where(tri, cum_c - cum_r, -jnp.inf))
    scores = jnp.dot(Cm, Bt, preferred_element_type=jnp.float32)  # [Q, Q]
    y = jnp.dot(L * scores, x, preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)

    decay_end = jnp.exp(cumc_ref[pl.ds(Q - 1, 1), :] - cum_c)   # [Q, 1]
    st = jnp.dot(Bt, x * decay_end,
                 preferred_element_type=jnp.float32)       # [N, hp]
    st_ref[...] = st.astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_pallas(x, cum, Bm, Cm, *, interpret: bool = False):
    """x: [B,nc,Q,nh,hp] (dt-weighted), cum: [B,nc,Q,nh], Bm/Cm: [B,nc,Q,N].

    Returns (y_diag [B,nc,Q,nh,hp] f32, states [B,nc,nh,N,hp] f32).

    The kernel sees head-major operands — x as [.., nh, Q, hp], cum as a
    [Q, 1] column and a [1, Q] row, B transposed to [N, Q] — so every
    block's last two dims are whole array dims, the TPU tiling rule for
    blocks that are not (8, 128)-aligned.
    """
    B, nc, Q, nh, hp = x.shape
    N = Bm.shape[-1]
    sq = pl.squeezed
    cum_h = cum.transpose(0, 1, 3, 2)                      # [B,nc,nh,Q]
    head = lambda b, c, h: (b, c, h, 0, 0)                 # noqa: E731
    chunk = lambda b, c, h: (b, c, 0, 0)                   # noqa: E731
    y, st = pl.pallas_call(
        _kernel,
        grid=(B, nc, nh),
        in_specs=[
            pl.BlockSpec((sq, sq, sq, Q, hp), head),
            pl.BlockSpec((sq, sq, sq, Q, 1), head),
            pl.BlockSpec((sq, sq, sq, 1, Q), head),
            pl.BlockSpec((sq, sq, N, Q), chunk),
            pl.BlockSpec((sq, sq, Q, N), chunk),
        ],
        out_specs=[
            pl.BlockSpec((sq, sq, sq, Q, hp), head),
            pl.BlockSpec((sq, sq, sq, N, hp), head),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nc, nh, Q, hp), jnp.float32),
            jax.ShapeDtypeStruct((B, nc, nh, N, hp), jnp.float32),
        ],
        interpret=interpret,
    )(x.transpose(0, 1, 3, 2, 4), cum_h[..., None], cum_h[..., None, :],
      Bm.swapaxes(-1, -2), Cm)
    return y.transpose(0, 1, 3, 2, 4), st
