"""Full chunked-SSD forward built on the Pallas intra-chunk kernel.

Matches ``models.mamba2.ssd_chunked`` (the XLA path): the kernel computes the
block-diagonal term and the chunk summary states; the O(S/chunk) inter-chunk
recurrence and the off-diagonal contribution remain cheap jnp ops.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .kernel import ssd_chunk_pallas


def ssd_forward(x, dt, A, Bm, Cm, chunk: int, *,
                interpret: Optional[bool] = None):
    """Same contract as models.mamba2.ssd_chunked.

    x: [B,S,nh,hp]; dt: [B,S,nh] fp32; A: [nh]; Bm/Cm: [B,S,N].
    """
    interpret = interpret_mode(interpret)
    Bsz, S, nh, hp = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    xd = x.astype(jnp.float32) * dt[..., None]
    dtA = dt * A[None, None, :]
    cum = jnp.cumsum(dtA.reshape(Bsz, nc, Q, nh), axis=2)
    xc = xd.reshape(Bsz, nc, Q, nh, hp)
    Bc = Bm.astype(jnp.float32).reshape(Bsz, nc, Q, N)
    Cc = Cm.astype(jnp.float32).reshape(Bsz, nc, Q, N)

    y_diag, states = ssd_chunk_pallas(xc, cum, Bc, Cc, interpret=interpret)

    chunk_decay = jnp.exp(cum[:, :, -1, :])

    def body(h, inp):
        st, dec = inp
        h_before = h
        h = h * dec[..., None, None] + st
        return h, h_before

    h0 = jnp.zeros((Bsz, nh, N, hp), jnp.float32)
    _, h_prev = jax.lax.scan(
        body, h0, (states.transpose(1, 0, 2, 3, 4),
                   chunk_decay.transpose(1, 0, 2)))
    h_prev = h_prev.transpose(1, 0, 2, 3, 4)
    y_off = jnp.einsum("bctn,bcth,bchnp->bcthp", Cc, jnp.exp(cum), h_prev)
    return (y_diag + y_off).reshape(Bsz, S, nh, hp).astype(x.dtype)
