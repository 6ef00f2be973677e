"""jit'd wrapper: layout adaptation [B,S,H,hd] <-> [B,H,S,hd].

``models.layers.attention_fwd`` can be pointed at this implementation on TPU
(``attention_impl="pallas"`` in the serving/training drivers); the dry-run and
CPU tests use the chunked-jnp path, which this kernel matches bit-for-bit in
fp32 (see tests/test_kernels.py sweeps).
"""
from __future__ import annotations

from typing import Optional

from .. import interpret_mode
from .kernel import flash_attention_pallas


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    interpret: Optional[bool] = None, **kw):
    """q: [B, S, H, hd]; k/v: [B, S, K, hd] (models.layers layout)."""
    interpret = interpret_mode(interpret)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention_pallas(qt, kt, vt, causal=causal, window=window,
                               interpret=interpret, **kw)
    return o.transpose(0, 2, 1, 3)
