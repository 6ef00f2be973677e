"""Pallas kernels of the FL hot path (fusion_loss) and of the backbone
mixers (flash_attention, ssd_scan), each with ``kernel.py`` + ``ops.py`` +
a pure-jnp ``ref.py`` oracle."""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas call runs in interpret mode — the one rule every
    kernel wrapper applies.

    On a TPU backend the kernel always compiles, whatever was requested.
    Other backends cannot run Mosaic kernels, so there the kernel is
    interpreted unless the caller asks for ``interpret=False`` explicitly:
    lowering for a described TPU topology without a chip attached, which
    is what tests/test_tpu_compile.py does."""
    if jax.default_backend() == "tpu":
        return False
    return True if interpret is None else bool(interpret)
